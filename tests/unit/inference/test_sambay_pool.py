"""A SambaY stack (``layer_types`` with ``mamba``, ``diff_attention``,
``gmu``, ``cross_attention``: Phi-4-mini-flash-reasoning's kinds) at a small
size on the CPU in float32: the program against the plain reference
(``chipbench/references/phi4_flash.py``: one causal forward, no cache, a mask
for the window, the SSM as the plain recurrence), each mixer alone, the slot
pool's span programs over ring rows, shared rows and SSM state, the
refusals, and the sizes of the published preset.

Weights: the benchmark's own draw (``serve_sambay.sambay_params``: the
layers' published starts) with biases and norm scales moved off 0 and 1, so
that a dropped bias or scale shows. ``TOL``: the reference's float32 limit,
1e-5; the served path reads 1e-6 at worst; a wrong state, ring row, window
or span gives 1e-3 and up."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

from . import _ladder
from ._serving import VOCAB
from ._serving import prompts as _prompts

NAME = "tiny-sambay"
ref, HP, TOL = _ladder.reference(NAME)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


def _tree(model, params):
    return _ladder.tree_of(NAME, model, params)


def _reference_logits(eng, prompt, tokens):
    """The reference's logits of the positions that chose ``tokens``."""
    ids = jnp.asarray([prompt + [int(t) for t in tokens[:-1]]], jnp.int32)
    return ref.forward(_tree(eng.module, eng.params), ids, HP, first=len(prompt) - 1)[0]


def _agrees(model, params, ids):
    res = _ladder.agrees(NAME, model, params, ids)
    assert res["ok"], res["error"]


class TestLadder(_ladder.Ladder):
    """(70 positions wrap the 16-row ring four times; chunks of 12 straddle
    the ring's end: rows 12-15 and 0-7.)"""
    twin = NAME

    def more_refusals(self, eng, sched):
        with pytest.raises(NotImplementedError, match="no int8 tier"):
            eng.module.init_cache(2, 64, quantized=True)


@pytest.mark.parametrize("kinds, windows", [
    (("mamba", ), (0, )),
    (("diff_attention", ), (0, )),
    (("diff_attention", ), (16, )),
    (("diff_attention", ), (12, )),
    (("mamba", "gmu"), (0, 0)),
    (("diff_attention", "cross_attention"), (0, 0)),
], ids=["mamba", "full", "window16", "window12", "gmu", "cross"])
def test_each_mixer_alone_matches_its_reference(tiny, kinds, windows):
    """A stack of the one mixer (a gated memory unit and a cross layer with
    the layer they read below them), 50 positions, so a window of 16 or 12
    bites and the recurrence carries."""
    cfg = dataclasses.replace(tiny[0].cfg, num_layers=len(kinds), layer_types=kinds,
                              layer_windows=windows)
    model = type(tiny[0])(cfg)
    _agrees(model, _ladder.params_of(NAME, model, seed=11),
            jax.random.randint(jax.random.key(2), (2, 50), 0, VOCAB))


def test_layer_table_and_its_rules():
    types, windows = tfm.sambay_layers(32, 2, 512)
    assert types[:18] == ("mamba", "diff_attention") * 9
    assert types[18:] == ("gmu", "cross_attention") * 7
    assert windows == (0, 512) * 8 + (0, ) * 16
    cfg = get_model("tiny-sambay").cfg
    with pytest.raises(ValueError, match="has no mamba layer below it"):
        dataclasses.replace(cfg, num_layers=1, layer_types=("gmu", ), layer_windows=())
    with pytest.raises(ValueError, match="has no full layer below it"):
        dataclasses.replace(cfg, num_layers=2, layer_types=("diff_attention", "cross_attention"),
                            layer_windows=(16, 0))
    with pytest.raises(ValueError, match="do not mix"):
        dataclasses.replace(cfg, num_layers=2, layer_types=("mamba", "full_attention"),
                            layer_windows=())
    with pytest.raises(ValueError, match="layer takes one"):
        dataclasses.replace(cfg, num_layers=1, layer_types=("mamba", ), layer_windows=(16, ))


def test_a_window_that_is_not_the_rings_length(tiny):
    """A window of 12 rests in a ring of 16 rows: the ring then holds keys
    that have left the window, masked by position, in XLA and with the
    kernels injected (which then leave such a ring to XLA)."""
    model = type(tiny[0])(dataclasses.replace(
        tiny[0].cfg, layer_windows=(0, 12, 0, 12, 0, 0, 0, 0)))
    assert [model.cfg.ring_rows(i) for i in (1, 3)] == [16, 16]
    for kernels in (False, True):
        eng = _ladder.engine(NAME, 4, 16, 4, kernels, twin=(model, tiny[1]))
        sched = eng.scheduler()
        prompt = _prompts((45, ), seed=3)[0]
        h = sched.submit(prompt, max_new_tokens=16, collect_logits=True)
        sched.drain()
        res = ref.compare(h.result_logits(), _reference_logits(eng, prompt, h.result()), tol=TOL)
        assert res["ok"], res["error"]


def test_a_cross_layer_reads_the_chunks_own_rows(tiny):
    """One span forward over a chunk from an empty pool gives the full
    forward's logits at every column: the cross layer (and the gated memory
    unit) read what the full layer (and the Mamba layer) below wrote in this
    very forward. The second chunk reads the first's through the pool."""
    model, params = tiny
    ids = jax.random.randint(jax.random.key(4), (1, 40), 0, VOCAB)
    want = ref.forward(_tree(model, params), ids, HP)[0]
    pool = model.init_cache(1, 64)
    got = []
    for start, stop in ((0, 24), (24, 40)):
        n = stop - start
        logits, pool = model.apply_with_cache(
            params, ids[:, start:stop], pool, 0,
            position_ids=(start + jnp.arange(n))[None], write_index=jnp.asarray([start]),
            q_spans=jnp.asarray([n]))
        got.append(logits[0])
    assert ref.compare(jnp.concatenate(got), want, tol=TOL)["ok"]
    kinds = jax.tree_util.tree_leaves(model.cache_kinds())
    assert len(kinds) == len(jax.tree_util.tree_leaves(pool)) == 12


def test_counters_and_gauges_of_attended_rows(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 2 windowed layers (window 16), 2 readers of the shared rows
    (the full layer and the cross layer)."""
    eng = _ladder.engine(NAME, slots=2, config={
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    sched.submit(_prompts((20, ))[0], max_new_tokens=8)
    sched.drain()
    total = eng.telemetry.counter_total
    # chunk 1 (16 columns, not final, alone: K = 1): 16 positions; chunk 2 (4
    # columns, final, K = 4): 20, then 21, 22, 23; one decode sync (K = 4)
    # from 23 positions: 24, 25, 26, 27
    shared = 16 + 20 + 21 + 22 + 23 + 24 + 25 + 26 + 27
    window = 16 + min(20, 16 + 3) + 16 * 3 + 16 * 4
    assert total("serving/attn_rows_shared") == 2 * shared
    assert total("serving/attn_rows_window") == 2 * window
    assert total("serving/cross_decoder_rows_unread") == 16 + 3
    gauges = eng.telemetry.snapshot()["gauges"]
    assert gauges["serving/window_bytes_per_slot"] == sched.cache.window_bytes_per_slot() == 32768
    assert gauges["serving/state_bytes_per_slot"] == sched.cache.state_bytes_per_slot() == 43008
    eng.telemetry.close()


def test_preset_builds_the_published_sizes():
    """3.85 B parameters, 32 layers in the table's order, the whole
    vocabulary; a position costs 5,120 B of rows (layer 17 alone), a slot
    20,971,520 B of rings in its 8 windowed layers and 1,751,040 B of state
    and window in its 9 Mamba layers; gated memory units and cross layers
    hold nothing."""
    from chipbench import cells
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("phi-4-mini-flash-reasoning")
    cfg = whole.cfg
    assert (cfg.layer_types, cfg.layer_windows) == tfm.sambay_layers(32, 2, 512)
    assert (cfg.hidden_size, cfg.ffn_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads,
            cfg.head_size) == (2560, 10240, 200064, 40, 20, 64)
    assert (cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.num_params() == 3_852_562_944 and round(cfg.num_params() / 1e9, 2) == 3.85
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    config = cells.load_config("phi-4-mini-flash-reasoning")
    served = cells.build_model(config, dtype=jnp.bfloat16)
    assert served.cfg.num_params() == cfg.num_params() and served.cfg.max_seq_len == 4096
    pool = jax.eval_shape(lambda: served.init_cache(64, 4096))
    kv = SlotKVCache(pool, 64, 4096, kinds=served.cache_kinds())
    assert kv.bytes_per_token() == 5_120 == config["reference"]["kv_bytes_per_token"]
    assert kv.window_bytes_per_slot() == 8 * 512 * 5_120 <= 8 * (512 + 256 + 256) * 5_120
    assert kv.state_bytes_per_slot() == 9 * 97_280 * 2
    assert (kv.window_bytes_per_slot(), kv.state_bytes_per_slot()) == (
        config["reference"]["window_bytes_per_slot"], config["reference"]["state_bytes_per_slot"])
    assert kv.capacity_bytes() == 64 * (4096 * 5_120 + 20_971_520 + 1_751_040)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert shapes.count((64, 10, 512, 128)) == 16 and shapes.count((64, 10, 4096, 128)) == 2
    assert shapes.count((64, 1, 16, 5120)) == 9 and shapes.count((64, 1, 3, 5120)) == 9
    assert len(shapes) == 36
    for comp in served.cache_kinds():
        assert all(k is None for k in comp[18:])
