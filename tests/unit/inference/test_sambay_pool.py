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
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench.references import phi4_flash as ref
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

TOL = ref.TOL["float32"]
HP = {"eps": 1e-5, "head_dim": 64}
VOCAB = 256


def _params(model, seed=7):
    """The benchmark's draw, biases and norm scales perturbed."""
    from chipbench.jobs.serve_sambay import sambay_params
    root = jax.random.key(seed)

    def perturb(path, leaf):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, int(hashlib.sha256(name.encode()).hexdigest()[:7], 16))
        if name.endswith("['bias']") or name.endswith("['conv_bias']"):
            return 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if name.endswith("['scale']"):
            return 1.0 + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(perturb,
                                            sambay_params(model, seed, jnp.dtype("float32")))


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny-sambay", dtype=jnp.float32)
    return model, _params(model)


def _engine(tiny, slots=4, chunk=16, steps=4, kernels=False, **cb):
    model, params = tiny
    return deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "kernel_inject": kernels, "max_out_tokens": 128,
        "continuous_batching": dict({"enabled": True, "num_slots": slots,
                                     "steps_per_sync": steps, "prefill_chunk": chunk}, **cb)},
        params=params)


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, VOCAB, n)] for n in lengths]


def _tree(model, params):
    return ref.from_tree(params, model.cfg.layer_types, model.cfg.layer_windows)


def _reference_logits(eng, prompt, tokens):
    """The reference's logits of the positions that chose ``tokens``."""
    ids = jnp.asarray([prompt + [int(t) for t in tokens[:-1]]], jnp.int32)
    return ref.forward(_tree(eng.module, eng.params), ids, HP, first=len(prompt) - 1)[0]


def _agrees(model, params, ids):
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, ids)
    want = ref.forward(_tree(model, params), ids, HP)
    res = ref.compare(got.reshape(-1, VOCAB), want.reshape(-1, VOCAB), tol=TOL)
    assert res["ok"], res["error"]


def test_full_forward_matches_the_reference(tiny):
    _agrees(*tiny, jax.random.randint(jax.random.key(1), (2, 70), 0, VOCAB))


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
    _agrees(model, _params(model, seed=11),
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


@pytest.mark.parametrize("slots, chunk, steps, split, kernels", [
    (4, 16, 1, False, False), (4, 16, 4, False, False), (4, 12, 4, False, False),
    (8, 64, 4, True, False), (4, 16, 4, False, True), (8, 64, 4, True, True)])
def test_served_path_matches_the_reference(tiny, slots, chunk, steps, split, kernels):
    """Prefill in chunks (a partial last one; 70 positions wrap the 16-row
    ring four times; chunks of 12 straddle the ring's end: rows 12-15 and
    0-7), then 16 decode steps through the pool at every position,
    neighbours live in other slots, in the whole-block program and in the
    live-rows split, in XLA and through the paged kernels (interpreted)."""
    eng = _engine(tiny, slots, chunk, steps, kernels)
    sched = eng.scheduler()
    assert eng.model_config.attention_impl == ("flash" if kernels else "xla")
    assert sched._splits_chunk(("fused", False, True, chunk, steps)) is split
    prompts = _prompts((37, 70, 9))
    handles = [sched.submit(p, max_new_tokens=16, collect_logits=True) for p in prompts]
    sched.drain()
    for p, h in zip(prompts, handles):
        res = ref.compare(h.result_logits(), _reference_logits(eng, p, h.result()), tol=TOL)
        assert res["ok"] and res["rows"] == 16, res["error"]
    assert sched.state_slots_reset == 3 and sched.radix is None


def test_a_window_that_is_not_the_rings_length(tiny):
    """A window of 12 rests in a ring of 16 rows: the ring then holds keys
    that have left the window, masked by position, in XLA and with the
    kernels injected (which then leave such a ring to XLA)."""
    model = type(tiny[0])(dataclasses.replace(
        tiny[0].cfg, layer_windows=(0, 12, 0, 12, 0, 0, 0, 0)))
    assert [model.cfg.ring_rows(i) for i in (1, 3)] == [16, 16]
    for kernels in (False, True):
        eng = _engine((model, tiny[1]), 4, 16, 4, kernels)
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


def test_a_span_0_slot_is_bit_for_bit_unchanged(tiny):
    """A sync that advances other slots leaves an idle slot's state, window,
    ring and rows exactly as they were: slot 1's, once its request has
    ended, through a neighbour's chunked prefill and both neighbours'
    decode."""
    sched = _engine(tiny, slots=4, chunk=16, steps=4).scheduler()
    a, b, c = _prompts((20, 50, 100))
    long_one = sched.submit(a, max_new_tokens=60)
    short = sched.submit(b, max_new_tokens=6)  # still live when the third is admitted
    late = sched.submit(c, max_new_tokens=8)
    while not short.done:
        sched.step()
    assert sched.cache.state[1] == "free" and late._req.slot == 2 and not late.done
    slot1 = lambda: [np.asarray(leaf[1]) for leaf in jax.tree_util.tree_leaves(sched.cache.pool)]
    before = slot1()
    assert all(np.any(x != 0) for x in before)
    steps = 0
    while not (long_one.done and late.done):
        sched.step()
        steps += 1
    assert steps >= 6 and sched.cache.state[1] == "free"
    for x, y in zip(before, slot1()):
        np.testing.assert_array_equal(x, y)


def test_a_reused_slot_gives_a_fresh_pools_logits(tiny):
    """A new request in a slot that held another starts from a zero state
    and sees none of the ring's old rows: its logits are a fresh pool's, bit
    for bit; one prompt twice is served cold twice and counted."""
    prompt = _prompts((40, ), seed=5)[0]
    fresh = _engine(tiny, slots=2, chunk=16).scheduler()
    want = fresh.submit(prompt, max_new_tokens=8, collect_logits=True)
    fresh.drain()
    used = _engine(tiny, slots=2, chunk=16).scheduler()
    for p in _prompts((33, 61), seed=6):
        used.submit(p, max_new_tokens=10)
    used.drain()
    for _ in range(2):
        got = used.submit(prompt, max_new_tokens=8, collect_logits=True)
        used.drain()
        np.testing.assert_array_equal(got.result_logits(), want.result_logits())
    assert used.state_slots_reset == 4 and used.prefix_cache_state_bypass == 4


@pytest.mark.parametrize("overrides, message", [
    ({"spec_tokens": 2}, "speculative verify"),
    ({"max_extents": 2}, "extent chains"),
    ({"seq_parallel_min_tokens": 64}, "sequence-parallel prefill"),
    ({"prefix_store": object()}, "tier demotion"),
    ({"allow_lossy_kv": True}, "lossy KV windows"),
    ({"kv_cache_dtype": "int8"}, "an int8 KV pool"),
    ({"adapter_store": object()}, "adapters"),
])
def test_what_a_pool_with_ring_or_shared_rows_refuses(tiny, overrides, message):
    eng = _engine(tiny, kernels=True)
    with pytest.raises(ValueError, match="holds recurrent state, ring rows, rows that layers "
                                         "share.*" + message):
        eng.scheduler(**overrides)


def test_the_other_refusals(tiny):
    """Migration between replicas, the static-batch cache, int8 weights, a
    tensor-parallel pool; the fused decode gate declines by kind."""
    model, params = tiny
    eng = _engine(tiny)
    sched = eng.scheduler()
    with pytest.raises(ValueError, match="cannot migrate between replicas"):
        sched.migrate_out(None, "key", None)
    with pytest.raises(ValueError, match="continuous-batching scheduler"):
        eng.generate([[1, 2, 3]], max_new_tokens=2)
    assert any("cross_attention, diff_attention, gmu, mamba" in r
               for r in sched._fused_block_reasons)
    with pytest.raises(ValueError, match="served in its float dtype"):
        deepspeed_tpu.init_inference(model, config={"dtype": "int8"}, params=params)
    with pytest.raises(NotImplementedError, match="span programs"):
        model.apply_with_cache(params, jnp.zeros((2, 4), jnp.int32), model.init_cache(2, 64), 0)
    with pytest.raises(NotImplementedError, match="no int8 tier"):
        model.init_cache(2, 64, quantized=True)
    from deepspeed_tpu.comm import comm
    comm._state["mesh"] = None
    comm.initialize_mesh(tensor=2)
    tp = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "continuous_batching": {"enabled": True, "num_slots": 2}},
        params=params)
    with pytest.raises(ValueError, match="a tensor-parallel pool"):
        tp.scheduler()


def test_counters_and_gauges_of_attended_rows(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 2 windowed layers (window 16), 2 readers of the shared rows
    (the full layer and the cross layer)."""
    model, params = tiny
    eng = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "max_out_tokens": 128,
        "continuous_batching": {"enabled": True, "num_slots": 2, "steps_per_sync": 4,
                                "prefill_chunk": 16},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}}, params=params)
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


def _digest(tree):
    items = [(jax.tree_util.keystr(p), tuple(getattr(leaf, "shape", ())),
              str(getattr(leaf, "dtype", leaf)))
             for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16], len(items)


@pytest.mark.parametrize("name, overrides, params, pool, kinds", [
    ("gpt2-large", {}, ("eecb65f1ac785bed", 20), ("2ace4ad8ee7b3d07", 1), ("7efe6c18a76c403f", 1)),
    ("gpt2-large", {"scan_layers": False}, ("9c4584445a884b9c", 580), ("253ab50641bbfc93", 36),
     ("70973dd72aba3407", 36)),
    ("llama2-7b", {"scan_layers": False}, ("413338bc326a560b", 291), ("e55ad93897f78f38", 64),
     ("7d581e97c6a287c5", 64)),
    # PR 55: the latent leaves rest position-last, (2, 1, 320, 64), declared
    # "columns" (30bae00ac1380acc / 70973dd72aba3407 while they were rows of
    # 320; tiny-mla-moe's 3b84258aa35bd04e / 7efe6c18a76c403f likewise)
    ("mistral-small-4-119b", {"scan_layers": False}, ("d378c3c33fb6535c", 579),
     ("2b3563502a59eee8", 36), ("5bb7fe90c6ddf5e9", 36)),
    # PR 42: its state leaves rest two heads a lane row, (2, 15, 96, 384)
    # (1c5f151820235ef8 while they were (2, 30, 96, 192))
    ("olmo-hybrid-7b", {}, ("7bf46abc96dcf40d", 475), ("df45013d6f63db31", 64),
     ("72fa486ac50a3d4b", 64)),
    ("tiny-hybrid", {}, ("fe34f5c3f89eebab", 62), ("19da4dfe885cea0f", 8), ("2cb26fe38a1746cd", 8)),
    ("tiny-mla-moe", {}, ("585ee1651c3e6625", 19), ("52eea5a78f13e3c5", 1), ("d70a613fd25d4c61", 1)),
])
def test_models_without_new_layer_types_build_what_they_built(name, overrides, params, pool, kinds):
    """The parameter tree, the cache tree and the declared kinds (paths,
    shapes, dtypes; digests taken at the parent commit a87638f with this very
    function): models without the new kinds build what they built, so their
    step programs are the parent's and its compile-cache entries are hit."""
    model = get_model(name, **overrides)
    assert _digest(jax.eval_shape(model.init_params, jax.random.key(0))) == params
    assert _digest(jax.eval_shape(lambda: model.init_cache(2, 64))) == pool
    assert _digest(model.cache_kinds()) == kinds
