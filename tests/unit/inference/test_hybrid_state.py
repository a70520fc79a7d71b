"""A model whose slots hold recurrent state beside K/V rows (``layer_types``
with ``linear_attention``: the gated delta rule), at a small size on the CPU
in float32 unless said: the program against the plain reference
(``chipbench/references/olmo_hybrid.py``, token by token, no cache), the
scan against the recurrence, the slot pool's span programs, the refusals,
and the sizes of the published preset.

Weights: the benchmark's own draw (``serve_hybrid.hybrid_params``: unit
embedding, output norms at 0.3, the gates' published start), a forward that
does not amplify a rounding. ``TOL``: the reference's float32 limit, 1e-5;
the served path reads 1e-6 at worst; a wrong state, window or span gives
0.01 and up. (With flax's own start, norm scales 1 over a 0.02 embedding, the
same program reads 1.1e-5 and the reference itself 9e-6 against a float64
forward: such a network amplifies float32's noise.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

from . import _ladder
from ._serving import prompts as _prompts

NAME = "tiny-hybrid"
ref, HP, TOL = _ladder.reference(NAME)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


@pytest.fixture(scope="module")
def published_heads():
    """``tiny-hybrid``'s hidden size, depth and vocabulary around linear
    layers whose heads keep the published shape (``dk`` 96, ``dv`` 192, two
    of them: one packed unit of 384 lanes), so that the decode column's
    state update is the Pallas kernel (``ops/pallas/gdn_step.py``, interpret
    mode here) and the leaf at rest the packed one."""
    base = _ladder.built(NAME)[0]
    model = type(base)(dataclasses.replace(
        base.cfg, linear_num_heads=2, linear_key_head_dim=96, linear_value_head_dim=192))
    return model, _ladder.params_of(NAME, model)


def _reference_logits(eng, prompt, tokens):
    """The reference's logits of the positions that chose ``tokens``."""
    ids = jnp.asarray([prompt + [int(t) for t in tokens[:-1]]], jnp.int32)
    return ref.forward(_ladder.tree_of(NAME, eng.module, eng.params), ids, HP,
                       first=len(prompt) - 1)[0]


class TestLadder(_ladder.Ladder):
    """(150 = 2 x 64 + 22 = 128 + 22 = 9 x 16 + 6: a partial last chunk at
    every chunk size; the idle slot sits through eight chunks of a neighbour's
    prefill.)"""
    twin = NAME

    def served_pool(self, case, sched):
        # tiny-hybrid's heads (8 x 16) do not tile: the definition serves them
        assert sched.gdn_step_programs["xla"] > 0 and sched.gdn_step_programs["kernel"] == 0

    def more_refusals(self, eng, sched):
        """The way in of a migration, the engine's own gate, scanned layers."""
        with pytest.raises(ValueError, match="cannot migrate between replicas"):
            sched.admit_migration(None)
        assert any("layer_types" in r for r in eng._fused_decode_eligible().reasons)
        with pytest.raises(ValueError, match="requires scan_layers=False"):
            dataclasses.replace(eng.module.cfg, scan_layers=True)


@pytest.mark.parametrize("length", [1, 63, 64, 150, 200])
def test_chunked_scan_matches_the_recurrence(length):
    """Lengths that are no multiple of 64, from a carried state; a token with
    beta 0 and g 0 in the middle leaves the state as it is."""
    B, n, dk, dv = 2, 3, 8, 16
    ks = jax.random.split(jax.random.key(length), 6)
    q = jax.random.normal(ks[0], (B, n, length, dk))
    k = jax.random.normal(ks[1], (B, n, length, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, n, length, dv))
    g = -1.6 * jax.random.uniform(ks[3], (B, n, length))
    beta = 2.0 * jax.random.uniform(ks[4], (B, n, length))
    g, beta = g.at[:, :, length // 2].set(0.0), beta.at[:, :, length // 2].set(0.0)
    S0 = jax.random.normal(ks[5], (B, n, dk, dv))
    o_scan, S_scan = tfm.gated_delta_chunked(S0, q, k, v, g, beta)
    S, outs = S0, []
    for t in range(length):
        before = S
        o, S = tfm.gated_delta_step(S, q[:, :, t], k[:, :, t], v[:, :, t], g[:, :, t],
                                    beta[:, :, t])
        if t == length // 2:
            np.testing.assert_array_equal(np.asarray(S), np.asarray(before))
        outs.append(o)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert rel(o_scan, jnp.stack(outs, axis=2)) < 5e-6 and rel(S_scan, S) < 5e-6


@pytest.mark.parametrize("slots, chunk, steps", [(4, 16, 4), (8, 64, 1)])
def test_published_heads_are_served_through_the_kernel(published_heads, slots, chunk, steps):
    """The same streams at the published head shape: the decode column's
    update is the in-place kernel on the packed leaf, the chunk's scan
    converts its slot's state on load and store, and the logits are the
    reference's; the counters say ``kernel`` here and ``xla`` for
    ``tiny-hybrid``'s heads, which do not tile."""
    eng = _ladder.engine(NAME, slots, chunk, steps, twin=published_heads, fresh=True)
    sched = eng.scheduler()
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(sched.cache.pool)]
    assert shapes.count((slots, 1, 96, 384)) == 3
    prompts = _prompts((37, 150, 70))
    handles = [sched.submit(p, max_new_tokens=12, collect_logits=True) for p in prompts]
    sched.drain()
    for p, h in zip(prompts, handles):
        res = ref.compare(h.result_logits(), _reference_logits(eng, p, h.result()), tol=TOL)
        assert res["ok"], res["error"]
    assert sched.state_slots_reset == 3
    assert sched.gdn_step_programs["kernel"] > 0 and sched.gdn_step_programs["xla"] == 0


def test_the_kernel_serves_the_definitions_streams(published_heads):
    """Kernel against definition on one model: ``attention_impl`` flash takes
    the kernel, the XLA attention fallback keeps ``gated_delta_step``; same
    tokens, logits within the reference's limit of each other."""
    prompts = _prompts((23, 41), seed=3)
    got = {}
    for inject in (True, False):
        eng = _ladder.engine(NAME, kernels=inject, twin=published_heads, fresh=True)
        sched = eng.scheduler()
        handles = [sched.submit(p, max_new_tokens=24, collect_logits=True) for p in prompts]
        sched.drain()
        assert (sched.gdn_step_programs["kernel"] > 0) is inject
        got[inject] = [(list(h.result()), h.result_logits()) for h in handles]
    for (toks, logits), (want_toks, want_logits) in zip(got[True], got[False]):
        assert toks == want_toks
        assert ref.compare(logits, want_logits, tol=TOL)["ok"]


def test_mamba2_programs_are_counted_by_their_one_token_update():
    """``ssd_step_programs``, beside ``gdn_step_programs``: a served
    ``tiny-nemotron-h`` (cut to two layers) has a state leaf (4 heads of 8 x
    16) that does not tile, so
    every program with a decode column counts ``xla`` (``serving/
    ssd_step_xla_programs``); a program traced over a leaf that tiles (two
    heads of 64 x 128) counts ``kernel``, once, when it is built."""
    base = get_model("tiny-nemotron-h", dtype=jnp.float32)
    # (two of its eight layers: the programs build in a quarter of the time)
    model = type(base)(dataclasses.replace(
        base.cfg, num_layers=2, layer_types=("mamba2", "attention"), num_experts=0))
    eng = _ladder.engine(NAME, slots=2, steps=2, fresh=True,
                         twin=(model, jax.jit(model.init_params)(jax.random.key(7))))
    sched = eng.scheduler()
    sched.submit(_prompts((9, ))[0], max_new_tokens=4)
    sched.drain()
    served = dict(sched.ssd_step_programs)
    assert served["xla"] > 0 and served["kernel"] == 0
    assert sched.gdn_step_programs == {"kernel": 0, "xla": 0}
    tiling = type(model)(dataclasses.replace(
        model.cfg, ssm_num_heads=2, ssm_head_dim=64, ssm_state_size=128, ssm_groups=1,
        attention_impl="flash", max_seq_len=32))
    params = jax.jit(tiling.init_params)(jax.random.key(0))
    at = jnp.asarray([3, 0], jnp.int32)
    step = jax.jit(lambda params, pool: tiling.apply_with_cache(
        params, jnp.asarray([[5], [7]], jnp.int32), pool, 0, position_ids=at[:, None],
        write_index=at, q_spans=jnp.ones(2, jnp.int32)))
    for _ in range(2):  # the second call traces nothing
        sched._run_program(step, (params, tiling.init_cache(2, 32)))
    assert sched.ssd_step_programs == {"kernel": 1, "xla": served["xla"]}


def test_one_prompt_twice_is_served_cold_twice(tiny):
    """The radix cache is off for a pool with state: the second request finds
    no prefix, gives the same logits, and the lookups not made are counted."""
    sched = _ladder.engine(NAME, slots=4, chunk=16).scheduler()
    prompt = _prompts((50, ), seed=7)[0]
    one = sched.submit(prompt, max_new_tokens=8, collect_logits=True)
    sched.drain()
    two = sched.submit(prompt, max_new_tokens=8, collect_logits=True)
    sched.drain()
    np.testing.assert_array_equal(one.result_logits(), two.result_logits())
    assert sched.radix is None and sched.prefix_cache_state_bypass == 2
    assert _ladder.engine(NAME, prefix_cache=False).scheduler().prefix_cache_state_bypass == 0


@pytest.mark.parametrize("heads, widens, at_worst", [("tiny", 1.5, 0.02),
                                                     ("published_heads", 4.0, 0.05)])
def test_bf16_state_at_rest_over_512_decode_steps(request, heads, widens, at_worst):
    """A float32 program over a pool at rest in bf16 (``kv_cache_dtype``:
    state, window and rows), so that the rounding at rest is all that
    differs from the reference: the state is rounded once a token, 512
    times. The band: a position reads 0.003 in the median and under 0.01
    (bf16 keeps 8 bits; the decay forgets old roundings, a head's slowest
    here keeps a few hundred positions), and it does not widen: the last 64
    positions' median stays under 1.5 times the first 64's, every position
    under 0.02. A float32 pool reads 1e-6 on the same request.
    ``tiny-hybrid``'s heads go through the definition; the published head
    shape goes through the kernel and reads the same 0.0030 first. Its two
    heads of ``dk`` 96 keep more positions, so the medians of 64 positions
    level off later (0.0030, 0.0034, 0.0040, 0.0044, then 0.0043 to 0.0046)
    and a greedy stream that a rounding tie sends another way reads
    otherwise: the kernel 0.0073 last and 0.023 at worst, the DEFINITION
    through the XLA attention fallback 0.0098 and 0.038 on the same model
    and prompt. Held under 4 times the first and 0.05: a wrong state reads
    0.1 and up, and the float32 pool holds the kernel to 1e-5."""
    model, params = request.getfixturevalue(heads)
    model = type(model)(dataclasses.replace(model.cfg, max_seq_len=768))
    prompt = _prompts((24, ), seed=9)[0]
    medians = {}
    for at_rest in ("bfloat16", "auto"):
        eng = _ladder.engine(NAME, slots=2, twin=(model, params), fresh=True,
                             config={"max_out_tokens": 768}, kv_cache_dtype=at_rest)
        sched = eng.scheduler()
        want_dtype = jnp.dtype(jnp.bfloat16 if at_rest == "bfloat16" else jnp.float32)
        assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(sched.cache.pool)} == {want_dtype}
        h = sched.submit(prompt, max_new_tokens=513, collect_logits=True)
        sched.drain()
        err = np.asarray(ref.position_errors(h.result_logits(),
                                             _reference_logits(eng, prompt, h.result())))
        assert err.shape == (513, )
        assert (sched.gdn_step_programs["kernel"] > 0) is (heads == "published_heads")
        medians[at_rest] = (np.median(err[:64]), np.median(err[-64:]), err.max())
    first, last, worst = medians["bfloat16"]
    assert 5e-4 < first and last < widens * first and worst < at_worst, medians
    assert medians["auto"][2] < TOL, medians


def test_preset_builds_the_published_sizes():
    """7.43 B parameters whole; the cut (16 layers, four whole periods, the
    whole vocabulary) 4,100,788,944; a position costs 61,440 B of rows in
    its 4 full-attention layers, a slot 14,100,480 B of state in its 12
    linear-attention layers."""
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("olmo-hybrid-7b")
    cfg = whole.cfg
    assert cfg.num_layers == 32 and cfg.layer_types == (
        ("linear_attention", ) * 3 + ("full_attention", )) * 8
    assert (cfg.hidden_size, cfg.ffn_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads,
            cfg.head_size) == (3840, 11008, 100352, 30, 30, 128)
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel, cfg.linear_neg_eigval) == (30, 96, 192, 4, True)
    assert cfg.num_params() == 7_430_870_688 and round(cfg.num_params() / 1e9, 2) == 7.43
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    from chipbench import cells
    cut = cells.build_model(cells.load_config("olmo-hybrid-7b"), dtype=jnp.bfloat16)
    assert cut.cfg.num_params() == 4_100_788_944 and cut.cfg.num_layers == 16
    pool = jax.eval_shape(lambda: cut.init_cache(64, 1024))
    kv = SlotKVCache(pool, 64, 1024, kinds=cut.cache_kinds())
    assert kv.bytes_per_token() == 61_440 and kv.state_bytes_per_slot() == 14_100_480
    assert kv.capacity_bytes() == 64 * (1024 * 61_440 + 14_100_480)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    # two heads side by side in a state's lanes: 384, not 192 padded to 256
    assert shapes.count((64, 15, 96, 384)) == 12 and shapes.count((64, 1, 3, 11520)) == 12
    assert shapes.count((64, 30, 1024, 128)) == 8


@pytest.mark.parametrize("name, unrolled, leaves, geometry", [
    ("gpt2-large", False, [(36, 2, 20, 64, 128)], "packed"),
    ("gpt2-large", True, [(2, 20, 64, 128)] * 36, "packed"),
    ("llama2-7b", False, [(32, 2, 32, 64, 128)] * 2, "split"),
    ("llama2-7b", True, [(2, 32, 64, 128)] * 64, "split"),
    ("mistral-small-4-119b", False, [(36, 2, 1, 320, 64)], "latent"),
    ("mistral-small-4-119b", True, [(2, 1, 320, 64)] * 36, "latent"),
])
def test_models_without_layer_types_build_the_cache_they_built(name, unrolled, leaves, geometry):
    """``init_cache``'s leaves and ``kv_pool_geometry`` as the parent commit
    (f7774de) built them, every leaf declared as rows; the int8 tier keeps
    its scale leaf last. But the latent leaf, position-last since PR 55 and
    declared as columns: a position's 320 values down a column, the same
    bytes a token."""
    model = get_model(name, scan_layers=not unrolled)
    pool = jax.eval_shape(lambda: model.init_cache(2, 64))
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)] == leaves
    assert tfm.kv_pool_geometry(model.cfg, pool) == geometry
    assert set(jax.tree_util.tree_leaves(model.cache_kinds())) == {
        "columns" if geometry == "latent" else "rows"}
    assert (jax.tree_util.tree_structure(model.cache_kinds())
            == jax.tree_util.tree_structure(pool))
    if geometry != "latent":
        q = jax.tree_util.tree_leaves(jax.eval_shape(lambda: model.init_cache(2, 64,
                                                                              quantized=True)))
        assert q[0].dtype == jnp.int8 and q[-1].dtype == jnp.float16
        assert q[-1].shape[-3:] == (1, 64, 1)
