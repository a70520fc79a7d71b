"""Tensor-sharded decode: tp>1 through the continuous-batching scheduler.

The contract under test is the bitwise-TP serving layout
(``TransformerConfig.bitwise_tp``, set by the engine whenever the mesh's
``tensor`` axis exceeds 1): every cross-shard transfer is an all-gather
(concatenation), never a partial-sum reduction, so a tp=2 scheduler's
logits — greedy or sampled, radix hit or cold, XLA or Pallas attention,
fp32 or int8 KV — are BIT-identical to the tp=1 scheduler's on the same
weights. Runs on the conftest-forced 8-virtual-CPU-device mesh (the
``XLA_FLAGS=--xla_force_host_platform_device_count`` lane).
"""

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm

PROMPTS = [[5, 6, 7, 8, 9], [10, 11, 12], [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]]


def make_engine(tp, params=None, model="tiny", **cfg_extra):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    cb = {"enabled": True, "num_slots": 4, "collect_logits": True}
    cb.update(cfg_extra.pop("continuous_batching", {}))
    cfg = {"dtype": "float32", "tensor_parallel": {"tp_size": tp},
           "continuous_batching": cb}
    cfg.update(cfg_extra)
    return deepspeed_tpu.init_inference(model, config=cfg, params=params)


def run_requests(eng, requests):
    """Submit all, drain, return [(tokens, logits)] per request."""
    sched = eng.scheduler()
    handles = [sched.submit(p, collect_logits=True, **kw) for p, kw in requests]
    return [(h.result(), h.result_logits()) for h in handles]


def assert_bit_identical(a, b):
    for (ta, la), (tb, lb) in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
        assert la.shape == lb.shape
        assert np.array_equal(la, lb), \
            f"logits diverge: max abs diff {np.abs(la - lb).max()}"


@pytest.fixture(scope="module")
def tp1_state():
    eng = make_engine(1)
    params = jax.device_get(eng.params)
    return params


GREEDY = [(p, {"max_new_tokens": 8}) for p in PROMPTS]
SAMPLED = [(p, {"max_new_tokens": 8, "do_sample": True, "temperature": 0.9,
                "top_k": 7, "top_p": 0.9, "seed": 100 + i})
           for i, p in enumerate(PROMPTS)]


def test_tp2_greedy_bit_identical_to_tp1(tp1_state):
    """Chunked-prefill + fused decode under tp=2: tokens AND logits match
    tp=1 bit-for-bit (the all-gather layout admits no reduction-order
    drift)."""
    params = tp1_state
    ref = run_requests(make_engine(1, params), GREEDY)
    got = run_requests(make_engine(2, params), GREEDY)
    assert_bit_identical(ref, got)


def test_tp2_sampled_bit_identical_to_tp1(tp1_state):
    """Sampling (temperature/top-k/top-p over the vocab-sharded logits)
    stays bit-identical: the filtered distribution and the fold_in keys see
    identical f32 logits on every shard."""
    params = tp1_state
    ref = run_requests(make_engine(1, params), SAMPLED)
    got = run_requests(make_engine(2, params), SAMPLED)
    assert_bit_identical(ref, got)


def test_tp2_radix_hit_bit_identical(tp1_state):
    """A tp=2 prefix-cache hit (copy_slot on the sharded pool + suffix
    chunks) replays the cold path bit-for-bit, same as tp=1."""
    params = tp1_state
    shared = list(range(1, 65))  # one full chunk of shared prefix
    reqs = [(shared + [70 + i], {"max_new_tokens": 6}) for i in range(3)]

    def run(tp):
        eng = make_engine(tp, params)
        sched = eng.scheduler()
        out = []
        for p, kw in reqs:  # sequential: later requests hit the radix trie
            h = sched.submit(p, collect_logits=True, **kw)
            out.append((h.result(), h.result_logits()))
        assert sched.radix.hits >= 1, "stream never hit the prefix cache"
        return out

    assert_bit_identical(run(1), run(2))


def test_tp2_speculative_matches_tp1(tp1_state):
    """Self-speculative verify steps under tp=2 (span program over the
    sharded pool) commit the same drafts as tp=1, with logits within 1e-6
    absolute: a float32 model, about ten units in the last place of its
    largest logits. Not bit-identity: the verify step's span program over the
    sharded pool differs from tp=1 by one unit in the last place at
    |x| ~ 0.13 (8.94e-08 measured, on every tree since PR 21), and ROADMAP D6
    keeps bit-identity only where it costs nothing."""
    params = tp1_state
    rep = [7, 8, 9] * 8  # repetitive: the prompt-lookup drafter fires
    reqs = [(rep, {"max_new_tokens": 10})]
    cb = {"continuous_batching": {"enabled": True, "num_slots": 4,
                                  "collect_logits": True, "spec_tokens": 4}}

    def run(tp):
        eng = make_engine(tp, params, **cb)
        out = run_requests(eng, reqs)
        assert eng.scheduler().spec_steps >= 1, "speculation never dispatched"
        return out

    for (t1, l1), (t2, l2) in zip(run(1), run(2)):
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_allclose(l2, l1, rtol=0, atol=1e-6)


def test_tp2_flash_kernel_path_bit_identical(tp1_state):
    """kernel_inject (Pallas paged kernels, shard_mapped over ``tensor``
    with the shard-local KV block walk) under tp=2 == tp=1 bit-for-bit."""
    comm._state["mesh"] = None
    eng = make_engine(1, None, kernel_inject=True)
    params = jax.device_get(eng.params)
    ref = run_requests(eng, GREEDY)
    got = run_requests(make_engine(2, params, kernel_inject=True), GREEDY)
    assert_bit_identical(ref, got)


def test_tp2_int8_kv_tier_bit_identical_within_tier(tp1_state):
    """The int8 paged-KV tier under tp=2 (int8 k/v leaves head-sharded,
    per-token-row scale leaves replicated) == the tp=1 int8 tier
    bit-for-bit; the joint K/V row scale is a cross-head max — an exact
    comparison reduction, no arithmetic drift."""
    params = tp1_state
    cb = {"continuous_batching": {"enabled": True, "num_slots": 4,
                                  "collect_logits": True,
                                  "kv_cache_dtype": "int8"}}
    ref = run_requests(make_engine(1, params, **cb), GREEDY)
    got = run_requests(make_engine(2, params, **cb), GREEDY)
    assert_bit_identical(ref, got)


def test_tp2_pool_sharded_and_layout_pinned(tp1_state):
    """The slot pool's kv-head axis is actually sharded over ``tensor``,
    and the step programs PIN that layout: after a full serve cycle every
    pool leaf still carries the _init_cache sharding (GSPMD must not
    re-layout the donated pool between program variants)."""
    params = tp1_state
    eng = make_engine(2, params)
    sched = eng.scheduler()

    def kv_specs():
        # stacked layout: (L, N, kv, S, hd) — kv axis is ndim-3
        return [leaf.sharding.spec for leaf in
                jax.tree_util.tree_leaves(sched.cache.pool)]

    before = kv_specs()
    assert any("tensor" in str(spec) for spec in before), before
    for p, kw in GREEDY:
        sched.submit(p, **kw).result()
    assert kv_specs() == before, "step programs re-laid-out the pool"
    assert sched.tp_size == 2


def test_tp2_kv_head_divisibility_fallback(tp1_state):
    """Head counts % tp != 0: the engine falls back to FULLY REPLICATED
    serving — unevenly-padded head shards measurably re-split contractions
    (ulp drift), so tp>1 either shards bit-identically or replicates
    loudly. The ready line says so, and serving matches tp=1 bit-for-bit
    (trivially: nothing shards)."""
    overrides = dict(hidden_size=96, num_heads=6, num_kv_heads=3,
                     intermediate_size=128)
    from deepspeed_tpu.models import get_model

    def run(tp, params=None):
        comm._state["mesh"] = None
        from deepspeed_tpu.telemetry import set_sink
        set_sink(None)
        model = get_model("tiny", **overrides)
        eng = deepspeed_tpu.init_inference(model, config={
            "dtype": "float32", "tensor_parallel": {"tp_size": tp},
            "continuous_batching": {"enabled": True, "num_slots": 2,
                                    "collect_logits": True}}, params=params)
        return eng, jax.device_get(eng.params)

    eng1, params = run(1)
    ref = run_requests(eng1, GREEDY[:2])
    eng2, _ = run(2, params)
    assert "REPLICATED fallback" in eng2._shard_desc()
    assert eng2.model_config.bitwise_tp is False
    specs = [str(leaf.sharding.spec) for leaf in
             jax.tree_util.tree_leaves(eng2.scheduler().cache.pool)]
    assert all("tensor" not in s for s in specs), specs
    got = run_requests(eng2, GREEDY[:2])
    assert_bit_identical(ref, got)


def test_tp2_ready_line_reports_real_shard_config(tp1_state):
    """The `InferenceEngine ready:` surface tells the truth about the
    shard config — the effective mesh tensor degree and the layout, not
    the config knob."""
    eng = make_engine(2, tp1_state)
    desc = eng._shard_desc()
    assert "tp=2" in desc and "bitwise all-gather layout" in desc
    assert "kv_heads sharded /2" in desc
    assert "tp=1" in make_engine(1, tp1_state)._shard_desc()


def test_int8_weights_tp2_fused_qkv_falls_back_loudly(caplog, tp1_state):
    """dtype=int8 under an effective tensor degree > 1 disables the fused
    [q;k;v] matmul with a logged, documented reason (the fused column axis
    cannot shard across component boundaries), serves through the SPLIT
    column-sharded projections, and reports the gating outcome on the
    ready line. The decision follows the MESH, not the config's tp_size."""
    import logging
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    ds_logger = logging.getLogger("DeepSpeedTPU")
    ds_logger.propagate = True  # caplog listens on root; restored below
    try:
        with caplog.at_level(logging.WARNING, logger="DeepSpeedTPU"):
            eng = deepspeed_tpu.init_inference("tiny-gpt2", config={
                "dtype": "int8", "tensor_parallel": {"tp_size": 2},
                "continuous_batching": {"enabled": True, "num_slots": 2}})
    finally:
        ds_logger.propagate = False
    assert eng.model_config.int8_fused_qkv is False
    assert any("fused-qkv decode disabled under tensor parallelism" in r.message
               for r in caplog.records)
    desc = eng._shard_desc()
    assert "int8_fused_qkv=off" in desc and "component boundaries" in desc
    # and it actually serves
    out = eng.scheduler().submit([5, 6, 7, 8], max_new_tokens=4).result()
    assert out.shape == (4, )
    # tp=1 keeps the fused path on
    comm._state["mesh"] = None
    set_sink(None)
    eng1 = deepspeed_tpu.init_inference("tiny-gpt2", config={"dtype": "int8"})
    assert eng1.model_config.int8_fused_qkv is True
    assert "int8_fused_qkv=on" in eng1._shard_desc()


def test_training_models_unaffected_by_bitwise_flag():
    """bitwise_tp defaults False: a model built outside the inference
    engine keeps the full Megatron row/col rules (training perf contract —
    row-parallel shards must not silently vanish)."""
    from deepspeed_tpu.models import get_model
    model = get_model("tiny")
    assert model.cfg.bitwise_tp is False
    rules = dict(model.tp_rules())
    o_rule = rules[r"attn/o_proj/kernel$"]
    assert "tensor" in str(o_rule)
