"""Fused llama-family decode-block tests (PR 17).

The fused per-layer decode kernels (``ops/pallas/decode_block.py``) now
cover RoPE, RMSNorm, gated MLPs (SwiGLU/GeGLU), and GQA — the llama
family — and the continuous-batching scheduler dispatches whole fused
blocks through ``CausalLMModel.fused_paged_step`` on its hot path
(``fused_block``/``spec_block`` step programs). These tests pin:

- model-level parity: ``fused_paged_step`` vs the per-projection
  ``apply_with_cache`` across RoPE x norm x activation x GQA x int8-KV
  x column width, on the SAME paged slot pool;
- scheduler-level parity: greedy and seeded-sampled token streams
  through fused-block step programs match the per-projection programs,
  with radix prefix reuse and speculation on top;
- the O(1)-compiled-programs guard (jax.monitoring: zero new XLA
  programs on a fresh request mix after warmup);
- the structured eligibility gate: a concrete reason per excluded
  condition, surfaced on the engine and the scheduler;
- capacity-meter registration of the new program kinds.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import comm

from ._reference_path import REASON, per_projection_engine

PROMPTS = [[5, 6, 7, 8, 9], [10, 11, 12]]


def make_engine(model="tiny", params=None, **cfg):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    config = {"dtype": "float32"}
    config.update(cfg)
    return deepspeed_tpu.init_inference(model, config=config, params=params)


def make_fused_engine(params=None, num_slots=4, collect_logits=False, **cfg):
    """int8 kernel-inject engine on the llama-shaped tiny preset — the
    configuration the fused decode-block gate admits."""
    cfg.setdefault("dtype", "int8")
    cfg.setdefault("kernel_inject", True)
    cfg["continuous_batching"] = {"enabled": True, "num_slots": num_slots,
                                  "collect_logits": collect_logits}
    return make_engine(params=params, **cfg)


@pytest.fixture(scope="module")
def baseline():
    eng = make_engine()
    params = jax.device_get(eng.params)
    out = eng.generate(PROMPTS, max_new_tokens=8)
    return params, out


# --------------------------------------------------------- model-level parity
def _quantized_model(**kw):
    """fp32 init -> group-quantized int8 model, eager params."""
    from deepspeed_tpu.models.transformer import TransformerConfig, CausalLMModel
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, intermediate_size=128, dtype=jnp.float32,
                scan_layers=False, attention_impl="flash", int8_fused_qkv=True)
    base.update(kw)
    model = CausalLMModel(TransformerConfig(**base))
    params = model.init_params(jax.random.PRNGKey(0))
    qmodel = CausalLMModel(dataclasses.replace(model.cfg, int8_weights=True))
    qparams = jax.tree_util.tree_map(jnp.asarray, qmodel.quantize_params(params))
    return qmodel, qparams


_SHAPES = {
    "llama": dict(num_kv_heads=2, pos_embedding="rope", norm="rmsnorm",
                  activation="swiglu"),
    "gpt2": dict(pos_embedding="learned", norm="layernorm", activation="gelu"),
    "geglu-gqa": dict(num_kv_heads=1, pos_embedding="rope", norm="rmsnorm",
                      activation="geglu"),
    "rope-ln-bias": dict(pos_embedding="rope", norm="layernorm",
                         activation="gelu_exact"),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_fused_paged_step_parity_matrix(shape):
    """``fused_paged_step`` (3 fused kernels/layer) == per-projection
    ``apply_with_cache`` on the same slot pool: logits to float32 rounding,
    greedy argmax identical, committed KV rows byte-stable, for both KV
    dtypes and both decode (C=1) and chunk (C=4) column widths."""
    qmodel, qparams = _quantized_model(**_SHAPES[shape])
    cfg = qmodel.cfg
    for quant_kv in (False, True):
        for C in (1, 4):
            N, S = 3, 64
            pool = qmodel.init_cache(N, S, quantized=quant_kv)
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (N, C)), jnp.int32)
            lengths = jnp.asarray([0, 5, 17], jnp.int32)
            spans = jnp.asarray([C, max(C - 1, 1), 1], jnp.int32)
            pos = lengths[:, None] + jnp.arange(C)[None, :]
            ref_logits, ref_pool = qmodel.apply_with_cache(
                qparams, ids, pool, 0, position_ids=pos,
                write_index=lengths, q_spans=spans)
            got_logits, got_pool = qmodel.fused_paged_step(
                qparams, ids, pool, pos, lengths, spans)
            rl = np.asarray(ref_logits, np.float32)
            gl = np.asarray(got_logits, np.float32)
            live = np.arange(C)[None, :] < np.asarray(spans)[:, None]
            tag = (shape, quant_kv, C)
            assert np.abs(rl - gl)[live].max() < 1e-4, tag
            assert (rl.argmax(-1) == gl.argmax(-1))[live].all(), tag
            cache_err = max(
                float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).max())
                for ca, cb in zip(ref_pool, got_pool)
                for a, b in zip(ca, cb))
            assert cache_err < 1e-4, tag


@pytest.mark.parametrize("quant_kv", [False, True], ids=["f32kv", "int8kv"])
def test_mixed_sync_leaves_one_pool(quant_kv, monkeypatch):
    """One chunk row, decode rows and a dead row in a sync, on a pool that
    already holds other requests' rows: the per-projection path and the
    fused path share the span commit and leave byte-identical pools — the
    bytes the XLA scatter would leave — so prefill, ``copy_slot``, the radix
    cache and the tier restore read the same pool after either."""
    from deepspeed_tpu.models import transformer
    qmodel, qparams = _quantized_model(**_SHAPES["llama"])
    N, S, C = 4, 64, 4
    rng = np.random.RandomState(1)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.randint(-100, 100, a.shape), a.dtype),
        qmodel.init_cache(N, S, quantized=quant_kv))
    ids = jnp.asarray(rng.randint(0, qmodel.cfg.vocab_size, (N, C)), jnp.int32)
    lengths = jnp.asarray([6, 15, 31, 9], jnp.int32)
    spans = jnp.asarray([C, 1, 1, 0], jnp.int32)
    pos = lengths[:, None] + jnp.arange(C)[None, :]

    def both():
        _, per_proj = qmodel.apply_with_cache(
            qparams, ids, pool, 0, position_ids=pos, write_index=lengths,
            q_spans=spans)
        _, fused = qmodel.fused_paged_step(qparams, ids, pool, pos, lengths, spans)
        return jax.tree_util.tree_leaves((per_proj, fused))

    in_place = both()
    paged = transformer._commit_span_rows  # then every leaf through the scatter
    monkeypatch.setattr(transformer, "_commit_span_rows",
                        lambda w, wi, sp, paged_kernels: paged(w, wi, sp, False))
    scattered = both()
    half = len(in_place) // 2
    as_bytes = lambda a: np.asarray(a).view(np.uint8)
    for got, ref in zip(in_place, scattered):
        np.testing.assert_array_equal(as_bytes(got), as_bytes(ref))
    for per_proj, fused, before in zip(in_place[:half], in_place[half:],
                                       jax.tree_util.tree_leaves(pool)):
        np.testing.assert_array_equal(as_bytes(fused)[3], as_bytes(before)[3])  # dead row
        assert (as_bytes(fused) != as_bytes(before)).any()
        if not quant_kv:  # int8 rows may round a last-bit difference apart
            np.testing.assert_allclose(np.asarray(fused), np.asarray(per_proj),
                                       atol=1e-4)
        untouched = np.ones((N, S), bool)
        for i, (at, n) in enumerate(zip(np.asarray(lengths), np.asarray(spans))):
            untouched[i, at:at + n] = False
        np.testing.assert_array_equal(
            as_bytes(np.moveaxis(np.asarray(fused), 2, 1)[untouched]),
            as_bytes(np.moveaxis(np.asarray(before), 2, 1)[untouched]))


# ----------------------------------------------------- scheduler-level parity
def test_scheduler_fused_block_matches_per_projection(baseline, monkeypatch):
    """Greedy AND seeded-sampled streams through the retagged
    ``fused_block`` step programs == the per-projection ``fused`` programs,
    and the radix cache lands prefix hits on the fused path."""
    params, _ = baseline
    eng_on = make_fused_engine(params)
    assert eng_on._fused_decode_eligible(), \
        eng_on._fused_decode_eligible().reasons
    assert "fused_decode=on" in eng_on._shard_desc()
    sched_on = eng_on.scheduler()
    assert sched_on._fused_block and sched_on._fused_block_reasons == []

    eng_off = per_projection_engine(monkeypatch, make_fused_engine, params)
    sched_off = eng_off.scheduler()
    assert not sched_off._fused_block
    assert sched_off._fused_block_reasons == [REASON]

    kw_s = dict(max_new_tokens=8, do_sample=True, temperature=0.7, top_k=20,
                top_p=0.9, seed=11)
    long = list(range(1, 70))  # spans multiple prefill chunks
    for sched in (sched_on, sched_off):
        sched.greedy = [sched.submit(p, max_new_tokens=8).result()
                        for p in PROMPTS]
        sched.greedy.append(sched.submit(long, max_new_tokens=8).result())
        # a shared-prefix resubmit exercises the radix donor copy
        sched.prefixed = sched.submit(long + [71, 72],
                                      max_new_tokens=8).result()
        sched.sampled = sched.submit(PROMPTS[0], **kw_s).result()
    for a, b in zip(sched_on.greedy, sched_off.greedy):
        assert (a == b).all(), (a.tolist(), b.tolist())
    assert (sched_on.prefixed == sched_off.prefixed).all()
    assert (sched_on.sampled == sched_off.sampled).all()
    assert sched_on.radix is not None and sched_on.radix.hits > 0

    kinds_on = {k[0] for k in sched_on._compiled if isinstance(k, tuple)}
    kinds_off = {k[0] for k in sched_off._compiled if isinstance(k, tuple)}
    assert "fused_block" in kinds_on and "fused" not in kinds_on
    assert "fused" in kinds_off and "fused_block" not in kinds_off


def test_commit_path_counter(baseline, tmp_path):
    """``serving/kv_commit_*_programs``: a fused program on one device is
    built with the in-place commit, a tensor-parallel one with the scatter."""
    params, _ = baseline
    eng = make_fused_engine(params, telemetry={"enabled": True,
                                               "output_path": str(tmp_path)})
    sched = eng.scheduler()
    sched.submit(PROMPTS[0], max_new_tokens=3).result()
    built = eng.telemetry.counter_total("serving/kv_commit_inplace_programs")
    assert built == sched.kv_commit_programs["inplace"] > 0
    assert sched.kv_commit_programs["scatter"] == 0
    assert eng.telemetry.counter_total("serving/kv_commit_scatter_programs") == 0
    eng_tp = make_engine(params=params, tensor_parallel={"tp_size": 2},
                         continuous_batching={"enabled": True, "num_slots": 4})
    sched_tp = eng_tp.scheduler()
    sched_tp.submit(PROMPTS[0], max_new_tokens=3).result()
    assert sched_tp.kv_commit_programs == {
        "inplace": 0, "scatter": len(sched_tp._compiled) - ("copy" in sched_tp._compiled)}


def test_scheduler_fused_block_spec_lossless(baseline):
    """Speculation over the fused path: drafts verify through the SAME
    fused kernels (``spec_block`` programs) and the stream stays lossless
    vs the non-speculative fused scheduler."""
    params, _ = baseline
    eng0 = make_fused_engine(params)
    s0 = eng0.scheduler()
    base = [s0.submit(p, max_new_tokens=10).result() for p in PROMPTS]

    eng1 = make_fused_engine(params)
    s1 = eng1.scheduler(spec_tokens=4)
    spec = [s1.submit(p, max_new_tokens=10).result() for p in PROMPTS]
    for a, b in zip(base, spec):
        assert (a == b).all(), (a.tolist(), b.tolist())
    assert s1.spec_steps > 0 and s1.spec_accepted > 0
    kinds = {k[0] for k in s1._compiled if isinstance(k, tuple)}
    assert "spec_block" in kinds and "spec" not in kinds
    s1.cache.check_invariants()


_XLA_COMPILES = []  # registered once: jax.monitoring listeners can't detach


def _count_xla_compiles():
    if not _XLA_COMPILES:
        _XLA_COMPILES.append("registered")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _XLA_COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _XLA_COMPILES


def test_fused_block_zero_new_programs(baseline):
    """Compile-count guard (jax.monitoring): after warmup, a fresh mix of
    prompt lengths and budgets through the fused-block programs compiles
    ZERO new XLA programs — same O(1) bound as the per-projection path."""
    params, _ = baseline
    eng = make_fused_engine(params, num_slots=3)
    sched = eng.scheduler()
    # warm phase: short/long prompts (both step-count variants), a repeat
    # (the radix copy program), and a short odd prompt (idle-pool variant)
    for p in ([1, 2], list(range(1, 100)), list(range(1, 100)),
              [3, 4, 5, 6, 7]):
        sched.submit(p, max_new_tokens=6).result()
    compiles = _count_xla_compiles()
    n_before = len(compiles)
    lens = [2, 9, 33, 40, 64, 70, 90]
    handles = [sched.submit(list(range(2, n + 2)), max_new_tokens=5)
               for n in lens]
    for h in handles:
        h.result()
    n_compiles = len(compiles) - n_before
    assert n_compiles == 0, \
        f"XLA compiled {n_compiles} new programs on the fused-block path"
    C, K = sched.prefill_chunk, sched.steps_per_sync
    keys = set(sched._compiled)
    assert keys <= {("fused_block", False, False, C, K),
                    ("fused_block", False, False, C, 1),
                    ("fused_block", False, False, 1, K), "copy"}, keys


# ------------------------------------------------------------ eligibility gate
def test_fused_gate_reasons():
    """Structured eligibility: one concrete reason per excluded condition,
    the llama-shaped tiny preset is admitted, and the scheduler carries the
    verdict for /v1/metrics."""
    from deepspeed_tpu.models import get_model

    eng = make_fused_engine()
    elig = eng._fused_decode_eligible()
    assert bool(elig) and elig.eligible and elig.reasons == ()
    assert "eligible" in repr(elig)

    cases = [({"pos_embedding": "alibi"}, "alibi"),
             ({"rotary_dim": 8}, "rotary"),
             ({"local_attention_layers": (1,), "scan_layers": False}, "local"),
             ({"parallel_residual": True}, "parallel_residual")]
    for overrides, fragment in cases:
        eng_x = make_engine(model=get_model("tiny", **overrides),
                            dtype="int8", kernel_inject=True)
        e = eng_x._fused_decode_eligible()
        assert not bool(e) and not e.eligible, overrides
        assert any(fragment in r for r in e.reasons), (overrides, e.reasons)
        assert e.reasons and all(isinstance(r, str) and r for r in e.reasons)
        assert "fused_decode=off" in eng_x._shard_desc(), overrides

    # fp32 engines never qualify: the scheduler records the dtype reason
    eng_fp = make_engine(continuous_batching={"enabled": True, "num_slots": 2})
    sched = eng_fp.scheduler()
    assert not sched._fused_block
    assert any("int8" in r for r in sched._fused_block_reasons)


# ------------------------------------------------------- capacity registration
def test_capacity_program_kinds_and_int8_bytes():
    """The retagged step programs register in the roofline with the fused
    batch shape, and int8 serving prices weight traffic at 1 byte/param
    plus the per-group fp32 scales instead of the bf16 2 bytes."""
    from deepspeed_tpu.telemetry.capacity import (
        CapacityModel, program_shape, _program_kind)

    assert program_shape(("fused_block", False, False, 8, 4)) == (8, 4)
    assert program_shape(("fused_block", False, False, 8, 4, "lora")) == (8, 4)
    assert program_shape(("spec_block", False, False, 5)) == (5, 1)
    assert _program_kind(("fused_block", False, False, 8, 4)) == "fused_block"
    assert _program_kind(("spec_block", False, False, 5, "lora")) == \
        "spec_block+lora"

    def cfg(**kw):
        base = {"hidden_size": 64, "num_layers": 2, "num_heads": 4,
                "vocab_size": 128}
        base.update(kw)
        return type("C", (), base)()

    bf16 = CapacityModel(cfg(dtype="bfloat16"), kv_bytes_per_token=1.0,
                         num_slots=1)
    i8 = CapacityModel(cfg(dtype="bfloat16", int8_weights=True,
                           int8_group_size=64),
                       kv_bytes_per_token=1.0, num_slots=1)
    params = bf16.weight_read_bytes / 2.0  # bf16 prices 2 bytes/param
    assert i8.weight_read_bytes == pytest.approx(params * (1.0 + 4.0 / 64))
