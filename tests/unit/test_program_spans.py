"""Program spans on the profiler's clock, names on what the device runs, the
set-up counters and the prefill-lane histogram.

- ``TelemetrySink.span`` marks a ``jax.profiler.TraceAnnotation``
  (``dstpu/<name>``) whether the sink is enabled or not: a CPU profiler
  capture around a tiny scheduler run holds ``dstpu/sched/step`` with
  ``admit``, ``assemble``, ``dispatch``, ``fetch`` and ``deliver`` inside it.
- The serving Pallas calls carry their ``dstpu_...`` names into the lowered
  program; the flash-attention calls carry none (the accepted
  ``flash_attention_roofline`` matches the names they have today).
- ``compile_cache.stats()`` counts a traced, lowered and compiled program.
- ``serving/prefill_wait_ms`` is observed once per admitted request.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry import TelemetrySink, set_sink
from deepspeed_tpu.telemetry.capacity import SPAN_BUCKETS, HostGapTracker
from deepspeed_tpu.utils import compile_cache

_RNG = np.random.default_rng(31)
PROMPTS = [_RNG.integers(0, 256, 40).astype(np.int32), _RNG.integers(0, 256, 17).astype(np.int32)]
INNER = ("admit", "assemble", "dispatch", "fetch", "deliver")


def make_engine(params=None, telemetry=None):
    comm._state["mesh"] = None
    set_sink(None)
    cfg = {"dtype": "float32", "max_out_tokens": 512,
           "continuous_batching": {"enabled": True, "num_slots": 4}}
    if telemetry:
        cfg["telemetry"] = telemetry
    return deepspeed_tpu.init_inference("tiny", config=cfg, params=params)


@pytest.fixture(scope="module")
def params():
    return jax.device_get(make_engine().params)


def _decode(eng, n=3, max_new=8):
    handles = [eng.scheduler().submit(PROMPTS[i % 2], max_new_tokens=max_new, seed=7 + i)
               for i in range(n)]
    return [h.result().tolist() for h in handles]


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the ``dstpu/`` annotations in a capture."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                        if e.name.startswith("dstpu/")]
    return out


@pytest.mark.parametrize("sink_on", [False, True], ids=["sink_disabled", "sink_enabled"])
def test_profiler_capture_holds_the_pump_spans(params, tmp_path, sink_on):
    tel = ({"enabled": True, "output_path": str(tmp_path / "tel")} if sink_on else None)
    eng = make_engine(params, telemetry=tel)
    _decode(eng, n=2, max_new=4)  # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _decode(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path / "trace"))
    steps = [(a, b) for name, a, b in spans if name == "dstpu/sched/step"]
    assert steps, sorted({name for name, _, _ in spans})
    for inner in INNER:
        mine = [(a, b) for name, a, b in spans if name == "dstpu/sched/" + inner]
        assert mine, f"no dstpu/sched/{inner} in the capture"
        # every inner span lies inside one iteration's span
        assert all(any(s0 <= a and b <= s1 for s0, s1 in steps) for a, b in mine), inner
    # block level only: a handful per iteration, however many tokens it carried
    per_step = sum(1 for name, _, _ in spans if name != "dstpu/sched/step") / len(steps)
    assert per_step <= 8, per_step
    if sink_on:
        eng.telemetry.close()
        import json
        with open(eng.telemetry.jsonl_path) as f:
            events = [json.loads(line) for line in f]
        names = {e["name"] for e in events if e.get("type") == "span"}
        assert {"sched/step"} | {"sched/" + i for i in INNER} <= names
    else:
        assert not eng.telemetry.enabled and eng.scheduler()._gap is None


def test_span_feeds_the_gap_tracker_from_its_boundaries():
    class Sink:
        enabled = True

        def __init__(self):
            self.hist, self.counters = [], {}

        def histogram(self, name, value, attrs=None):
            self.hist.append((name, value))

        def counter(self, name, value=1, attrs=None):
            self.counters[name] = self.counters.get(name, 0.0) + value

    fake = Sink()
    gap = HostGapTracker(fake)
    gap.span_exit("sched/fetch", 0.0, 1.0)            # results on the host: gap opens at 1.0
    gap.span_enter("sched/admit", 1.0)
    gap.span_enter("sched/trie_probe", 1.002)
    gap.span_exit("sched/trie_probe", 1.002, 1.005)   # 3 ms out of admission's 10
    gap.span_exit("sched/admit", 1.0, 1.010)
    gap.span_enter("sched/assemble", 1.010)
    gap.span_exit("sched/assemble", 1.010, 1.014)
    gap.span_enter("sched/dispatch", 1.020)           # closes the gap: 20 ms
    assert fake.hist == [("serving/host_gap_ms", pytest.approx(20.0))]
    assert fake.counters["serving/host_gap/admission_ms"] == pytest.approx(7.0)
    assert fake.counters["serving/host_gap/trie_probe_ms"] == pytest.approx(3.0)
    assert fake.counters["serving/host_gap/sampling_host_ms"] == pytest.approx(4.0)
    assert fake.counters["serving/host_gap/other_ms"] == pytest.approx(6.0)
    assert set(SPAN_BUCKETS.values()) <= set(gap._acc)


def test_pump_ahead_observes_a_zero_gap_every_sync(params, tmp_path):
    """The scheduler's own spans through its own tracker: every sync launched
    with the last one unlanded adds one 0.0 to ``serving/host_gap_ms`` (an
    empty histogram has no quantile for ``sched_host_gap_ms`` to read) and
    nothing to the bucket counters; those still sum to the gaps measured."""
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)})
    sched = eng.scheduler()
    handles = [sched.submit(PROMPTS[i], max_new_tokens=24, seed=i) for i in range(2)]
    sched.drain()
    assert all(len(h.result()) == 24 for h in handles)
    tel = eng.telemetry
    assert sched.syncs_ahead > 0
    assert tel.counter_total("serving/syncs_ahead") == sched.syncs_ahead
    assert tel.counter_total("serving/syncs_serial") == sched.syncs_serial
    snap = tel.snapshot()
    hg = snap["histograms"]["serving/host_gap_ms"]
    assert hg["count"] == sched._gap.gaps >= sched.syncs_ahead
    assert hg["min"] == 0.0
    buckets = sum(c["total"] for name, c in snap["counters"].items()
                  if name.startswith("serving/host_gap/"))
    assert buckets == pytest.approx(sched._gap.total_gap_s * 1e3, rel=1e-6)
    tel.close()
    set_sink(None)


def test_scheduler_times_no_gap_section_by_hand():
    import inspect
    from deepspeed_tpu.inference import scheduler
    src = inspect.getsource(scheduler)
    assert "_gap.add(" not in src and "_gap.sync_end(" not in src and "_gap.dispatch(" not in src


def test_disabled_sink_span_records_nothing_but_tells_its_observer(tmp_path):
    seen = []

    class Obs:
        def span_enter(self, name, ts):
            seen.append(("enter", name))

        def span_exit(self, name, t0, t1):
            seen.append(("exit", name, t1 >= t0))

    sink = TelemetrySink({"enabled": False, "output_path": str(tmp_path / "t")})
    with sink.span("x/y", observer=Obs(), k=1) as span:
        assert span.name == "x/y" and span.attrs == {"k": 1}
    assert seen == [("enter", "x/y"), ("exit", "x/y", True)]
    assert sink._buffer == [] and not (tmp_path / "t").exists()


# ------------------------------------------------------------------ names
def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_serving_kernels_carry_their_names_into_the_program():
    """The int8 fused serving step, one layer deep: its lowering names the
    four serving kernels and the region that writes the pool."""
    import dataclasses
    from deepspeed_tpu.models import get_model
    cfg = dataclasses.replace(
        get_model("tiny").cfg, dtype=jnp.bfloat16, int8_weights=True, int8_fused_qkv=True,
        attention_impl="flash", scan_layers=False, num_layers=1)
    model = type(get_model("tiny"))(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    pool = jax.eval_shape(lambda: model.init_cache(8, 128))
    ids = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    rows = jax.ShapeDtypeStruct((8, ), jnp.int32)
    text = jax.jit(model.fused_paged_step).lower(params, ids, pool, ids, rows, rows).as_text(
        debug_info=True)
    for name in ("dstpu_decode_attn", "dstpu_fused_qkv_ln", "dstpu_fused_out_mlp",
                 "dstpu_quant_matmul", "kv_commit"):
        assert name in text, name


def test_flash_attention_calls_stay_unnamed():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    text = _lowered(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert "tpu_custom_call" in text or "pallas" in text.lower()
    assert "dstpu_" not in text


def test_train_step_regions_are_named():
    from deepspeed_tpu.models import get_model
    comm._state["mesh"] = None
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=get_model("tiny", dtype=jnp.float32, max_seq_len=32),
        config={"train_micro_batch_size_per_gpu": 2, "steps_per_print": 10**9,
                "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    ids = np.zeros((engine.train_batch_size(), 32), np.int32)
    engine.train_batch(batch={"input_ids": ids})
    stacked = {"input_ids": ids.reshape((1, ) + ids.shape)}
    with engine.mesh:
        text = engine._compiled["train_batch"].lower(engine.state, stacked).as_text(
            debug_info=True)
    for scope in ("grad_norm", "optimizer", "loss_ce"):
        assert scope in text, scope


# ------------------------------------------------------------------ counters
def test_compile_stats_count_one_program():
    compile_cache.listen()
    before = compile_cache.stats()

    def fresh(x):  # a function no other test has compiled
        return (x * 3.25 + 1.5).sum()

    jax.jit(fresh)(np.ones(7, np.float32))
    after = compile_cache.stats()
    for phase in ("trace", "lower", "backend"):
        assert after[phase + "_count"] - before[phase + "_count"] == 1, phase
        assert after[phase + "_s"] > before[phase + "_s"], phase
    assert after["cache_read_s"] >= before["cache_read_s"]
    assert after["cache_read_s"] <= after["backend_s"]


def test_compile_stats_nested_jit_seconds_are_a_union():
    compile_cache.listen()

    @jax.jit
    def inner(x):
        return x * 2.75

    def outer(x):
        return inner(x) + inner(x + 1.0)

    before = compile_cache.stats()
    import time
    t0 = time.time()
    jax.jit(outer)(np.ones(5, np.float32))
    wall = time.time() - t0
    after = compile_cache.stats()
    assert after["trace_count"] - before["trace_count"] == 1  # inner's lie inside outer's
    assert after["trace_s"] - before["trace_s"] <= wall


def test_prefill_wait_observed_once_per_admitted_request(params, tmp_path):
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)})
    _decode(eng, n=5, max_new=3)
    hists = eng.telemetry.snapshot()["histograms"]
    assert hists["serving/prefill_wait_ms"]["count"] == 5
    assert hists["serving/ttft_ms"]["count"] == 5
    # waiting for the lane is part of the time to the first token
    assert hists["serving/prefill_wait_ms"]["sum"] <= hists["serving/ttft_ms"]["sum"]
    eng.telemetry.close()
