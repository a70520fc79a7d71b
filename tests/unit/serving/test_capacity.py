"""Serving capacity observability guards (roofline / host-gap / goodput /
on-demand profiling).

The contracts under test, in the order the module docstring of
``telemetry/capacity.py`` states them:

- the sampled fenced-timing window adds ZERO new XLA programs after warmup
  (jax.monitoring-guarded, ``capacity_sample_every=1`` so EVERY sync fences);
- host-gap bucket counters sum EXACTLY to the measured gap — including the
  deferred-steal case where the nested timer stamps before its enclosing
  section, and the over-attribution scale-back;
- the analytic :class:`CapacityModel` FLOPs agree with XLA's own
  ``lower().cost_analysis()`` for the same forward (factor tolerance — the
  analytic model intentionally ignores norms/rope/softmax);
- goodput arithmetic (useful vs wasted token-FLOPs, byte waste converted at
  the machine balance);
- ``serving/mfu`` / ``serving/goodput_fraction`` / ``serving/host_gap_ms``
  actually land in the sink and in the Prometheus rendering (native
  ``_hist_bucket``/``le`` series) on a CPU smoke;
- the disabled sink allocates nothing (no meter, no tracker);
- instrumented decode stays within the overhead budget;
- :class:`XlaProfiler` produces a loadable trace, 409s on overlap, and the
  gateway's ``POST /v1/debug/profile`` does both end-to-end.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry.capacity import (
    GAP_BUCKETS, CapacityMeter, CapacityModel, HostGapTracker, program_shape,
    _program_kind)
from deepspeed_tpu.telemetry.profiler import (ProfileBusy, XlaProfiler,
                                              trace_artifacts)

_XLA_COMPILES = []  # registered once: jax.monitoring listeners can't detach


def _count_xla_compiles():
    if not _XLA_COMPILES:
        _XLA_COMPILES.append("registered")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _XLA_COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _XLA_COMPILES


def make_engine(params=None, num_slots=4, telemetry=None, **cb_extra):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)  # sink hermeticity: no cross-test counter bleed
    cb = {"enabled": True, "num_slots": num_slots}
    cb.update(cb_extra)
    cfg = {"dtype": "float32", "max_out_tokens": 512,
           "continuous_batching": cb}
    if telemetry:
        cfg["telemetry"] = telemetry
    return deepspeed_tpu.init_inference("tiny", config=cfg, params=params)


@pytest.fixture(scope="module")
def params():
    eng = make_engine()
    return jax.device_get(eng.params)


_RNG = np.random.default_rng(23)
PROMPTS = [_RNG.integers(0, 256, 40).astype(np.int32),
           _RNG.integers(0, 256, 17).astype(np.int32)]


class FakeSink:
    """Counter/gauge/histogram recorder for the pure-host unit tests."""

    enabled = True

    def __init__(self):
        self.counters = {}
        self.gauges = {}
        self.hists = {}

    def counter(self, name, value=1, attrs=None):
        c, t = self.counters.get(name, (0, 0))
        self.counters[name] = (c + 1, t + value)

    def gauge(self, name, value, step=None, attrs=None):
        self.gauges[name] = value

    def histogram(self, name, value, attrs=None):
        self.hists.setdefault(name, []).append(value)


# ------------------------------------------------------------- host-gap units
def test_host_gap_buckets_sum_exactly_to_gap():
    sink = FakeSink()
    gap = HostGapTracker(sink)
    gap.sync_end(10.0)
    gap.add("admission", 0.004)
    gap.add("sampling_host", 0.002)
    gap.add("on_token", 0.001)
    gap.dispatch(10.020)  # 20 ms gap, 7 ms attributed -> 13 ms other
    total = sum(t for _, t in sink.counters.values())
    assert total == pytest.approx(20.0, abs=1e-9)
    assert sink.counters["serving/host_gap/other_ms"][1] == pytest.approx(13.0)
    assert sink.hists["serving/host_gap_ms"] == [pytest.approx(20.0)]
    assert gap.gaps == 1 and gap.total_gap_s == pytest.approx(0.020)


def test_host_gap_deferred_steal_is_order_independent():
    # the trie probe runs inside the admission region but stamps FIRST
    # (scheduler's _acquire_slot precedes step()'s admission stamp) — the
    # debit must survive the ordering, not be floored away
    results = []
    for order in ("probe_first", "admission_first"):
        sink = FakeSink()
        gap = HostGapTracker(sink)
        gap.sync_end(0.0)
        if order == "probe_first":
            gap.add("trie_probe", 0.003, steal_from="admission")
            gap.add("admission", 0.010)
        else:
            gap.add("admission", 0.010)
            gap.add("trie_probe", 0.003, steal_from="admission")
        gap.dispatch(0.020)
        results.append({k: t for k, (_, t) in sink.counters.items()})
    assert results[0] == results[1]
    assert results[0]["serving/host_gap/admission_ms"] == pytest.approx(7.0)
    assert results[0]["serving/host_gap/trie_probe_ms"] == pytest.approx(3.0)
    assert sum(results[0].values()) == pytest.approx(20.0)


def test_host_gap_over_attribution_scales_back():
    # overlapping timers claim 30 ms of a 10 ms gap: the invariant
    # "buckets sum to the measured gap" must hold via proportional scaling
    sink = FakeSink()
    gap = HostGapTracker(sink)
    gap.sync_end(0.0)
    gap.add("admission", 0.020)
    gap.add("on_token", 0.010)
    gap.dispatch(0.010)
    total = sum(t for _, t in sink.counters.values())
    assert total == pytest.approx(10.0, abs=1e-9)
    adm = sink.counters["serving/host_gap/admission_ms"][1]
    tok = sink.counters["serving/host_gap/on_token_ms"][1]
    assert adm == pytest.approx(2 * tok)  # proportions preserved
    assert "serving/host_gap/other_ms" not in sink.counters


def test_host_gap_dispatch_before_sync_clears():
    # warmup dispatches (no prior fence) must not emit phantom gaps
    sink = FakeSink()
    gap = HostGapTracker(sink)
    gap.add("admission", 0.005)
    gap.dispatch(1.0)
    assert not sink.counters and not sink.hists and gap.gaps == 0


@pytest.mark.parametrize("ahead", [True, False])
def test_host_gap_of_a_pump_one_sync_deep(ahead):
    """A dispatch that opens before the previous fetch closes (the pump
    launched sync N+1 with N unlanded) found the device busy: ONE 0.0
    observation, no bucket counters, whatever host work was stamped since;
    and the fetch that then lands N opens no gap, because N+1 is out. The
    serial order records what it always did, its buckets summing to the gap."""
    sink = FakeSink()
    unlanded = [False]
    gap = HostGapTracker(sink, unlanded=lambda: unlanded[0])
    gap.span_enter("sched/dispatch", 0.0)             # sync N: nothing before it
    assert not sink.hists and gap.gaps == 0
    unlanded[0] = ahead                               # N is out when N+1 is assembled
    if not ahead:
        gap.span_exit("sched/fetch", 0.001, 0.010)    # serial: N lands first
    gap.span_enter("sched/admit", 0.010)
    gap.span_exit("sched/admit", 0.010, 0.014)
    gap.span_enter("sched/assemble", 0.014)
    gap.span_exit("sched/assemble", 0.014, 0.016)
    gap.span_enter("sched/dispatch", 0.020)           # sync N+1
    if ahead:
        assert sink.hists["serving/host_gap_ms"] == [0.0] and not sink.counters
        gap.span_exit("sched/fetch", 0.021, 0.030)    # lands N while N+1 is out: no gap opens
        gap.span_enter("sched/deliver", 0.030)
        gap.span_exit("sched/deliver", 0.030, 0.035)
        gap.span_enter("sched/dispatch", 0.040)       # sync N+2, again behind an unlanded one
        assert sink.hists["serving/host_gap_ms"] == [0.0, 0.0] and not sink.counters
        assert gap.gaps == 2 and gap.total_gap_s == 0.0
        # the pump turns serial (say a capacity-sampled fence): N+1 and N+2
        # land with nothing out, and the next dispatch measures a real gap
        unlanded[0] = False
        gap.span_exit("sched/fetch", 0.041, 0.050)
        gap.span_enter("sched/deliver", 0.050)
        gap.span_exit("sched/deliver", 0.050, 0.053)
        gap.span_enter("sched/dispatch", 0.060)
        assert sink.hists["serving/host_gap_ms"][2] == pytest.approx(10.0)
        assert sink.counters["serving/host_gap/on_token_ms"][1] == pytest.approx(3.0)
        assert sum(t for _, t in sink.counters.values()) == pytest.approx(10.0)
    else:
        assert sink.hists["serving/host_gap_ms"] == [pytest.approx(10.0)]
        assert sink.counters["serving/host_gap/admission_ms"][1] == pytest.approx(4.0)
        assert sink.counters["serving/host_gap/sampling_host_ms"][1] == pytest.approx(2.0)
        assert sum(t for _, t in sink.counters.values()) == pytest.approx(10.0)
        assert gap.gaps == 1


# ---------------------------------------------------------- program-key units
def test_program_shape_and_kind():
    assert program_shape(("fused", True, False, 8, 4)) == (8, 4)
    assert program_shape(("fused", True, False, 8, 4, "lora")) == (8, 4)
    assert program_shape(("spec", False, False, 5)) == (5, 1)
    assert program_shape(("spec", False, False, 5, "lora")) == (5, 1)
    assert program_shape(("fused_block", False, False, 64, 4)) == (64, 4)
    assert program_shape("copy") == (1, 1)
    assert _program_kind(("fused", True, False, 8, 4, "lora")) == "fused+lora"
    assert _program_kind(("spec", False, False, 5)) == "spec"
    assert _program_kind("tier_slice") == "tier_slice"


# -------------------------------------------------------------- goodput units
def test_goodput_accounting():
    model = CapacityModel(type("C", (), {"hidden_size": 64, "num_layers": 2,
                                         "num_heads": 4, "vocab_size": 128})(),
                          kv_bytes_per_token=1024, num_slots=4)
    meter = CapacityMeter(FakeSink(), model, peak_flops=1e12, peak_hbm_bw=1e11)
    assert meter.goodput_fraction == 1.0  # nothing accounted yet
    meter.account(10, wasted_tokens=5, ctx=0.0)
    assert meter.goodput_fraction == pytest.approx(10 / 15)
    # byte waste converts at the machine balance (FLOPs/byte = 10 here)
    ft = model.flops_per_token(0.0)
    meter2 = CapacityMeter(FakeSink(), model, peak_flops=1e12, peak_hbm_bw=1e11)
    meter2.account(1, ctx=0.0, wasted_bytes=ft / 10.0)
    assert meter2.goodput_fraction == pytest.approx(0.5)


def test_observe_dispatch_roofline_classification():
    model = CapacityModel(type("C", (), {"hidden_size": 64, "num_layers": 2,
                                         "num_heads": 4, "vocab_size": 128})(),
                          kv_bytes_per_token=1024, num_slots=4)
    sink = FakeSink()
    meter = CapacityMeter(sink, model, peak_flops=1e12, peak_hbm_bw=1e11,
                          sample_every=4)
    key = ("fused", True, False, 1, 1)
    meter.register(key, model)  # any hashable stand-in for the fn
    assert meter.key_for(model) == key
    assert [meter.should_sample(s) for s in range(5)] == [
        True, False, False, False, True]
    meter.observe_dispatch(key, 1e-3, np.array([10, 20]), width=1, ksteps=1)
    assert meter.samples == 1
    assert 0.0 < sink.gauges["serving/mfu"]
    assert 0.0 < sink.gauges["serving/hbm_bw_util"]
    assert "serving/roofline/fused" in sink.gauges
    table = meter.program_table()
    ent = table[str(key)]
    assert ent["kind"] == "fused" and ent["samples"] == 1
    assert ent["bound"] in ("compute", "bandwidth")


@pytest.mark.parametrize("int8", [False, True], ids=["fused", "fused_block"])
@pytest.mark.parametrize("split", [False, True])
def test_dispatch_cost_prices_a_split_chunk_program(split, int8):
    """A chunk program that runs its first forward over the live rows is
    priced at ``num_slots + width`` first-forward rows and two weight streams
    for them, the whole block at ``num_slots * width`` rows and one; a split
    ``fused_block`` program's extra stream is one of int8 weights with their
    group scales (1 + 4/128 bytes a parameter, where bf16 moves 2)."""
    cfg = {"hidden_size": 64, "num_layers": 2, "num_heads": 4, "vocab_size": 128,
           "dtype": "bfloat16", "int8_weights": int8}
    model = CapacityModel(type("C", (), cfg)(), kv_bytes_per_token=0, num_slots=8)
    flops, bytes_ = model.dispatch_cost(np.zeros(0), width=64, ksteps=4, split=split)
    first = 8 + 64 if split else 8 * 64
    assert flops == (first + 3 * 8) * model.matmul_flops_per_col
    assert bytes_ == (4 + split) * model.weight_read_bytes
    weights = 2 * (64 * 16 * 12 + 4 * 16 * 64 + 2 * 64 * 256) + 64 * 128
    assert model.weight_read_bytes == weights * ((1 + 4 / 128) if int8 else 2)


# ------------------------------------------------- analytic-model cross-check
def test_capacity_model_flops_cross_check(params):
    """Analytic matmul+attention FLOPs vs XLA's own cost analysis of the
    same forward. The analytic model ignores norms/rope/softmax/router and
    counts the padded slot block, so the tolerance is a factor band — the
    guard is against being off by a power of ten (a miscounted projection,
    a dropped layer factor), not rounding."""
    eng = make_engine(params)
    T = 33
    ids = jax.numpy.asarray(PROMPTS[0][:T][None, :], jax.numpy.int32)
    lowered = jax.jit(eng.module.apply).lower(eng.params, ids)
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    measured = float(ca.get("flops", 0.0)) if ca else 0.0
    if measured <= 0.0:
        pytest.skip("backend reports no flops in cost_analysis")
    model = CapacityModel(eng.model_config,
                          kv_bytes_per_token=1.0, num_slots=1)
    # full-sequence causal forward: T columns, position i attends to i+1
    analytic = (T * model.matmul_flops_per_col
                + (T * (T + 1) / 2) * model.attn_flops_per_ctx_tok)
    ratio = measured / analytic
    assert 0.25 <= ratio <= 4.0, (measured, analytic)


# --------------------------------------------------------------- end-to-end
def _decode(eng, n=3, max_new=8):
    handles = [eng.scheduler().submit(PROMPTS[i % 2], max_new_tokens=max_new,
                                      seed=7 + i) for i in range(n)]
    return [h.result().tolist() for h in handles]


def test_capacity_metrics_emitted_cpu_smoke(params, tmp_path):
    eng = make_engine(params, telemetry={"enabled": True,
                                         "output_path": str(tmp_path),
                                         "capacity_sample_every": 1})
    _decode(eng)
    sched = eng.scheduler()
    assert sched.capacity is not None and sched._gap is not None
    assert sched.capacity.samples > 0
    table = sched.capacity.program_table()
    assert table and all(e["bound"] in ("compute", "bandwidth")
                         for e in table.values())
    snap = eng.telemetry.snapshot()
    assert 0.0 < snap["gauges"]["serving/mfu"]
    assert 0.0 < snap["gauges"]["serving/hbm_bw_util"]
    assert snap["gauges"]["serving/goodput_fraction"] == pytest.approx(1.0)
    hg = snap["histograms"]["serving/host_gap_ms"]
    assert hg["count"] == sched._gap.gaps > 0
    # per-bucket counters only name known buckets and sum to the gap total
    bucket_ms = sum(c["total"] for name, c in snap["counters"].items()
                    if name.startswith("serving/host_gap/"))
    assert bucket_ms == pytest.approx(sched._gap.total_gap_s * 1e3, rel=1e-6)
    for name in snap["counters"]:
        if name.startswith("serving/host_gap/"):
            assert name[len("serving/host_gap/"):-len("_ms")] in GAP_BUCKETS
    # Prometheus rendering carries the gauges + the native histogram family
    from deepspeed_tpu.telemetry.prometheus import render
    text = render(snap)
    assert "dstpu_serving_mfu " in text
    assert "dstpu_serving_goodput_fraction " in text
    assert 'dstpu_serving_host_gap_ms_hist_bucket{le="' in text
    assert f'_hist_bucket{{le="+Inf"}} {hg["count"]}' in text
    eng.telemetry.close()


def test_disabled_sink_allocates_nothing(params):
    eng = make_engine(params)
    sched = eng.scheduler()
    assert sched.capacity is None and sched._gap is None
    assert _decode(eng, n=1)[0]  # and decode still works


def test_sampled_fencing_adds_zero_new_xla_programs(params, tmp_path):
    """capacity_sample_every=1 fences EVERY sync — over a warm mix of both
    prompt-length buckets, fresh requests must add zero compiles."""
    compiles = _count_xla_compiles()
    eng = make_engine(params, telemetry={"enabled": True,
                                         "output_path": str(tmp_path),
                                         "capacity_sample_every": 1})
    _decode(eng, n=3)  # warm: both prefill buckets + fused decode
    before = len(compiles)
    fresh = [np.roll(PROMPTS[0], 5), np.roll(PROMPTS[1], 3)]
    handles = [eng.scheduler().submit(p, max_new_tokens=8, seed=99 + i)
               for i, p in enumerate(fresh)]
    for h in handles:
        assert h.result().tolist()
    assert len(compiles) == before, \
        f"sampled fencing added {len(compiles) - before} XLA program(s)"
    assert eng.scheduler().capacity.samples > 0
    eng.telemetry.close()


def test_instrumented_decode_overhead_bounded(params, tmp_path):
    """The capacity instrumentation's marginal cost per sync, as counts (a
    wall-clock ratio of two tiny CPU decodes is the machine's load, not the
    program's cost): with fenced sampling every 4th sync, one dispatch in
    four is fenced and never more — the async hot path is not serialized —
    and the pump marks at most 8 spans a sync, however many tokens the sync
    carries."""
    eng = make_engine(params, telemetry={
        "enabled": True, "output_path": str(tmp_path), "capacity_sample_every": 4})
    sched = eng.scheduler()
    _decode(eng, n=2)  # warm
    syncs0, fenced0 = sched._sync_seq, sched.capacity.samples
    _decode(eng, n=4, max_new=48)
    syncs, fenced = sched._sync_seq - syncs0, sched.capacity.samples - fenced0
    assert syncs >= 8
    assert 1 <= fenced <= syncs // 4 + 1, (fenced, syncs)
    eng.telemetry.close()
    with open(eng.telemetry.jsonl_path) as f:
        spans = [e["name"] for e in map(json.loads, f)
                 if e.get("type") == "span" and e["name"].startswith("sched/")]
    steps = spans.count("sched/step")
    assert steps >= 8
    assert (len(spans) - steps) / steps <= 8, (len(spans), steps)


# ----------------------------------------------------------------- profiling
def test_xla_profiler_capture_and_busy(tmp_path):
    prof = XlaProfiler(str(tmp_path))
    trace_dir = prof.start(duration_s=0.2, tag="unit test!")
    assert os.path.isdir(trace_dir) and "unit_test_" in trace_dir
    with pytest.raises(ProfileBusy):
        prof.start(duration_s=0.2)
    # run some device work so the trace has content, then let it expire
    jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    # wait on captures, not .active: the stopper clears _active before the
    # (slow) trace write finishes and the capture is recorded
    deadline = time.monotonic() + 10.0
    while not prof.captures and time.monotonic() < deadline:
        time.sleep(0.05)
        prof.poll()
    assert prof.active is None
    assert prof.captures == [trace_dir]
    arts = trace_artifacts(trace_dir)
    assert arts, f"no trace artifacts under {trace_dir}"
    assert any(a.endswith((".xplane.pb", ".trace.json.gz", ".trace.json"))
               for a in arts)
    # manager is reusable after the capture ends; a long deadline keeps
    # the daemon timer from racing the explicit stop
    d2 = prof.start(duration_s=30.0)
    assert prof.stop() == d2


def test_profiler_report_boundary_request(tmp_path):
    prof = XlaProfiler(str(tmp_path))
    assert prof.maybe_capture() is None  # nothing pending: no-op
    prof.request(duration_s=0.05)
    with pytest.raises(ProfileBusy):
        prof.request(duration_s=0.05)  # pending counts as in-flight
    d = prof.maybe_capture(tag="report")
    assert d is not None and "report" in d
    prof.stop()
    assert prof.captures == [d]
    assert prof.maybe_capture() is None  # request was consumed


def test_gateway_profile_endpoint_and_capacity_metrics(params, tmp_path):
    from deepspeed_tpu.serving import Gateway
    eng = make_engine(params, num_slots=2,
                      telemetry={"enabled": True, "output_path": str(tmp_path),
                                 "capacity_sample_every": 1})
    gw = Gateway(eng, port=0, request_timeout_s=60.0)
    gw.start_background()
    base = f"http://127.0.0.1:{gw.port}"

    def post(path, body):
        req = urllib.request.Request(base + path, data=json.dumps(body).encode(),
                                     headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=60).read())

    def get(path, headers=None):
        req = urllib.request.Request(base + path, headers=headers or {})
        return urllib.request.urlopen(req, timeout=60).read()

    try:
        out = post("/v1/completions",
                   {"prompt": PROMPTS[0].tolist(), "max_tokens": 6, "seed": 3})
        assert out["choices"][0]["token_ids"]
        m = json.loads(get("/v1/metrics"))
        cap = m["capacity"]
        assert cap["programs"] and cap["samples"] > 0
        assert cap["goodput_fraction"] == pytest.approx(1.0)
        assert cap["host_gap_total_s"] >= 0.0
        assert set(cap["host_gaps"] if isinstance(cap["host_gaps"], dict)
                   else []) <= set(GAP_BUCKETS) or isinstance(
                       cap["host_gaps"], (int, float))
        text = get("/v1/metrics", {"Accept": "text/plain"}).decode()
        assert "dstpu_serving_mfu " in text
        assert 'dstpu_serving_host_gap_ms_hist_bucket{le="' in text
        # on-demand profiling: 200 with a trace dir, 409 while in flight
        resp = post("/v1/debug/profile", {"duration_ms": 400})
        assert os.path.isdir(resp["path"])
        assert cap["profiling"] is None  # was idle at the metrics scrape
        try:
            post("/v1/debug/profile", {"duration_ms": 100})
            assert False, "overlapping capture should 409"
        except urllib.error.HTTPError as e:
            assert e.code == 409
        # device work inside the capture window, then let it expire
        post("/v1/completions",
             {"prompt": PROMPTS[1].tolist(), "max_tokens": 4, "seed": 5})
        deadline = time.monotonic() + 10.0
        while not gw.profiler.captures and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gw.profiler.captures, "capture never expired"
        assert gw.profiler.active is None
        assert trace_artifacts(resp["path"]), "profile wrote no artifacts"
    finally:
        assert gw.close(60), "gateway failed to drain"
