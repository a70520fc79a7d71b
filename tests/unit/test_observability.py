"""Observability-layer tests: request tracing, SLO burn-rate engine,
anomaly flight recorder, Prometheus exposition, comm overlap accounting.

Covers the ISSUE 8 acceptance criteria:

- a request submitted through the gateway with a ``traceparent`` header
  yields a CONNECTED span tree in ``trace.json`` (queued -> admitted ->
  prefill -> decode -> complete, flow-linked to scheduler iteration spans),
  verified by loading the trace and walking the links;
- ``/v1/metrics`` serves parseable Prometheus text exposition;
- an induced deadline-expiry storm trips an SLO burn-rate alert and
  produces a flight-recorder dump containing the surrounding iterations;
- a telemetry-enabled train step emits nonzero ``comm/{op}/realized_ms``
  and ``comm/overlap_efficiency`` gauges (the multichip dryrun asserts the
  same);

plus the satellite contracts: windowed (never-frozen) histogram
percentiles with ``dropped``/``window`` accounting, histogram ``attrs``
recorded, per-thread trace tracks, the zero-allocation disabled hot path,
the bounded-tracing-overhead guard, and ``trace_summary.py --requests``.
"""

import http.client
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.comm.overlap import CommOverlapTracker
from deepspeed_tpu.telemetry import (RequestTrace, SLOEngine, TelemetrySink,
                                     set_sink)
from deepspeed_tpu.telemetry.prometheus import render as prom_render
from deepspeed_tpu.telemetry.tracing import extract_trace_context

from .simple_model import SimpleModel, random_batch

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROMPT = [5, 6, 7, 8, 9]
TRACEPARENT = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"
TRACE_ID = "4bf92f3577b34da6a3ce929d0e0e4736"


@pytest.fixture(autouse=True)
def _reset_sink():
    yield
    set_sink(None)


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def make_sink(tmp_path, **over):
    cfg = {"enabled": True, "output_path": str(tmp_path / "tel"),
           "flush_interval": 4,
           "flight_recorder": {"post_window_s": 0.0, "min_interval_s": 0.0}}
    cfg.update(over)
    return TelemetrySink(cfg)


# ---------------------------------------------------------------------------
# windowed histograms (satellite: frozen-percentile fix)
# ---------------------------------------------------------------------------
def test_histogram_window_slides(tmp_path):
    """Percentiles must track the LAST window, not the first samples ever
    (the old _HIST_SAMPLE_CAP froze p95 on startup-era data forever)."""
    sink = make_sink(tmp_path, hist_window_s=0.15, hist_max_samples=120)
    for _ in range(50):
        sink.histogram("lat", 1.0)
    time.sleep(0.2)
    for _ in range(50):
        sink.histogram("lat", 100.0)
    h = sink.snapshot()["histograms"]["lat"]
    assert h["p50"] == 100.0 and h["p95"] == 100.0, h
    assert h["min"] == 100.0, "window min must not remember expired samples"
    assert h["count"] == 100, "lifetime count stays cumulative"
    assert h["sum"] == 50 * 1.0 + 50 * 100.0
    assert h["window_count"] == 50
    assert h["window_s"] == 0.15
    assert 0 <= h["dropped"] < 50


def test_histogram_reservoir_bounds_memory_and_reports_dropped(tmp_path):
    sink = make_sink(tmp_path, hist_window_s=60.0, hist_max_samples=60)
    for i in range(5000):
        sink.histogram("lat", float(i % 97))
    h = sink.snapshot()["histograms"]["lat"]
    assert h["count"] == 5000 and h["window_count"] == 5000
    # retained samples bounded by the reservoir; the shortfall is reported
    assert h["dropped"] >= 5000 - 60
    hist = sink._hists["lat"]
    retained = sum(len(c[2]) for c in hist._chunks)
    assert retained <= 60
    # percentiles still in the data's range (uniform reservoir)
    assert 0.0 <= h["p50"] <= 96.0


def test_histogram_attrs_recorded(tmp_path):
    """Satellite: histogram(attrs=...) used to be silently discarded."""
    sink = make_sink(tmp_path)
    sink.histogram("lat", 1.5, attrs={"unit": "ms"})
    sink.histogram("lat", 2.5)
    assert sink.snapshot()["histograms"]["lat"]["attrs"] == {"unit": "ms"}
    sink.close()
    lines = [ev for ev in read_jsonl(sink.jsonl_path)
             if ev["type"] == "histogram" and ev["name"] == "lat"]
    assert lines and lines[-1]["attrs"] == {"unit": "ms"}


# ---------------------------------------------------------------------------
# thread tracks / async spans / flows / instants
# ---------------------------------------------------------------------------
def test_spans_land_on_per_thread_tracks(tmp_path):
    sink = make_sink(tmp_path)

    def worker():
        sink.record_span("from_worker", sink.now(), 0.001)

    t = threading.Thread(target=worker, name="pump-thread")
    t.start()
    t.join()
    sink.record_span("from_main", sink.now(), 0.001)
    sink.close()
    trace = json.load(open(sink.trace_path))["traceEvents"]
    spans = {e["name"]: e for e in trace if e.get("ph") == "X"}
    assert spans["from_worker"]["tid"] != spans["from_main"]["tid"]
    names = {e["args"]["name"] for e in trace
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert "pump-thread" in names


def test_async_spans_flows_and_instants(tmp_path):
    sink = make_sink(tmp_path)
    sink.record_span("sched/step", 0.0, 0.01, attrs={"iter": 1},
                     flow_out=["tid/1"])
    sink.record_async("req/decode", "tid", 0.002, 0.006, attrs={"rid": 7},
                      flow_in=["tid/1"])
    sink.event("req/complete", attrs={"tokens": 3}, track="tid")
    sink.close()
    trace = json.load(open(sink.trace_path))["traceEvents"]
    b = next(e for e in trace if e.get("ph") == "b")
    e_ = next(e for e in trace if e.get("ph") == "e")
    assert b["id"] == e_["id"] == "tid" and b["cat"] == "request"
    s = next(e for e in trace if e.get("ph") == "s")
    f = next(e for e in trace if e.get("ph") == "f")
    assert s["id"] == f["id"] == "tid/1"
    inst = next(e for e in trace if e.get("ph") == "i")
    assert inst["id"] == "tid" and inst["args"]["tokens"] == 3
    lines = read_jsonl(sink.jsonl_path)
    dec = next(ev for ev in lines if ev.get("name") == "req/decode")
    assert dec["track"] == "tid" and dec["flow_in"] == ["tid/1"]


def test_traceparent_parsing():
    assert extract_trace_context({"traceparent": TRACEPARENT}) == \
        (TRACE_ID, "00f067aa0ba902b7", True)
    tid, parent, prop = extract_trace_context({"x-request-id": "my-req-42"})
    assert (tid, parent, prop) == ("my-req-42", None, True)
    tid, _, prop = extract_trace_context({})
    assert len(tid) == 32 and not prop
    # malformed traceparent falls back to generation, never raises
    tid, _, prop = extract_trace_context({"traceparent": "garbage"})
    assert len(tid) == 32 and not prop


# ---------------------------------------------------------------------------
# disabled hot path (CI overhead guard, part 1)
# ---------------------------------------------------------------------------
def test_disabled_sink_hot_path_is_inert(tmp_path):
    sink = TelemetrySink({"enabled": False, "output_path": str(tmp_path / "t")})
    # a disabled sink's span only annotates the profiler: the sink records
    # nothing of it (its own events are checked empty below)
    with sink.span("a"), sink.span("b", attr=1):
        pass
    sink.histogram("h", 1.0)
    sink.counter("c", 1)
    sink.event("e")
    sink.record_async("req/x", "t", 0.0, 0.0)
    assert sink._hists == {} and sink._counters == {} and sink._buffer == []
    assert sink.flight is None
    assert sink.dump_flight("nope") is None
    assert not (tmp_path / "t").exists()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_ring_and_dump(tmp_path):
    sink = make_sink(tmp_path,
                     flight_recorder={"capacity": 64, "post_window_s": 0.0,
                                      "min_interval_s": 0.0})
    for i in range(500):
        sink.counter("serving/decode_steps")
        sink.histogram("serving/step_ms", float(i))
    path = sink.dump_flight("test_anomaly", {"detail": 42})
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "test_anomaly" and doc["attrs"] == {"detail": 42}
    assert len(doc["events_before"]) <= 64  # ring bound held
    names = {ev[2] for ev in doc["events_before"]}
    assert "serving/step_ms" in names
    # full resolution: the ring keeps raw observations, not summaries
    last = [ev for ev in doc["events_before"] if ev[2] == "serving/step_ms"][-1]
    assert last[1] == "hist" and last[3] == 499.0


def test_flight_dump_finalizes_on_idle_sink(tmp_path):
    """A dump must land shortly after its post-window even when NO further
    telemetry arrives (SIGUSR1 on a quiet server): dump_flight schedules
    its own finalizing flush instead of waiting on the next event."""
    sink = make_sink(tmp_path,
                     flight_recorder={"post_window_s": 0.1,
                                      "min_interval_s": 0.0})
    sink.counter("a_little_context")
    path = sink.dump_flight("sigusr1")
    assert path is not None and not os.path.exists(path)
    deadline = time.time() + 5
    while time.time() < deadline and not os.path.exists(path):
        time.sleep(0.02)
    assert os.path.exists(path), "idle dump never finalized"
    assert any(ev[2] == "a_little_context"
               for ev in json.load(open(path))["events_before"])


def test_flight_recorder_post_window_and_rate_limit(tmp_path):
    sink = make_sink(tmp_path,
                     flight_recorder={"post_window_s": 0.1,
                                      "min_interval_s": 10.0})
    sink.counter("before_trigger")
    path = sink.dump_flight("anomaly")
    assert path is not None
    # rate-limited: a second trigger inside min_interval_s is dropped
    assert sink.dump_flight("storm_echo") is None
    sink.counter("after_trigger")
    time.sleep(0.12)
    sink.flush()  # post-window elapsed -> dump finalizes
    doc = json.load(open(path))
    assert any(ev[2] == "before_trigger" for ev in doc["events_before"])
    assert any(ev[2] == "after_trigger" for ev in doc["events_after"])


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------
def test_slo_ratio_objective_burn_and_recovery(tmp_path):
    sink = make_sink(tmp_path)
    slo = SLOEngine(sink, {"fast_window_s": 0.2, "slow_window_s": 0.4,
                           "eval_interval_s": 0.0,
                           "objectives": [{"name": "err", "kind": "ratio",
                                          "num": ["errors"], "den": ["requests"],
                                          "max": 0.05}]})
    alerts = []
    slo.on_alert.append(alerts.append)
    for _ in range(20):
        sink.counter("requests")
    sink.counter("errors", 10)
    state = slo.evaluate()
    obj = state["objectives"][0]
    assert obj["burn_fast"] >= 1.0 and obj["burning"], obj
    assert alerts and alerts[0]["name"] == "err"
    assert slo.alerts == 1 and sink.counter_total("slo/alerts") == 1
    # a second evaluation while still burning is NOT a new alert transition
    slo.evaluate()
    assert slo.alerts == 1
    # recovery: enough clean traffic after the windows roll over
    time.sleep(0.45)
    for _ in range(500):
        sink.counter("requests")
    slo.evaluate()
    assert not slo.state()["objectives"][0]["burning"]
    sink.flush()
    assert any(ev["name"] == "slo/recovered"
               for ev in read_jsonl(sink.jsonl_path) if ev["type"] == "event")


def test_slo_histogram_and_gauge_objectives(tmp_path):
    sink = make_sink(tmp_path)
    slo = SLOEngine(sink, {"fast_window_s": 5.0, "slow_window_s": 10.0,
                           "eval_interval_s": 0.0,
                           "objectives": [
                               {"name": "lat_p95", "kind": "histogram",
                                "metric": "lat_ms", "threshold": 100.0,
                                "target": 0.95},
                               {"name": "mfu_floor", "kind": "gauge_min",
                                "metric": "mfu", "min": 0.3, "budget": 0.5}]})
    for _ in range(80):
        sink.histogram("lat_ms", 10.0)
    for _ in range(20):
        sink.histogram("lat_ms", 500.0)  # 20% over threshold >> 5% budget
    sink.gauge("mfu", 0.1)  # under the floor
    state = slo.evaluate()
    by_name = {o["name"]: o for o in state["objectives"]}
    assert by_name["lat_p95"]["burn_fast"] > 1.0, by_name["lat_p95"]
    assert by_name["mfu_floor"]["burn_fast"] > 1.0
    sink.gauge("mfu", 0.9)
    slo.evaluate()
    gauges = sink.snapshot()["gauges"]
    assert "slo/lat_p95/burn_rate" in gauges and "slo/mfu_floor/burning" in gauges


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------
_PROM_LINE = re.compile(
    r"^(# (TYPE|HELP) .*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"([0-9eE.+-]+|NaN|[+-]Inf)( [0-9]+)?)$")


def test_prometheus_render_parseable(tmp_path):
    sink = make_sink(tmp_path)
    sink.counter("gateway/requests", 3)
    sink.counter("gateway/tenant/acme-corp/tokens", 42)
    # labeled comm family INTERLEAVED (by raw-name sort order) with plain
    # comm counters: samples of one metric must still group contiguously
    sink.counter("comm/all_reduce/data/bytes", 1 << 20)
    sink.counter("comm/grad_sync/bytes", 1 << 10)
    sink.counter("comm/reduce_scatter/tensor/bytes", 1 << 18)
    sink.gauge("serving/slot_occupancy", 0.75)
    # a diverging run's NaN loss must not fail the whole scrape
    sink.gauge("Train/Samples/train_loss", float("nan"))
    sink.gauge("grad_overflow_peak", float("inf"))
    for v in (1.0, 2.0, 3.0):
        sink.histogram("gateway/ttfb_ms", v)
    text = prom_render(sink.snapshot(), extra_gauges={"gateway/queue_depth": 2})
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"unparseable exposition line: {line!r}"
    assert "dstpu_Train_Samples_train_loss NaN" in text
    assert "dstpu_grad_overflow_peak +Inf" in text
    # contiguous-group rule (text format 0.0.4): once a metric's samples
    # end, its name never reappears
    seen, closed = [], set()
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        metric = line.split("{")[0].split(" ")[0]
        if seen and seen[-1] != metric:
            closed.add(seen[-1])
            assert metric not in closed, f"metric {metric} split into groups"
        seen.append(metric)
    assert 'dstpu_comm_bytes_total{op="reduce_scatter",group="tensor"}' in text
    assert 'dstpu_gateway_tenant_tokens_total{tenant="acme-corp"} 42' in text
    assert 'dstpu_comm_bytes_total{op="all_reduce",group="data"}' in text
    assert "dstpu_gateway_queue_depth 2" in text
    assert 'dstpu_gateway_ttfb_ms{quantile="0.95"}' in text
    assert "dstpu_gateway_ttfb_ms_count 3" in text


# ---------------------------------------------------------------------------
# comm overlap accounting
# ---------------------------------------------------------------------------
def test_comm_overlap_tracker_unions_and_efficiency():
    tr = CommOverlapTracker()
    # async flow: dispatch stamped, realized fenced off-thread, nothing exposed
    t0 = time.perf_counter()
    time.sleep(0.01)
    tr.track_async("host_to_device", np.zeros(4), t0=t0)
    # synchronous host collective: fully exposed
    with tr.track_host("barrier"):
        time.sleep(0.02)
    stats = tr.collect(reset=True)
    ops = stats["ops"]
    assert ops["host_to_device"]["realized_s"] >= 0.01
    assert ops["host_to_device"]["exposed_s"] == 0.0
    assert ops["barrier"]["realized_s"] >= 0.02
    assert ops["barrier"]["exposed_s"] >= 0.02
    assert 0.0 < stats["overlap_efficiency"] < 1.0
    # reset drained everything
    assert tr.collect()["ops"] == {}


def test_comm_overlap_busy_union_not_sum():
    tr = CommOverlapTracker()
    # two fully-overlapping spans must count the wall time ONCE
    tr._bump_busy("put", 1.0, 2.0)
    tr._bump_busy("put", 1.2, 1.8)  # inside the counted region
    tr._bump_busy("put", 1.5, 2.5)  # extends by 0.5
    assert abs(tr.collect()["ops"]["put"]["realized_s"] - 1.5) < 1e-9


def test_train_step_emits_comm_overlap_gauges(tmp_path):
    """Acceptance: a telemetry-enabled step reports realized (fenced)
    comm transfer time and an overlap efficiency — the same contract the
    multichip dryrun asserts on the CPU mesh."""
    set_sink(None)
    comm._state["mesh"] = None
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 1,
           "telemetry": {"enabled": True, "output_path": str(tmp_path / "t")}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=32),
                                               config=cfg, rng_seed=0)
    engine.train_batch(batch=random_batch(engine.train_batch_size(), 32))
    gauges = engine.telemetry.snapshot()["gauges"]
    realized = {k: v for k, v in gauges.items()
                if k.startswith("comm/") and k.endswith("/realized_ms")}
    assert realized and any(v > 0 for v in realized.values()), gauges
    assert "comm/host_to_device/dispatch_ms" in gauges
    assert 0.0 <= gauges["comm/overlap_efficiency"] <= 1.0
    engine.telemetry.close()


# ---------------------------------------------------------------------------
# gateway e2e: the acceptance span tree + endpoints + storm
# ---------------------------------------------------------------------------
def make_gateway(tmp_path, *, params=None, num_slots=2, tel_over=None, **gw):
    from deepspeed_tpu.serving import Gateway
    comm._state["mesh"] = None
    set_sink(None)
    tel = {"enabled": True, "output_path": str(tmp_path / "tel"),
           "flush_interval": 16,
           "flight_recorder": {"post_window_s": 0.05, "min_interval_s": 0.0}}
    tel.update(tel_over or {})
    eng = deepspeed_tpu.init_inference(
        "tiny", config={"dtype": "float32",
                        "continuous_batching": {"enabled": True,
                                                "num_slots": num_slots},
                        "telemetry": tel},
        params=params)
    gateway = Gateway(eng, port=0, **gw)
    gateway.start_background()
    return gateway


def http_post(port, body, headers=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json", **(headers or {})})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def http_get(port, path, headers=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def test_gateway_traceparent_yields_connected_span_tree(tmp_path):
    """THE tracing acceptance test: load trace.json and walk the links."""
    gw = make_gateway(tmp_path)
    tel = gw.telemetry
    try:
        status, headers, _ = http_post(gw.port, {"prompt": PROMPT, "max_tokens": 6},
                                       {"traceparent": TRACEPARENT})
        assert status == 200
        assert headers.get("x-request-id") == TRACE_ID
    finally:
        assert gw.close(timeout=60)
    tel.close()
    trace = json.load(open(tel.trace_path))["traceEvents"]

    # 1. the request's phase tree: async b/e pairs on the request's track
    # (the trace id suffixed with the gateway rid, so a client REUSING an
    # x-request-id across retries can never interleave two trees)
    tracks = {e["id"] for e in trace if e.get("cat") == "request"
              and str(e.get("id", "")).startswith(TRACE_ID)}
    assert len(tracks) == 1, tracks
    track = tracks.pop()
    assert track.startswith(TRACE_ID + ":")
    phases = [e for e in trace if e.get("cat") == "request"
              and e.get("id") == track]
    begins = {e["name"]: e["ts"] for e in phases if e["ph"] == "b"}
    ends = {e["name"]: e["ts"] for e in phases if e["ph"] == "e"}
    for name in ("req/queued", "req/prefill", "req/decode"):
        assert name in begins and name in ends, sorted(begins)
        assert ends[name] >= begins[name]
    assert begins["req/queued"] <= begins["req/prefill"] <= begins["req/decode"]
    # milestones carry the same track id
    instants = {e["name"] for e in trace if e.get("ph") == "i"
                and e.get("id") == track}
    assert {"req/admitted", "req/complete"} <= instants, instants

    # 2. flow links connect request phases to scheduler iteration spans
    finishes = [e for e in trace if e.get("ph") == "f"
                and str(e.get("id", "")).startswith(TRACE_ID)]
    starts = {e["id"]: e for e in trace if e.get("ph") == "s"}
    iters = [e for e in trace if e.get("ph") == "X" and e["name"] == "sched/step"]
    assert finishes and iters
    for f in finishes:
        s = starts.get(f["id"])
        assert s is not None, f"flow {f['id']} has no source"
        # flows must run FORWARD in time (Perfetto drops backward links)
        assert s["ts"] <= f["ts"], f"flow {f['id']} runs backward"
        # the flow start sits inside one sched/step span on the same track
        assert any(e["tid"] == s["tid"] and e["ts"] <= s["ts"] <= e["ts"] + e["dur"]
                   for e in iters), f"flow {f['id']} not anchored in an iteration"

    # 3. the JSONL stream carries the same tree (the trace_summary substrate)
    events = read_jsonl(tel.jsonl_path)
    req_lines = [ev for ev in events
                 if str(ev.get("track", "")).startswith(TRACE_ID)]
    assert {ev["name"] for ev in req_lines} >= {"req/queued", "req/prefill",
                                                "req/decode", "req/complete"}
    complete = next(ev for ev in req_lines if ev["name"] == "req/complete")
    assert complete["attrs"]["tokens"] == 6
    assert complete["attrs"]["ttft_ms"] > 0


def test_gateway_prometheus_exposition(tmp_path):
    gw = make_gateway(tmp_path)
    try:
        http_post(gw.port, {"prompt": PROMPT, "max_tokens": 4})
        # scraper Accept -> text exposition
        status, headers, body = http_get(
            gw.port, "/v1/metrics",
            {"Accept": "text/plain;version=0.0.4;q=0.9,*/*;q=0.1"})
        assert status == 200 and headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        for line in text.strip().splitlines():
            assert _PROM_LINE.match(line), f"unparseable: {line!r}"
        assert "dstpu_gateway_requests_total 1" in text
        assert "dstpu_scheduler_num_slots 2" in text
        # explicit query param works for curl users
        status, headers, _ = http_get(gw.port, "/v1/metrics?format=prometheus")
        assert headers["Content-Type"].startswith("text/plain")
        # default stays JSON (back-compat with every existing consumer)
        status, headers, body = http_get(gw.port, "/v1/metrics")
        assert headers["Content-Type"] == "application/json"
        assert json.loads(body)["gateway"]["completed"] == 1
    finally:
        assert gw.close(timeout=60)


def test_gateway_slo_endpoint_and_debug_flight(tmp_path):
    gw = make_gateway(tmp_path)
    try:
        status, _, body = http_get(gw.port, "/v1/slo")
        assert status == 200
        slo = json.loads(body)
        assert slo["enabled"]
        names = {o["name"] for o in slo["objectives"]}
        assert {"ttft_p95", "queue_wait_p95", "itl_p95", "error_rate"} <= names
        status, _, body = http_get(gw.port, "/v1/debug/flight")
        assert status == 200
        dump_path = json.loads(body)["path"]
    finally:
        assert gw.close(timeout=60)
    gw.telemetry.close()
    assert os.path.exists(dump_path)


def test_deadline_storm_trips_slo_alert_and_flight_dump(tmp_path):
    """THE anomaly acceptance test: a deadline-expiry storm burns the
    error-rate budget, the alert fires, and the flight recorder dumps the
    iterations surrounding the trip."""
    gw = make_gateway(
        tmp_path, num_slots=1,
        tel_over={"slo": {"fast_window_s": 0.3, "slow_window_s": 0.6,
                          "eval_interval_s": 0.02, "burn_threshold": 1.0,
                          "objectives": [
                              {"name": "error_rate", "kind": "ratio",
                               "num": ["gateway/deadline_expired"],
                               "den": ["gateway/requests"], "max": 0.05}]}})
    tel = gw.telemetry
    try:
        # park the single slot so the storm's queued requests expire
        occupier = threading.Thread(
            target=http_post, args=(gw.port, {"prompt": PROMPT,
                                              "max_tokens": 192}))
        occupier.start()
        time.sleep(0.2)
        storm = [threading.Thread(
            target=http_post, args=(gw.port, {"prompt": [7, 7], "max_tokens": 4,
                                              "timeout_s": 0.02}))
            for _ in range(8)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()
        deadline = time.time() + 20
        while time.time() < deadline and tel.counter_total("slo/alerts") == 0:
            time.sleep(0.02)
        assert tel.counter_total("slo/alerts") >= 1, "storm did not trip the SLO"
        assert gw.stats["deadline_expired"] >= 4
        occupier.join()
    finally:
        assert gw.close(timeout=120)
    tel.close()
    dumps = [f for f in os.listdir(tel.output_path)
             if f.startswith("flight_") and "slo_burn_error_rate" in f]
    assert dumps, os.listdir(tel.output_path)
    doc = json.load(open(os.path.join(tel.output_path, dumps[0])))
    names = {ev[2] for ev in doc["events_before"] + doc["events_after"]}
    # the dump shows the scheduler iterations and expiries around the trip
    assert "sched/step" in names or "serving/step_ms" in names, sorted(names)[:20]
    assert "gateway/deadline_expired" in names
    # the alert itself is in the JSONL stream
    events = read_jsonl(tel.jsonl_path)
    alerts = [ev for ev in events if ev.get("name") == "slo/alert"]
    assert alerts and alerts[0]["attrs"]["objective"] == "error_rate"


# ---------------------------------------------------------------------------
# CI overhead guard, part 2: enabled tracing stays bounded on the hot path
# ---------------------------------------------------------------------------
def _timed_decode(tmp_path, tag, telemetry_cfg):
    comm._state["mesh"] = None
    set_sink(None)
    cfg = {"dtype": "float32",
           "continuous_batching": {"enabled": True, "num_slots": 2}}
    if telemetry_cfg:
        cfg["telemetry"] = telemetry_cfg
    eng = deepspeed_tpu.init_inference("tiny", config=cfg)
    sched = eng.scheduler()
    sched.submit(PROMPT, max_new_tokens=32).result()  # warm the programs
    t0 = time.perf_counter()
    sched.submit(PROMPT, max_new_tokens=96).result()
    dur = time.perf_counter() - t0
    if telemetry_cfg:
        eng.telemetry.close()
    set_sink(None)
    return dur


@pytest.mark.parametrize("_", [0])
def test_tracing_overhead_bounded(tmp_path, _):
    """CI guard: full request tracing must not multiply the decode step
    time. The bound is deliberately loose (CI boxes are noisy) — it exists
    to catch an accidental O(tokens) sync or per-token file write, not to
    benchmark."""
    base = _timed_decode(tmp_path / "off", "off", None)
    traced = _timed_decode(tmp_path / "on", "on", {
        "enabled": True, "output_path": str(tmp_path / "on" / "tel"),
        "request_tracing": True})
    assert traced < base * 3.0 + 0.25, (
        f"tracing overhead blew the budget: {base:.3f}s untraced vs "
        f"{traced:.3f}s traced")


# ---------------------------------------------------------------------------
# trace_summary --requests
# ---------------------------------------------------------------------------
def test_trace_summary_per_request_view(tmp_path):
    sink = make_sink(tmp_path)
    for i, (tid, ttft) in enumerate([("req-slow", 900.0), ("req-fast", 30.0)]):
        tr = RequestTrace(sink, tid, tenant="acme")
        tr.rid = i
        tr.phase("queued", start=0.0, end=0.01)
        tr.phase("prefill", start=0.01, end=0.01 + ttft / 1e3, ttft_ms=ttft)
        tr.phase("decode", start=0.02 + ttft / 1e3, end=0.1 + ttft / 1e3)
        tr.instant("complete", reason="length", tokens=8, ttft_ms=ttft,
                   itl_ms=2.0)
    sink.close()
    tool = os.path.join(REPO_ROOT, "tools", "trace_summary.py")
    proc = subprocess.run([sys.executable, tool, sink.jsonl_path,
                           "--requests", "5"],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    out_lines = proc.stdout.strip().splitlines()
    assert "top 2 requests by ttft" in out_lines[0]
    # sorted by TTFT: the slow request leads, with its phase breakdown
    assert out_lines[2].startswith("req-slow") and "acme" in out_lines[2]
    assert "900.0" in out_lines[2]
    assert out_lines[3].startswith("req-fast")
    # the aggregate view still works on the same file
    proc = subprocess.run([sys.executable, tool, sink.jsonl_path],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
