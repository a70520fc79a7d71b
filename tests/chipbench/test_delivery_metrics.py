"""The per-layer metrics PR 52 adds over the account of the host's two
threads (``telemetry/capacity.py: HostGapTracker``, ``Delivery``): seven data
files on the built-in ``hist_quantile`` and one reader of the device trace,
``loop_send_trace_pct``. Each lists exactly the cells that report
``serve_tokens_per_s``, ``BENCHMARK.json`` says what the files say, the
harness finds them for a cell whose own file does not name them, a snapshot
of a bound tracker gives every data metric a number, and a program without
the histograms or the span (the parent) gives nothing and raises nothing."""

import json
import os

import pytest

from chipbench import cells, reducers
from chipbench.tests.test_xplane import fixture, trace  # noqa: F401  (pytest fixtures)
from deepspeed_tpu.telemetry.capacity import Delivery, HostGapTracker

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# metric: (histogram, quantile, layer, unit, source)
DATA_METRICS = {
    "delivery_lag_ms": ("gateway/delivery_lag_ms", "p50", "gateway", "ms", "program_span"),
    "delivery_lag_max_ms": ("gateway/delivery_lag_max_ms", "p95", "gateway", "ms",
                            "program_span"),
    "delivery_backlog_events": ("gateway/backlog_events", "p50", "gateway", "events",
                                "program_counter"),
    "loop_cpu_us_per_event": ("gateway/loop_cpu_us_per_event", "p50", "gateway", "us",
                              "program_counter"),
    "loop_cpu_ms": ("serving/loop_cpu_ms", "p50", "gateway", "ms", "program_counter"),
    "pump_cpu_ms": ("serving/pump_cpu_ms", "p50", "scheduler", "ms", "program_counter"),
    "host_threads_cpu_pct": ("serving/host_threads_cpu_pct", "p50", "scheduler", "%",
                             "program_counter"),
}
TRACE_METRIC = "loop_send_trace_pct"
# PR 53's one data file on the built-in ``counter_ratio``: events a hundred
# tokens the loop wrote (100 where an event is a token, ~25 where it is a
# row's four of a landing)
ENGAGEMENT = "sse_events_per_100_tokens"
ALL_METRICS = sorted(DATA_METRICS) + [TRACE_METRIC]
CELL_9 = "lfm2-8b-a1b.serve.reason-closed"


def _spec(name):
    with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _serving(bench):
    rate, = [m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    return rate["workloads"]


@pytest.mark.parametrize("name", ALL_METRICS)
def test_metric_file_lists_exactly_the_cells_of_the_serving_rate(name):
    bench, spec = _bench(), _spec(name)
    # the seven serving cells the benchmark had when the file was written; a
    # cell appended since (PR 54's tenth) names the metric in its own file
    assert spec["workloads"] == _serving(bench)[:7] and len(spec["workloads"]) == 7
    for later in _serving(bench)[7:]:
        assert name in cells.load_workload(later)[1]["per_layer"], later
    assert spec["moves"] == "serve_tokens_per_s" and spec["better"] == "lower"
    if name in DATA_METRICS:
        hist, quantile, layer, unit, source = DATA_METRICS[name]
        assert spec["reducer"] == "hist_quantile"
        assert spec["args"] == {"histogram": hist, "quantile": quantile}
        assert (spec["layer"], spec["unit"], spec["source"]) == (layer, unit, source)
    else:
        assert (spec["layer"], spec["unit"], spec["source"]) == ("gateway", "%", "device_trace")
        assert "reducer" not in spec


@pytest.mark.parametrize("name", ALL_METRICS)
def test_benchmark_entry_says_what_the_file_says(name):
    bench, spec = _bench(), _spec(name)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
    # the file's cells first, then the serving cells appended since
    assert entry["workloads"] == _serving(bench) and entry["workloads"][:7] == spec["workloads"]
    # appended behind everything the benchmark had then, in this order (PR 53's
    # one behind them; later PRs' metrics behind that)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("delivery_lag_ms")
    assert names[first:first + 9] == [
        "delivery_lag_ms", "delivery_lag_max_ms", "delivery_backlog_events",
        "loop_cpu_us_per_event", "loop_cpu_ms", "pump_cpu_ms", "host_threads_cpu_pct",
        "loop_send_trace_pct", ENGAGEMENT]


@pytest.mark.parametrize("name", ALL_METRICS)
def test_harness_finds_the_metric_for_the_serving_cells_alone(name):
    bench = _bench()
    for cell in bench["workloads"]:
        _, workload, root = cells.load_workload(cell["name"])
        found = cells.per_layer_metrics(cell["name"], workload, root)
        assert (name in found) == (cell["name"] in _serving(bench)), cell["name"]
    # cell 9's own file names none of them: the metric's file brings it
    _, workload, root = cells.load_workload(CELL_9)
    assert name not in workload.get("per_layer", ())
    metric = cells.per_layer_metrics(CELL_9, workload, root)[name]
    assert (cells.custom_reducer(metric) is not None) == (name == TRACE_METRIC)


class _Sink:
    enabled = True

    def __init__(self):
        self.hists = {}

    def counter(self, name, value=1, attrs=None):
        pass

    def histogram(self, name, value, attrs=None):
        self.hists.setdefault(name, []).append(value)


@pytest.fixture(scope="module")
def account_obs():
    """A snapshot-shaped ``obs`` of a bound tracker after four landed syncs
    of 10 ms: 6 ms of pump CPU and 3 of the loop's each, 10 events posted and
    8 written a sync, each 2.5 ms (at most 4) behind its landing."""
    sink, sent, clocks = _Sink(), Delivery(), {"pump": 0.0, "loop": 0.0}
    gap = HostGapTracker(sink, unlanded=lambda: True)
    gap.bind_threads(lambda: clocks["pump"], sent, lambda: clocks["loop"], primary=True)
    t = 0.0
    for sync in range(4):
        gap.span_enter("sched/step", t)
        clocks["pump"] += 0.006
        clocks["loop"] += 0.003
        gap.posted += 10
        sent.lag_s += 8 * 0.0025
        sent.lag_max_s = 0.004
        sent.bytes += 800
        sent.writes += 8
        sent.events += 8
        gap.span_enter("sched/fetch", t + 0.006)
        gap.span_exit("sched/fetch", t + 0.006, t + 0.010)
        gap.span_exit("sched/step", t, t + 0.010)
        t += 0.010
    hists = {}
    for name, values in sink.hists.items():
        ordered = sorted(values)
        hists[name] = {"count": len(values), "window_count": len(values),
                       "p50": ordered[len(ordered) // 2], "p95": ordered[-1], "p99": ordered[-1]}
    return {"telemetry": {"histograms": hists, "counters": {}}}


@pytest.mark.parametrize("name", sorted(DATA_METRICS))
def test_data_metric_reads_the_account_s_snapshot(account_obs, name):
    spec = _spec(name)
    got = reducers.BUILTIN[spec["reducer"]](spec["args"], account_obs)
    want = {"delivery_lag_ms": 2.5, "delivery_lag_max_ms": 4.0, "delivery_backlog_events": 6,
            "loop_cpu_us_per_event": 375.0, "loop_cpu_ms": 3.0, "pump_cpu_ms": 6.0,
            "host_threads_cpu_pct": 90.0}[name]
    assert got == pytest.approx(want)
    # the parent's snapshot: the pump's older histograms and none of these
    parent = {"telemetry": {"histograms": {"serving/pump_busy_ms": {
        "count": 3, "window_count": 3, "p50": 6.0, "p95": 6.0}}, "counters": {}}}
    assert reducers.BUILTIN[spec["reducer"]](spec["args"], parent) is None
    assert reducers.BUILTIN[spec["reducer"]](spec["args"], {}) is None


def test_loop_send_trace_pct_on_the_xplane_fixture(trace):  # noqa: F811
    reduce = cells.custom_reducer({"name": TRACE_METRIC,
                                   "dir": os.path.join(ROOT, "chipbench", "metrics")})
    # the recorded trace is a program's from before the span existed
    assert reduce({"program_trace": trace}) is None
    assert reduce({"program_trace": None}) is None
    # three sends in the 10 s window: [1.0, 1.5), [1.25, 2.0) overlapping it (a send
    # another coroutine opened while the first one's drain yielded), [9.5, 10.5) cut
    # at the window's end: 1.0 + 0.5 of 10 s
    sends = [("dstpu/gateway/send", 1.0, 0.5), ("dstpu/gateway/send", 1.25, 0.75),
             ("dstpu/gateway/send", 9.5, 1.0)]
    sent = dict(trace, host=sorted(trace["host"] + sends, key=lambda ev: ev[1]))
    assert reduce({"program_trace": sent}) == pytest.approx(15.0)
    late = dict(sent, t0=9.0, t1=10.0)
    assert reduce({"program_trace": late}) == pytest.approx(50.0)
    # the pump's reader beside it does not see the loop's span
    wait = cells.custom_reducer({"name": "pump_wait_trace_pct",
                                 "dir": os.path.join(ROOT, "chipbench", "metrics")})
    assert wait({"program_trace": sent}) == wait({"program_trace": trace})


# ------------------------------------------------ PR 53: events a hundred tokens
def test_engagement_metric_is_data_alone_and_says_what_the_benchmark_says():
    bench, spec = _bench(), _spec(ENGAGEMENT)
    assert not os.path.exists(os.path.join(ROOT, "chipbench", "metrics", ENGAGEMENT + ".py"))
    assert spec["reducer"] == "counter_ratio"
    assert spec["args"] == {"num": "gateway/sse_events", "den": ["gateway/sse_tokens"]}
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"], spec["moves"]) == (
        "gateway", "events", "lower", "program_counter", "serve_tokens_per_s")
    assert spec["workloads"] == _serving(bench)[:7] and len(spec["workloads"]) == 7
    entry, = [m for m in bench["per_layer"] if m["name"] == ENGAGEMENT]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert entry["workloads"] == _serving(bench)  # (the cells appended since name it themselves)


def test_training_cells_load_nothing_of_the_delivery():
    """The two training cells build no gateway: none of PR 52's eight metrics
    nor PR 53's one is found for them, and every serving cell finds all nine."""
    bench = _bench()
    training = [c["name"] for c in bench["workloads"] if c["name"] not in _serving(bench)]
    assert sorted(training) == ["gpt2-large.train.s1024", "opt-1.3b.train.zero3.s2048"]
    ours = set(ALL_METRICS) | {ENGAGEMENT}
    for cell in bench["workloads"]:
        _, workload, root = cells.load_workload(cell["name"])
        found = ours & set(cells.per_layer_metrics(cell["name"], workload, root))
        assert found == (set() if cell["name"] in training else ours), cell["name"]


@pytest.mark.parametrize("events,tokens,want", [
    (96, 384, 25.0), (100, 384, pytest.approx(26.0416667)), (384, 384, 100.0)],
    ids=["a-row-s-four", "with-partial-landings", "a-token-an-event"])
def test_engagement_metric_reads_the_two_counters(events, tokens, want):
    spec = _spec(ENGAGEMENT)
    reduce = reducers.BUILTIN[spec["reducer"]]
    obs = {"telemetry": {"histograms": {}, "counters": {
        "gateway/sse_events": {"total": events}, "gateway/sse_tokens": {"total": tokens}}}}
    assert reduce(spec["args"], obs) == want
    # the parent counts events and no tokens: nothing to read, nothing raised
    parent = {"telemetry": {"histograms": {}, "counters": {"gateway/sse_events": {"total": events}}}}
    assert reduce(spec["args"], parent) is None
    assert reduce(spec["args"], {}) is None
