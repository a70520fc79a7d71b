"""The scheduler-layer metrics of a pump that runs ahead, read from a real
sink snapshot through the benchmark's own data files and built-in reducers:
``pump_ahead_pct`` (PR 33) finds its counters, ``sched_host_gap_ms`` still
finds observations (0.0 each sync that was launched ahead), the account's
metrics (PR 34: ``pump_host_busy_pct``, ``pump_busy_ms``, ``pump_wait_ms``,
the six ``pump_busy_<bucket>_pct``) read what the tracker kept, and a
program without the counters (the parent) gives nothing and raises nothing.
The two readers of the device trace that PR 34 adds (``pump_wait_trace_pct``,
``lm_head_device_pct``) on the fixture ``chipbench/tests/test_xplane.py``
writes."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from chipbench import cells, reducers
from chipbench.tests.test_xplane import fixture, trace  # noqa: F401  (pytest fixtures)
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry import set_sink

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BUCKET_METRICS = [f"pump_busy_{b}_pct" for b in ("deliver", "assemble", "dispatch", "admit",
                                                 "gateway", "other")]
ACCOUNT_METRICS = ["pump_host_busy_pct", "pump_busy_ms", "pump_wait_ms"] + BUCKET_METRICS
TRACE_METRICS = ["pump_wait_trace_pct", "lm_head_device_pct"]
# each data-file metric of the pump with what it is listed as: source, better
DATA_METRICS = {"pump_ahead_pct": ("program_counter", "higher"),
                "pump_host_busy_pct": ("program_counter", "lower"),
                "pump_busy_ms": ("program_span", "lower"),
                "pump_wait_ms": ("program_span", "higher"),
                **{name: ("program_counter", "lower") for name in BUCKET_METRICS}}


def _spec(name):
    with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as f:
        return json.load(f)


def _metric(name):
    spec = _spec(name)
    return lambda obs: reducers.BUILTIN[spec["reducer"]](spec["args"], obs)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _serving(bench):
    return [w["name"] for w in bench["workloads"] if ".serve." in w["name"]]


@pytest.fixture(scope="module")
def pump_obs(tmp_path_factory):
    """(obs with a snapshot of a tiny pump's sink, its scheduler)."""
    comm._state["mesh"] = None
    set_sink(None)
    eng = deepspeed_tpu.init_inference("tiny", config={
        "dtype": "float32", "max_out_tokens": 256,
        "continuous_batching": {"enabled": True, "num_slots": 4},
        "telemetry": {"enabled": True, "hist_window_s": 600,
                      "output_path": str(tmp_path_factory.mktemp("telemetry"))}})
    try:
        sched = eng.scheduler()
        rng = np.random.default_rng(5)
        for n in (40, 17, 90):
            sched.submit(rng.integers(0, 256, n).astype(np.int32), max_new_tokens=20)
        sched.drain()
        yield {"telemetry": eng.telemetry.snapshot()}, sched
    finally:
        eng.telemetry.close()
        set_sink(None)


def test_pump_metrics_read_a_snapshot_of_the_pump(pump_obs):
    obs, sched = pump_obs
    ahead = _metric("pump_ahead_pct")(obs)
    assert ahead == 100.0 * sched.syncs_ahead / (sched.syncs_ahead + sched.syncs_serial)
    assert 50.0 < ahead < 100.0
    assert _metric("sched_host_gap_ms")(obs) == 0.0  # most syncs found the device busy
    assert _metric("serve_step_ms")(obs) > 0.0
    # the parent has no such counters: nothing to read, nothing raised
    assert _metric("pump_ahead_pct")({"telemetry": {"counters": {}}}) is None
    assert _metric("pump_ahead_pct")({}) is None


@pytest.mark.parametrize("name", ACCOUNT_METRICS)
def test_account_metric_reads_the_tracker_s_numbers(pump_obs, name):
    obs, sched = pump_obs
    got = _metric(name)(obs)
    counters, hists = obs["telemetry"]["counters"], obs["telemetry"]["histograms"]
    busy, wait = (counters[f"serving/pump/{p}_ms"]["total"] for p in ("busy", "wait"))
    if name == "pump_host_busy_pct":
        assert got == pytest.approx(100.0 * busy / (busy + wait)) and 0.0 < got < 100.0
        assert got == pytest.approx(100.0 * sched._gap.busy_s
                                    / (sched._gap.busy_s + sched._gap.wait_s))
    elif name in ("pump_busy_ms", "pump_wait_ms"):
        hist = hists["serving/" + name]
        assert got == hist["p50"] and got >= 0.0
        assert hist["count"] == sched.syncs_ahead + sched.syncs_serial
    else:
        bucket = name[len("pump_busy_"):-len("_pct")]
        part = counters.get(f"serving/pump/{bucket}_ms", {}).get("total", 0.0)
        assert got == pytest.approx(100.0 * part / busy) and 0.0 <= got <= 100.0
    # a snapshot of the parent (its counters and the gap's histogram, none of the account's)
    parent = {"telemetry": {"counters": {"serving/syncs_ahead": {"count": 5, "total": 5},
                                         "serving/decode_steps": {"count": 5, "total": 20}},
                            "histograms": {"serving/host_gap_ms": hists["serving/host_gap_ms"]}}}
    assert _metric(name)(parent) is None and _metric(name)({}) is None


def test_bucket_metrics_add_up_to_busy_less_the_two_unmetered(pump_obs):
    obs, _ = pump_obs
    counters = obs["telemetry"]["counters"]
    busy = counters["serving/pump/busy_ms"]["total"]
    unmetered = sum(counters.get(f"serving/pump/{b}_ms", {}).get("total", 0.0)
                    for b in ("trie_probe", "tier_transfer"))
    assert sum(_metric(name)(obs) for name in BUCKET_METRICS) == pytest.approx(
        100.0 * (1.0 - unmetered / busy))


@pytest.mark.parametrize("name", ACCOUNT_METRICS + TRACE_METRICS)
def test_new_metric_loads_for_the_serving_cells_only(name):
    bench = _bench()
    for cell in bench["workloads"]:
        _, workload, root = cells.load_workload(cell["name"])
        found = cells.per_layer_metrics(cell["name"], workload, root)
        assert (name in found) == (".serve." in cell["name"]), cell["name"]
    has_reader = cells.custom_reducer(dict(_spec(name), name=name, dir=os.path.join(
        ROOT, "chipbench", "metrics"))) is not None
    assert has_reader == (name in TRACE_METRICS)  # the account's metrics are data alone


@pytest.mark.parametrize("name", sorted(DATA_METRICS) + TRACE_METRICS)
def test_metric_is_listed_where_it_can_be_read(name):
    bench = _bench()
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    spec = _spec(name)
    # the metric's file lists the cells it was written for; a cell added since
    # names the metric in its own file (a later PR edits no benchmark file)
    # and joins the list in BENCHMARK.json
    assert entry["workloads"] == _serving(bench)
    assert entry["workloads"][:len(spec["workloads"])] == spec["workloads"]
    for cell in entry["workloads"]:
        _, workload, root = cells.load_workload(cell)
        assert name in cells.per_layer_metrics(cell, workload, root), cell
    assert {k: entry[k] for k in ("layer", "moves", "source", "unit", "better")} == {
        k: spec[k] for k in ("layer", "moves", "source", "unit", "better")}
    assert entry["moves"] == "serve_tokens_per_s"
    if name in DATA_METRICS:
        assert (entry["source"], entry["better"]) == DATA_METRICS[name]
        assert entry["layer"] == "scheduler"
    else:
        assert entry["source"] == "device_trace"
        assert entry["layer"] == {"pump_wait_trace_pct": "scheduler",
                                  "lm_head_device_pct": "model step"}[name]


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(ROOT, "chipbench", "metrics")})


def _bare(trace):  # noqa: F811
    """The fixture's trace with none of the program's marks (no span, no
    scope, no kernel name)."""
    return {"devices": {d: [(n.replace("dstpu_", "closed_call_"), s, dur, "")
                            for n, s, dur, _ in evs] for d, evs in trace["devices"].items()},
            "host": [ev for ev in trace["host"] if ev[0].startswith("chipbench/")],
            "t0": trace["t0"], "t1": trace["t1"]}


def test_pump_wait_trace_pct_on_the_xplane_fixture(trace):  # noqa: F811
    reduce = _reader("pump_wait_trace_pct")
    # sched/fetch covers [5.2, 8.4) of the 10 s window
    assert reduce({"program_trace": trace}) == pytest.approx(32.0)
    # a sampled sync's fence is time blocked on the device too; overlap counts once
    fenced = dict(trace, host=trace["host"] + [("dstpu/sched/fence", 4.4, 0.2),
                                               ("dstpu/sched/fence", 8.0, 1.0)])
    assert reduce({"program_trace": fenced}) == pytest.approx(32.0 + 2.0 + 6.0)
    # cut to the window
    late = dict(trace, t0=6.0, t1=10.0)
    assert reduce({"program_trace": late}) == pytest.approx(100.0 * 2.4 / 4.0)
    assert reduce({"program_trace": None}) is None
    assert reduce({"program_trace": _bare(trace)}) is None


def test_lm_head_device_pct_on_the_xplane_fixture(trace):  # noqa: F811
    reduce = _reader("lm_head_device_pct")
    # the fixture's step has no head: nothing to read
    assert reduce({"program_trace": {k: v for k, v in trace.items() if k != "self_times"}}) is None
    # device 0 idles in [4, 5) and [8, 10): a head there, by each of its marks
    head = [("fusion.40", 4.0, 0.5, "jit(fused)/CausalLM/lm_head/final_norm/mul"),
            ("fusion.41", 4.5, 0.25, "jit(fused)/CausalLM/lm_head/embed.attend/dot_general"),
            ("fusion.42", 8.0, 0.25, "jit(fused)/sample/argmax"),
            ("dstpu_quant_matmul.5", 8.5, 1.0, "jit(fused)/closed_call/pallas_call"),
            ("fusion.43", 9.5, 0.5, "jit(fused)/CausalLM/not_lm_head/add")]
    marked = {"devices": dict(trace["devices"], **{
        "/device:TPU:0": list(trace["devices"]["/device:TPU:0"]) + head}),
        "host": trace["host"], "t0": trace["t0"], "t1": trace["t1"]}
    # 2 s of 10 on one of two devices
    assert reduce({"program_trace": marked}) == pytest.approx(10.0)
    assert reduce({"program_trace": None}) is None
    assert reduce({"program_trace": _bare(trace)}) is None
