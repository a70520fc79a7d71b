"""The scheduler-layer metrics of a pump that runs ahead, read from a real
sink snapshot through the benchmark's own data files and built-in reducers:
``pump_ahead_pct`` (PR 33) finds its counters, ``sched_host_gap_ms`` still
finds observations (0.0 each sync that was launched ahead), and a program
without the counters (the parent) gives nothing and raises nothing."""

import json
import os

import numpy as np

import deepspeed_tpu
from chipbench import reducers
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry import set_sink

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _metric(name):
    with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as f:
        spec = json.load(f)
    return lambda obs: reducers.BUILTIN[spec["reducer"]](spec["args"], obs)


def test_pump_metrics_read_a_snapshot_of_the_pump(tmp_path):
    comm._state["mesh"] = None
    set_sink(None)
    eng = deepspeed_tpu.init_inference("tiny", config={
        "dtype": "float32", "max_out_tokens": 256,
        "continuous_batching": {"enabled": True, "num_slots": 4},
        "telemetry": {"enabled": True, "hist_window_s": 600, "output_path": str(tmp_path)}})
    try:
        sched = eng.scheduler()
        rng = np.random.default_rng(5)
        for n in (40, 17, 90):
            sched.submit(rng.integers(0, 256, n).astype(np.int32), max_new_tokens=20)
        sched.drain()
        obs = {"telemetry": eng.telemetry.snapshot()}
        ahead = _metric("pump_ahead_pct")(obs)
        assert ahead == 100.0 * sched.syncs_ahead / (sched.syncs_ahead + sched.syncs_serial)
        assert 50.0 < ahead < 100.0
        assert _metric("sched_host_gap_ms")(obs) == 0.0  # most syncs found the device busy
        assert _metric("serve_step_ms")(obs) > 0.0
    finally:
        eng.telemetry.close()
        set_sink(None)
    # the parent has no such counters: nothing to read, nothing raised
    assert _metric("pump_ahead_pct")({"telemetry": {"counters": {}}}) is None
    assert _metric("pump_ahead_pct")({}) is None


def test_pump_ahead_pct_is_listed_where_it_can_be_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    with open(os.path.join(ROOT, "chipbench", "metrics", "pump_ahead_pct.json")) as f:
        spec = json.load(f)
    serving = [w["name"] for w in bench["workloads"] if ".serve." in w["name"]]
    assert entry["name"] == "pump_ahead_pct" and entry["workloads"] == spec["workloads"] == serving
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        spec["layer"], spec["moves"], spec["source"]) == (
        "scheduler", "serve_tokens_per_s", "program_counter")
