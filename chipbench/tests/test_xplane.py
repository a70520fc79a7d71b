"""The shared reader of the program's own marks (``chipbench/xplane.py``) and
every metric file that reads through it, on a small hand-made trace
(``fixtures/program_trace_small.json``) written out as an ``.xplane.pb`` and
read back: the numbers below are worked by hand."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells, trace_reduce, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIX = os.path.join(HERE, "fixtures")
PS = 10**12


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(FIX, "program_trace_small.json")) as f:
        return json.load(f)


def write_xplane(fix, path):
    """The fixture as the profiler would have written it: one plane per
    device (an ``XLA Ops`` line, and a ``Steps`` line the reader skips),
    the scope path in the ``tf_op`` stat of each operation's metadata (once
    as a string, once as a reference), a host plane whose line starts at 1 s."""
    space = xplane._xspace_class()()
    for pname, events in fix["devices"].items():
        plane = space.planes.add(name=pname.encode())
        for key, name in ((1, b"tf_op"), (2, b"hlo_category")):
            plane.stat_metadata.add(key=key).value.name = name
        ops = plane.lines.add(name=b"XLA Ops", timestamp_ns=0)
        for i, (raw, start, dur, scope) in enumerate(events, 1):
            md = plane.event_metadata.add(key=i).value
            md.name = raw.encode()
            md.stats.add(metadata_id=2, str_value=b"fusion")
            if scope and i % 2:
                md.stats.add(metadata_id=1, str_value=scope.encode())
            elif scope:
                plane.stat_metadata.add(key=100 + i).value.name = scope.encode()
                md.stats.add(metadata_id=1, ref_value=100 + i)
            ops.events.add(metadata_id=i, offset_ps=round(start * PS), duration_ps=round(dur * PS))
        plane.event_metadata.add(key=999).value.name = b"step 3"
        plane.lines.add(name=b"Steps").events.add(metadata_id=999, offset_ps=0, duration_ps=PS)
    space.planes.add(name=b"/device:CUSTOM:Megascale Trace").lines.add(name=b"XLA Ops")
    host = space.planes.add(name=b"/host:CPU")
    line = host.lines.add(name=b"main/1", timestamp_ns=10**9)
    for i, (name, start, dur) in enumerate(fix["host"], 1):
        host.event_metadata.add(key=i).value.name = name.encode()
        line.events.add(metadata_id=i, offset_ps=round((start - 1.0) * PS),
                        duration_ps=round(dur * PS))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


@pytest.fixture(scope="module")
def trace(fixture, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("xplane") / "small.xplane.pb")
    write_xplane(fixture, path)
    got = xplane.read(path)
    got["t0"], got["t1"] = fixture["window"]
    return got


def test_read_gives_operations_with_scopes_and_the_kept_host_spans(fixture, trace):
    assert sorted(trace["devices"]) == ["/device:TPU:0", "/device:TPU:1"]  # no CUSTOM plane
    for dev, events in fixture["devices"].items():
        got = trace["devices"][dev]
        assert len(got) == len(events)  # the Steps line is not an operation
        for (raw, start, dur, scope), (name, s, d, sc) in zip(events, got):
            assert name == trace_reduce.op_name(raw) and sc == scope
            assert (s, d) == (pytest.approx(start), pytest.approx(dur))
    names = [n for n, _, _ in trace["host"]]
    assert "PjitFunction(fused)" not in names and names[0] == "chipbench/window"
    assert [(n, pytest.approx(s), pytest.approx(d)) for n, s, d in trace["host"]] == [
        tuple(e) for e in fixture["host"] if e[0].startswith(("dstpu/", "chipbench/"))]


def test_self_times_take_the_body_out_of_the_while(trace):
    st = xplane.self_times(trace["devices"]["/device:TPU:0"], 0.0, 10.0)
    by_name = {n.partition(" ")[0]: s for n, _, s in st}
    assert by_name["while.1"] == pytest.approx(1.0)  # 4 s less 3 s of body
    assert by_name["dstpu_decode_attn.3"] == pytest.approx(1.0)
    # self times add up to the busy time (the union), here 7 of 10 s
    assert sum(s for _, _, s in st) == pytest.approx(7.0)
    st1 = xplane.self_times(trace["devices"]["/device:TPU:1"], 0.0, 10.0)
    assert {n.partition(" ")[0]: s for n, _, s in st1}["copy.1"] == pytest.approx(6.5)
    # clipped to a window: only what lies inside counts
    assert sum(s for _, _, s in xplane.self_times(
        trace["devices"]["/device:TPU:0"], 2.5, 5.5)) == pytest.approx(2.0)


def test_device_share_by_kernel_name_and_by_scope(trace):
    # mean over the two devices, over the 10 s window
    assert xplane.device_share(trace, xplane.named("dstpu_decode_attn")) == pytest.approx(5.0)
    assert xplane.device_share(trace, xplane.named(
        "dstpu_fused_qkv_ln", "dstpu_fused_out_mlp")) == pytest.approx(7.5)
    assert xplane.device_share(trace, xplane.in_scope("kv_commit")) == pytest.approx(2.5)
    assert xplane.device_share(trace, xplane.in_scope(
        "optimizer", "grad_norm")) == pytest.approx(25.0)
    # the flash call keeps the name it has today, and nothing here reads it
    assert xplane.device_share(trace, xplane.named("dstpu_flash")) is None
    assert xplane.device_share(None, xplane.named("dstpu_decode_attn")) is None


def test_pump_idle_accounts_add_up_to_the_idle_time(trace):
    split = xplane.idle_by_host(trace)
    # device 0 idles in [4, 5) and [8, 10)
    assert split["dispatch"] == pytest.approx(0.8)  # [4.6, 5) and [8, 8.4)
    assert split["sched"] == pytest.approx(1.2)     # [4, 4.6) and [8.4, 9)
    assert split["gateway"] == pytest.approx(1.0)   # [9, 10)
    busy0 = trace_reduce.total(trace_reduce.union(
        [(s, s + d) for _, s, d, _ in trace["devices"]["/device:TPU:0"]]))
    assert split["dispatch"] + split["sched"] + split["gateway"] == pytest.approx(10.0 - busy0)
    # a program without spans (the parent commit) has no account to read
    bare = dict(trace, host=[ev for ev in trace["host"] if ev[0].startswith("chipbench/")])
    assert xplane.idle_by_host(bare) is None and xplane.idle_by_host(None) is None


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(cells.HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


TRACE_METRICS = {"pump_idle_gateway_pct": 10.0, "pump_idle_sched_pct": 12.0,
                 "pump_idle_dispatch_pct": 8.0, "decode_attention_device_pct": 5.0,
                 "fused_block_device_pct": 7.5, "kv_commit_device_pct": 2.5,
                 "optimizer_device_pct": 25.0}


@pytest.mark.parametrize("name", sorted(TRACE_METRICS))
def test_trace_metric_files(trace, name):
    reduce = _metric(name)
    assert reduce({"program_trace": trace}) == pytest.approx(TRACE_METRICS[name])
    # untraced run, rehearsal on the CPU, or a program with none of the marks
    assert reduce({"program_trace": None}) is None
    stripped = {"devices": {d: [(n.replace("dstpu_", "closed_call_"), s, dur, "")
                                for n, s, dur, _ in evs]
                            for d, evs in trace["devices"].items()},
                "host": [ev for ev in trace["host"] if ev[0].startswith("chipbench/")],
                "t0": trace["t0"], "t1": trace["t1"]}
    assert reduce({"program_trace": stripped}) is None


def test_pump_idle_metrics_sum_to_the_idle_share(trace):
    parts = [_metric(f"pump_idle_{k}_pct")({"program_trace": trace})
             for k in ("gateway", "sched", "dispatch")]
    assert sum(parts) == pytest.approx(30.0)  # device 0: 3 s idle of 10


@pytest.mark.parametrize("name,phases", [("setup_trace_lower_s", ("trace", "lower")),
                                         ("setup_backend_s", ("backend", )),
                                         ("setup_cache_read_s", ("cache_read", ))])
def test_setup_metric_files(monkeypatch, name, phases):
    from deepspeed_tpu.utils import compile_cache
    stats = {"trace_s": 3.0, "lower_s": 0.5, "backend_s": 20.0, "cache_read_s": 4.0}
    monkeypatch.setattr(compile_cache, "stats", lambda: dict(stats), raising=False)
    assert _metric(name)({}) == pytest.approx(sum(stats[p + "_s"] for p in phases))
    monkeypatch.delattr(compile_cache, "stats")  # the parent commit has no such counters
    assert _metric(name)({}) is None


def test_histogram_metric_files_read_the_program_histograms():
    for name, hist in (("sched_host_gap_ms", "serving/host_gap_ms"),
                       ("sched_prefill_wait_ms", "serving/prefill_wait_ms")):
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            m = json.load(f)
        assert (m["reducer"], m["args"]) == ("hist_quantile", {"histogram": hist, "quantile": "p50"})
        assert not os.path.exists(os.path.join(cells.HERE, "metrics", name + ".py"))


def test_run_trace_finds_the_run_s_file_and_keeps_it(fixture):
    scratch = os.path.join(ROOT, ".chipbench_run", "_test_xplane")
    shutil.rmtree(scratch, ignore_errors=True)
    try:
        write_xplane(fixture, os.path.join(scratch, "trace", "plugins", "profile", "run",
                                           "host.xplane.pb"))
        obs = {"trace_summary": {"t0": 0.0, "t1": 10.0}}
        got = xplane.run_trace(obs)
        assert got is obs["program_trace"] and (got["t0"], got["t1"]) == (0.0, 10.0)
        assert xplane.run_trace(obs) is got  # read once a process
        assert xplane.run_trace({}) is None  # an untraced run reads no file
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def test_serve_rehearsal_trace_holds_the_pump_spans():
    """A traced CPU rehearsal through the harness still prints its line, and
    its trace holds the program's spans (counted by a fixture metric that
    reads the run's ``.xplane.pb`` through the shared reader)."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         os.path.join(FIX, "workloads", "tiny.serve.spans.json"), "--seed", "3000000019",
         "--seconds", "1", "--trace", "1"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["pump_steps_traced"]["value"] > 0
    assert {m["unit"] for m in line["metrics"].values()} == {"count"}
