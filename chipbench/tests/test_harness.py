"""The harness is driven by data: a configuration, a cell and a per-layer
metric added as NEW FILES are found by name, no file edited. Off the chip a
real cell does not run; a rehearsal fixture does, reports counts only, and a
forced wrong answer makes it report ``correct: false``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells, harness, reducers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIX = os.path.join(HERE, "fixtures")


def run_cell(workload, *extra, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload, "--seed", "3000000019",
         "--seconds", "1", *extra], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)


@pytest.mark.parametrize("cell", sorted(f[:-5] for f in os.listdir(
    os.path.join(cells.HERE, "workloads"))))
def test_real_cell_does_not_run_off_the_chip(cell):
    out = run_cell(cell)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith('{"correct"') for line in out.stdout.splitlines())


def test_rehearsal_reports_counts_only_and_names_the_cpu():
    out = run_cell(os.path.join(FIX, "workloads", "tiny.train.json"), "--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["steps_done"]["value"] == line["attempted"]
    assert {m["unit"] for m in line["metrics"].values()} == {"count"}  # no time, rate, utilization
    timed = run_cell(os.path.join(FIX, "workloads", "tiny.train.json"))
    assert json.loads(timed.stdout.splitlines()[-1])["metrics"] == {}


def test_forced_wrong_answer_reports_correct_false():
    out = run_cell(os.path.join(FIX, "workloads", "tiny.train.wrong.json"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1])["correct"] is False


def test_serve_rehearsal_with_its_own_metric_reader():
    out = run_cell(os.path.join(FIX, "workloads", "tiny.serve.json"), "--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    # requests_done has no built-in reducer: metrics/requests_done.py reads it
    assert line["metrics"]["requests_done"]["value"] > 0


def test_new_cell_config_and_metric_are_new_files_only(tmp_path):
    """A later PR's view: copy the fixtures root, ADD three files, edit none."""
    root = tmp_path / "root"
    shutil.copytree(FIX, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs" / "tiny-gpt2.json").read_text())
    (root / "configs" / "tiny-new.json").write_text(json.dumps(dict(cfg, name="tiny-new")))
    wl = json.loads((root / "workloads" / "tiny.train.json").read_text())
    wl.update(config="tiny-new", traffic="tiny.new", per_layer=["steps_done"])
    (root / "workloads" / "tiny-new.train.json").write_text(json.dumps(wl))
    (root / "metrics" / "batch_tokens.json").write_text(json.dumps({
        "layer": "train step", "unit": "count", "better": "higher", "source": "program_counter",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["tiny-new.train"],
        "reducer": "value", "args": {"key": "steps"}}))
    assert all(p.read_bytes() == b for p, b in before.items())

    cell, workload, found_root = cells.load_workload(str(root / "workloads" / "tiny-new.train.json"))
    assert (cell, found_root) == ("tiny-new.train", str(root))
    assert cells.load_config(workload["config"], found_root)["name"] == "tiny-new"
    metrics = cells.per_layer_metrics(cell, workload, found_root)
    # its own metric by its file's list, steps_done by the workload's list,
    # and none of the real cells' metrics
    assert set(metrics) == {"batch_tokens", "steps_done"}
    out = run_cell(str(root / "workloads" / "tiny-new.train.json"), "--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    assert set(json.loads(out.stdout.splitlines()[-1])["metrics"]) == {"batch_tokens", "steps_done"}


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "-m", "chipbench.run"] and bench["paths"] == ["chipbench"]
    e2e = cells.end_to_end_units()
    assert e2e["setup_s"] == "s" and len(e2e) == len(bench["end_to_end"])
    for c in bench["configs"]:
        conf = cells.load_config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert (c["source"], c["reduced"]) == (conf["source"], conf["reduced"])
        cells.build_model(conf)  # the program builds the sizes the file states
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    reported = {}
    for w in bench["workloads"]:
        cell, wl, root = cells.load_workload(w["name"])
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            wl["config"], wl["traffic"], wl["chips"], wl["why"])
        assert len(w["why"]) <= 200
        assert "setup_s" in wl["end_to_end"] and len(wl["end_to_end"]) >= 2
        assert set(wl["end_to_end"]) <= set(e2e)
        assert {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])} \
            == set(wl["end_to_end"])
        for name, m in cells.per_layer_metrics(cell, wl, root).items():
            reported.setdefault(name, set()).add(cell)
            assert m["moves"] in wl["end_to_end"]
            assert m.get("reducer") in reducers.BUILTIN or cells.custom_reducer(m)
    assert set(reported) == set(per_layer)
    all_cells = {w["name"] for w in bench["workloads"]}
    for name, m in per_layer.items():
        assert set(m.get("workloads", all_cells)) == reported[name]
        f = json.load(open(os.path.join(cells.HERE, "metrics", name + ".json")))
        assert all(m[k] == f[k] for k in ("layer", "unit", "better", "source", "moves"))
    assert os.path.isdir(os.path.join(ROOT, "chipbench", "jobs"))


# ---- the measured window of a serving job (harness.measured_window)


class _FakeClock:
    """A clock that ``sleep`` alone moves, and every call of the window's
    collaborators in order."""

    def __init__(self):
        self.now, self.calls = 100.0, []

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s

    def open_trace(self, ctx, seconds):
        fake = self
        fake.calls.append(("start_trace", fake.now))
        fake.now += 0.5  # the profiler takes its time to start

        class Traced:
            dir = "trace"
            until = fake.now + seconds

            def due(self):
                return fake.now >= self.until

            def stop(self):
                fake.calls.append(("stop_trace", fake.now))
                fake.now += 12.0  # and far longer to write the trace

        return Traced()

    def run(self, trace, seconds=30.0, trace_seconds=5.0, counted=True):
        from types import SimpleNamespace
        t0 = self.now + 0.05
        samples = []
        out = harness.measured_window(
            SimpleNamespace(trace=trace), t0, t0 + seconds, trace_seconds,
            sample=lambda: samples.append(self.now),
            snapshot=lambda: self.calls.append(("snapshot", self.now)) or {"at": self.now},
            counted=(lambda: self.calls.append(("counted", self.now)) or len(self.calls))
            if counted else None,
            open_trace=self.open_trace, clock=self.clock, sleep=self.sleep)
        return t0, t0 + seconds, samples, out


def test_traced_window_is_the_last_part_and_snapshots_before_stop_trace():
    fake = _FakeClock()
    t0, t1, samples, (traced, after, counted_at, after_s, host) = fake.run(trace=True)
    names = [c[0] for c in fake.calls]
    assert names == ["start_trace", "counted", "snapshot", "counted", "stop_trace"]
    at = dict((n, t) for n, t in fake.calls)  # the last of each
    # the profiler opens TRACE_START_S before the last 5 s and the traced
    # part closes inside the window: nothing of it lies past t1
    assert at["start_trace"] == pytest.approx(t1 - 5.0 - harness.TRACE_START_S, abs=0.25)
    assert traced.until <= t1 and t1 - traced.until < harness.TRACE_START_S
    assert traced.until <= at["snapshot"] <= at["stop_trace"] <= t1
    assert after == {"at": at["snapshot"]}
    assert counted_at["start"] < counted_at["stop"]
    assert after_s["snapshot"] <= 0 and after_s["snapshot"] == pytest.approx(at["snapshot"] - t1)
    assert after_s["stop_trace"] == pytest.approx(12.0)
    assert len(samples) > 100 and max(samples) <= at["snapshot"]
    assert host["gc_collections"][0] >= 0 and host["cpu_count"] == os.cpu_count()


def test_untraced_window_snapshots_at_its_end_and_touches_no_profiler():
    fake = _FakeClock()
    t0, t1, samples, (traced, after, counted_at, after_s, host) = fake.run(trace=False)
    assert [c[0] for c in fake.calls] == ["snapshot"]  # no profiler, no counter read
    assert traced is None and counted_at is None
    assert t1 <= fake.calls[0][1] < t1 + 0.25 and 0 <= after_s["snapshot"] < 0.25
    assert after_s["stop_trace"] == 0.0
    assert len(samples) == 121 and samples[-1] >= t1
    obs = {}
    harness.finish_trace(None, traced, obs)  # nothing to load
    assert obs == {}


def test_traced_window_shorter_than_its_traced_part_stops_at_the_windows_end():
    """A rehearsal's two seconds: the profiler opens with the window, the
    snapshot is taken at its end though the traced part is not yet due."""
    fake = _FakeClock()
    t0, t1, _, (traced, _, counted_at, after_s, _) = fake.run(trace=True, seconds=2.0,
                                                              counted=False)
    assert [c[0] for c in fake.calls] == ["start_trace", "snapshot", "stop_trace"]
    assert t0 <= fake.calls[0][1] <= t0 + 0.25 and counted_at is None
    assert traced.until > t1 and 0 <= after_s["snapshot"] < 0.25


SERVE_JOBS = ("serve", "serve_ref", "serve_hybrid", "serve_sambay")


@pytest.mark.parametrize("job", SERVE_JOBS)
def test_serving_job_leaves_the_profiler_to_the_harness(job):
    with open(os.path.join(cells.HERE, "jobs", job + ".py")) as f:
        src = f.read()
    assert "measured_window(" in src
    for own in ("traced.stop(", "stop_trace(", "start_trace(", "TracedWindow"):
        assert own not in src, f"jobs/{job}.py handles the profiler itself: {own}"
    assert "import jax.profiler" not in src and "jax.profiler." not in src


def test_host_watch_times_the_collector():
    import gc
    watch = harness.HostWatch()
    watch.start(watch.clock())
    junk = [[i] for i in range(1000)]
    gc.collect()
    host = watch.stop()
    assert watch._on_gc not in gc.callbacks and junk
    assert host["gc_collections"][2] >= 1 and host["gc_pause_ms"][2] > 0
    assert any(gen == 2 for _, _, gen in host["gc_pauses_longest"])
    assert host["cpu_user_s"] >= 0 and host["watched_s"] >= 0


# ---- the data files against BENCHMARK.json


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", sorted(f[:-5] for f in os.listdir(
    os.path.join(cells.HERE, "workloads"))))
def test_every_end_to_end_name_of_a_cell_is_a_bounded_entry_that_lists_it(cell):
    e2e = {m["name"]: m for m in _bench()["end_to_end"]}
    _, wl, _ = cells.load_workload(cell)
    for name in wl["end_to_end"]:
        assert name in e2e, f"{cell} reports {name}, which BENCHMARK.json does not list"
        m = e2e[name]
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert cell in m.get("workloads", [cell]), f"{name} does not list {cell}"


def test_every_per_layer_metric_moves_an_end_to_end_metric_one_of_its_cells_reports():
    bench = _bench()
    e2e = {m["name"] for m in bench["end_to_end"]}
    reports = {w["name"]: set(cells.load_workload(w["name"])[1]["end_to_end"])
               for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which is no entry"
        its_cells = m.get("workloads", list(reports))
        assert all(m["moves"] in reports[c] for c in its_cells), \
            f"{m['name']} moves {m['moves']}, which a cell of its list does not report"
    for name in os.listdir(os.path.join(cells.HERE, "metrics")):
        if name.endswith(".json"):
            with open(os.path.join(cells.HERE, "metrics", name)) as f:
                assert json.load(f)["moves"] in e2e, name


def test_the_job_reports_what_cell_2s_new_metrics_read():
    """``client_tpot_p50_ms`` and ``client_tpot_p90_ms`` in every serving
    cell, the stall numbers where the cell states a ``stall_gap_ms``: each
    data file's key is one the jobs put under ``values``."""
    bench = {m["name"]: m for m in _bench()["per_layer"]}
    serving = sorted(w["name"] for w in _bench()["workloads"] if ".serve." in w["name"])
    assert sorted(bench["client_tpot_p50_ms"]["workloads"]) == serving
    assert sorted(bench["client_tpot_p90_ms"]["workloads"]) == serving
    for name in ("delivery_stall_ms_per_s", "delivery_stalls_per_min"):
        stated = [c for c in serving if "stall_gap_ms" in cells.load_workload(c)[1]["serve"]]
        assert sorted(bench[name]["workloads"]) == stated == ["gpt2-large.serve.chat-closed"]
    for name in ("client_tpot_p50_ms", "client_tpot_p90_ms", "delivery_stall_ms_per_s",
                 "delivery_stalls_per_min"):
        spec = json.load(open(os.path.join(cells.HERE, "metrics", name + ".json")))
        assert spec["reducer"] == "value" and spec["args"]["key"] == name
        for job in SERVE_JOBS if name.startswith("client") else ("serve", ):
            with open(os.path.join(cells.HERE, "jobs", job + ".py")) as f:
                assert f'"{name}": res[' in f.read()
