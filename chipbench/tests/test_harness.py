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

from chipbench import cells, reducers

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
