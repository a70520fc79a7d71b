"""The hybrid (linear-attention + full-attention) cell's files: required work
from shapes, the readers on a hand-made trace and on a program without the
layer, the configuration against the catalog's row and the program's preset,
the rehearsal fixtures through ``serve_hybrid``."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import cells, flops, flops_gdn

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "olmo-hybrid-7b.serve.decode-closed"
NEW_METRICS = ("gdn_mixer_device_pct", "gdn_state_device_pct", "gdn_state_roofline",
               "full_attention_device_pct")


@pytest.fixture(scope="module")
def cut():
    return cells.build_model(cells.load_config("olmo-hybrid-7b")).cfg


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def test_required_work_of_a_state_update(cut):
    """The issue's arithmetic: a slot's state is 1,105,920 B a layer; 64 live
    slots read and written in 12 layers are 1.70 GB a step, 2.07 ms at the
    chip's 819 GB/s, bound by memory."""
    assert flops_gdn.state_bytes(cut, 2) == 30 * 96 * 192 * 2 == 1_105_920
    ops, nbytes = flops_gdn.gdn_state_call(cut, live_slots=64, itemsize=2)
    assert nbytes == 2 * 64 * 1_105_920 and ops == 7 * 30 * 96 * 192 * 64
    least, bound = flops.roofline_seconds(ops, nbytes, cells.load_peaks()["TPU v5 lite"])
    assert bound == "memory" and abs(12 * nbytes / 1e9 - 1.70) < 0.01
    assert abs(12 * least * 1e3 - 2.07) < 0.01


def test_readers_on_a_hand_made_trace(cut):
    evs = [("fusion.1 f32[64,30,96,192]", 0.00, 0.30, "jit(fused)/layer_0/gdn/gdn_state/mul"),
           ("fusion.2 bf16[64,11520]", 0.30, 0.10, "jit(fused)/layer_0/gdn/gdn_proj/dot_general"),
           ("fusion.3 bf16[64,3840]", 0.40, 0.05, "jit(fused)/layer_0/gdn/gdn_out/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.45, 0.08, "jit(fused)/layer_3/attn/dstpu_decode_attn"),
           ("dstpu_kv_commit.1 custom-call", 0.53, 0.02, "jit(fused)/layer_3/attn/kv_commit/dstpu_kv_commit"),
           ("fusion.9 bf16[64,11008]", 0.55, 0.40, "jit(fused)/layer_0/mlp/up_proj/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": cut, "itemsize": 2, "peaks": peaks,
           "num_slots": 64, "series": {"slot_occupancy_pct": [100.0, 100.0]},
           "values": {"column_forwards_traced": 100}}
    assert _reader("gdn_mixer_device_pct")(obs) == pytest.approx(45.0)
    assert _reader("gdn_state_device_pct")(obs) == pytest.approx(30.0)
    assert _reader("full_attention_device_pct")(obs) == pytest.approx(10.0)
    # 100 forwards x 12 layers x 172.8 us over 0.3 s
    least = 2 * 64 * 1_105_920 / peaks["hbm_bytes_per_s"]
    assert _reader("gdn_state_roofline")(obs) == pytest.approx(100 * 100 * 12 * least / 0.3)


def test_readers_find_nothing_in_a_program_without_the_layer():
    """The parent's traces have no such scope, its model no ``layer_types``
    and its jobs no ``column_forwards_traced``: every new reader returns None
    and raises nothing (the line then leaves the metric out)."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": cells.load_peaks()["TPU v5 lite"]},
                {"program_trace": None}, {"program_trace": trace}):
        for name in NEW_METRICS:
            assert _reader(name)(dict(obs)) is None


def test_configuration_keeps_every_published_number(cut):
    with open(os.path.join(ROOT, "chipbench/configs/olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "max_position_embeddings"} == set(cfg["reduced"])
    assert cfg["overrides"]["layer_types"] == cfg["published"]["layer_types"][:16]
    assert tuple(cfg["overrides"]["layer_types"]) == cut.layer_types and cut.num_layers == 16
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed"):
        assert cfg[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert set(entry["reduced"]) == changed and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.hybrid", True),
                                              ("tiny.serve.hybrid.wrong", False)])
def test_serve_hybrid_rehearsal(fixture, correct):
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         os.path.join(HERE, "fixtures", "workloads", fixture + ".json"), "--seed", "3000000019",
         "--seconds", "2", "--trace", "1"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is correct and line["failed"] == 0 and line["attempted"] > 0
    assert {m["unit"] for m in line["metrics"].values()} == {"count"}
    note = json.loads(out.stdout.splitlines()[-2])["note"]
    checks = note["checks"]
    assert checks.pop("logits_match_reference") is correct and all(checks.values()), checks
