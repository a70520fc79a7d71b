"""The latent-attention MoE cell's files: required work from shapes, the
trace helpers on a hand-made trace, the configuration against the catalog's
row and the program's preset, the rehearsal fixtures through ``serve_ref``."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import cells, flops, flops_mla_moe, mla_moe_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mistral-small-4-119b.serve.decode-closed"


@pytest.fixture(scope="module")
def cut():
    return cells.build_model(cells.load_config("mistral-small-4-119b")).cfg


def test_required_work_of_a_decode_step(cut):
    """The issue's arithmetic: 28 of 32 experts touched read 1.41 GB a layer;
    64 pairs cost 3.2 GFLOP; the step is bound by the weight stream."""
    assert flops_mla_moe.expert_weight_bytes(cut, 2) == 3 * 4096 * 2048 * 2  # 50.3 MB
    ops, nbytes = flops_mla_moe.moe_experts_call(cut, experts_touched=28, pairs_here=64, itemsize=2)
    assert ops == 6 * 4096 * 2048 * 64 and abs(nbytes / 1e9 - 1.41) < 0.01
    peaks = cells.load_peaks()["TPU v5 lite"]
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "memory" and abs(least * 6 * 1e3 - 10.3) < 0.2  # ms for the 6 layers
    assert flops_mla_moe.latent_row_bytes(cut, 2) == 640
    ops, nbytes = flops_mla_moe.mla_attention_call(cut, context_rows=64 * 512, itemsize=2)
    assert ops == 2 * 32 * (320 + 256) * 64 * 512 and nbytes == 640 * 64 * 512


def test_trace_helpers_pick_products_by_name():
    evs = [("ragged-dot-none f32[256,2048] custom-call", 0.0, 0.2, ""),
           ("ragged-dot-none f32[256,2048] custom-call", 0.2, 0.2, ""),
           ("ragged-dot-none f32[256,4096] custom-call", 0.4, 0.1, ""),
           ("ragged-dot-metadata custom-call", 0.5, 0.01, ""),
           ("fusion bf16[256,2048]", 0.51, 0.04, "jit(f)/layer_0/moe/moe_experts/mul"),
           ("fusion f32[64,32,1,256]", 0.6, 0.3, "jit(f)/layer_0/attn/mla_attn/dot")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    assert mla_moe_trace.layer_calls(trace) == 1.0
    assert mla_moe_trace.picked_seconds(trace, mla_moe_trace.expert_products) == pytest.approx(0.55)
    assert mla_moe_trace.layer_calls(None) == 0


def test_configuration_keeps_every_published_number():
    with open(os.path.join(ROOT, "chipbench/configs/mistral-small-4-119b.json")) as f:
        cfg = json.load(f)
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mistral-Small-4-119B-2603")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"} == set(cfg["reduced"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "mistral-small-4-119b")
    assert set(entry["reduced"]) == changed and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.mla-moe", True),
                                              ("tiny.serve.mla-moe.wrong", False)])
def test_serve_ref_rehearsal(fixture, correct):
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         os.path.join(HERE, "fixtures", "workloads", fixture + ".json"), "--seed", "3000000019",
         "--seconds", "1", "--trace", "1"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is correct and line["failed"] == 0 and line["attempted"] > 0
    assert {m["unit"] for m in line["metrics"].values()} == {"count"}
