"""The SambaY cell's files (Phi-4-mini-flash-reasoning): the reference's own
checks, required work from shapes, the five readers on a hand-made trace and
on a program without the layers, the configuration against the catalog's row
and the program's preset, the rehearsal fixtures through ``serve_sambay``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, flops, flops_sambay
from chipbench.references import phi4_flash as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "phi-4-mini-flash.serve.reason-closed"
NEW_METRICS = ("ssm_mixer_device_pct", "ssm_state_roofline", "window_attention_device_pct",
               "shared_kv_attention_device_pct", "attention_rows_roofline")


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config("phi-4-mini-flash-reasoning")).cfg


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def _lp(key, H=32, d=8, heads=4, kv=2):
    ks = jax.random.split(key, 12)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    return dict(wq=n(ks[0], H, heads, d), bq=n(ks[1], heads, d), wk=n(ks[2], H, kv, d),
                bk=n(ks[3], kv, d), wv=n(ks[4], H, kv, d), bv=n(ks[5], kv, d),
                wo=n(ks[6], heads // 2, 2 * d, H), bo=n(ks[7], H), lq1=n(ks[8], d),
                lk1=n(ks[9], d), lq2=n(ks[10], d), lk2=n(ks[11], d),
                sub_norm=jnp.ones(2 * d), lam_init=jnp.float32(ref.lambda_init(3)))


def test_reference_window_is_a_mask_over_the_full_form():
    """A window as long as the sequence is no window; a window of 1 sees the
    query's own key alone, so both maps are 1 there and a position's output
    depends on its own input only; lambda_init follows the formula."""
    lp, hp = _lp(jax.random.key(0)), {"eps": 1e-5, "head_dim": 8}
    u = jax.random.normal(jax.random.key(1), (2, 12, 32))
    full, k, v = ref.diff_attention(u, lp, hp, 0)
    wide, _, _ = ref.diff_attention(u, lp, hp, 12)
    assert jnp.allclose(full, wide, atol=1e-6)
    own, _, _ = ref.diff_attention(u, lp, hp, 1)
    alone, _, _ = ref.diff_attention(u[:, 5:6], lp, hp, 0)
    assert jnp.allclose(own[:, 5:6], alone, atol=1e-5)
    cross, _, _ = ref.diff_attention(u, lp, hp, 0, k, v)
    assert jnp.allclose(cross, full, atol=1e-6)
    assert ref.lambda_init(0) == pytest.approx(0.2) and ref.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * 2.718281828 ** -5.1)


def test_reference_recurrence_is_causal_and_carries_state():
    """A later token changes no earlier output; with Delta's bias far below
    zero the state stands still and y is D x; the gated memory unit is
    elementwise in ``m``."""
    ks = jax.random.split(jax.random.key(2), 8)
    H, di, ds, r, W = 16, 32, 4, 2, 4
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    lp = dict(w_in=n(ks[0], H, 2 * di), conv=n(ks[1], di, W), conv_b=n(ks[2], di),
              w_x=n(ks[3], di, r + 2 * ds), w_dt=n(ks[4], r, di), dt_bias=jnp.zeros(di),
              a_log=jnp.log(jnp.broadcast_to(jnp.arange(1.0, ds + 1), (di, ds))),
              d=jnp.ones(di), w_out=n(ks[5], di, H))
    u = jax.random.normal(ks[6], (1, 10, H))
    out, m = ref.mamba(u, lp)
    changed = u.at[:, 7].add(1.0)
    out2, _ = ref.mamba(changed, lp)
    assert jnp.allclose(out[:, :7], out2[:, :7], atol=1e-6) and not jnp.allclose(out[:, 7:], out2[:, 7:])
    still, m_still = ref.mamba(u, dict(lp, dt_bias=jnp.full(di, -40.0)))
    xt = (u @ lp["w_in"])[..., :di]
    padded = jnp.pad(xt, ((0, 0), (W - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, j:j + 10] * lp["conv"][:, j] for j in range(W)) + lp["conv_b"])
    assert jnp.allclose(m_still, x, atol=1e-5) and not jnp.allclose(m, x, atol=1e-3)
    g = dict(w_1=n(ks[7], H, di), w_2=jnp.eye(di))
    assert jnp.allclose(ref.gmu(u, g, 2.0 * m), 2.0 * ref.gmu(u, g, m), atol=1e-5)


def test_required_work(served):
    """The issue's arithmetic: a slot's SSM state and window are 97,280
    values a layer; 64 live slots read and written in 9 layers are 0.22 GB a
    step; a position's K and V are 5,120 B a layer; 64 slots at 1,650
    positions read by 8 layers and 512 ring rows by 8 are 5.67 GB."""
    assert flops_sambay.ssm_slot_values(served) == 97_280
    ops, nbytes = flops_sambay.ssm_state_call(served, live_slots=64, itemsize=2)
    assert nbytes == 2 * 64 * 97_280 * 2 and ops == 8 * 16 * 5120 * 64
    peaks = cells.load_peaks()["TPU v5 lite"]
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "memory" and abs(9 * nbytes / 1e9 - 0.224) < 0.001
    assert flops_sambay.attention_row_bytes(served, 2) == 5_120
    rows = 64 * 8 * (1650 + 512)
    ops, nbytes = flops_sambay.attention_rows(served, rows, 2)
    assert nbytes == rows * 5_120 and abs(nbytes / 1e9 - 5.67) < 0.01
    assert ops == rows * 40 * 6 * 64 and flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


def test_readers_on_a_hand_made_trace(served):
    evs = [("fusion.1 f32[64,16,5120]", 0.00, 0.10, "jit(fused)/layer_0/mamba/ssm_state/mul"),
           ("fusion.2 bf16[64,10240]", 0.10, 0.08, "jit(fused)/layer_0/mamba/ssm_proj/dot_general"),
           ("fusion.3 bf16[64,2560]", 0.18, 0.04, "jit(fused)/layer_0/mamba/ssm_out/dot_general"),
           ("fusion.4 bf16[64,5120]", 0.22, 0.03, "jit(fused)/layer_18/gmu/gmu/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.25, 0.06,
            "jit(fused)/layer_1/attn/swa_attn/dstpu_decode_attn"),
           ("dstpu_kv_commit.1 custom-call", 0.31, 0.01,
            "jit(fused)/layer_1/attn/swa_attn/kv_commit/dstpu_kv_commit"),
           ("dstpu_decode_attn.5 custom-call", 0.32, 0.20,
            "jit(fused)/layer_19/attn/shared_attn/dstpu_decode_attn"),
           ("fusion.7 bf16[64,2560]", 0.52, 0.05, "jit(fused)/layer_19/attn/attn_proj/dot_general"),
           ("fusion.9 bf16[64,10240]", 0.57, 0.40, "jit(fused)/layer_0/mlp/up_proj/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
           "num_slots": 64, "series": {"slot_occupancy_pct": [100.0, 100.0]},
           "values": {"column_forwards_traced": 20, "attn_rows_window_traced": 5_000_000,
                      "attn_rows_shared_traced": 15_000_000}}
    assert _reader("ssm_mixer_device_pct")(obs) == pytest.approx(25.0)
    assert _reader("window_attention_device_pct")(obs) == pytest.approx(7.0)
    assert _reader("shared_kv_attention_device_pct")(obs) == pytest.approx(20.0)
    # 20 forwards x 9 layers x (2 x 64 x 194,560 B / 819 GB/s) over 0.1 s
    least = 2 * 64 * 194_560 / peaks["hbm_bytes_per_s"]
    assert _reader("ssm_state_roofline")(obs) == pytest.approx(100 * 20 * 9 * least / 0.1)
    # 20 M positions x 5,120 B over 0.27 s
    assert _reader("attention_rows_roofline")(obs) == pytest.approx(
        100 * 20e6 * 5_120 / peaks["hbm_bytes_per_s"] / 0.27)
    assert _reader("attention_rows_roofline")(obs) < 100 and _reader("ssm_state_roofline")(obs) < 100


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's traces have no such scope, its model no such kinds and
    its jobs no such values: every new reader returns None and raises
    nothing (the line then leaves the metric out)."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": cells.load_peaks()["TPU v5 lite"]},
                {"program_trace": None}, {"program_trace": trace}):
        for name in NEW_METRICS:
            assert _reader(name)(dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, "chipbench/configs/phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == {"max_position_embeddings"} == set(cfg["reduced"])
    assert served.num_layers == cfg["num_hidden_layers"] == 32 and served.max_seq_len == 4096
    table = cfg["sizes"]["layers"]
    for kind, key in (("mamba", "mamba"), ("gmu", "gmu"), ("cross_attention", "cross_attention")):
        assert [i for i, t in enumerate(served.layer_types) if t == kind] == table[key]
    assert [i for i, w in enumerate(served.layer_windows) if w == 512] == table[
        "diff_attention_window_512"]
    assert [i for i, (t, w) in enumerate(zip(served.layer_types, served.layer_windows))
            if t == "diff_attention" and not w] == table["diff_attention_full"] == [17]
    assert cfg["sizes"]["parameters"] == served.num_params()
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed"):
        assert cfg[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    assert set(entry["reduced"]) == changed and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert len(bench["workloads"]) == 6 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"]
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported
    tr = workload["serve"]["traffic"]
    assert (tr["clients"], workload["serve"]["num_slots"], workload["serve"]["max_len"]) == (
        64, 64, 4096)
    assert (tr["prompt_len"], tr["output_len"], tr["max_total"]) == (
        {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 3072},
        {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}, 4088)


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.sambay", True),
                                              ("tiny.serve.sambay.wrong", False)])
def test_serve_sambay_rehearsal(fixture, correct):
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
    assert note["info"]["window_bytes_per_slot"] == 32768
