"""The ``falcon_h1`` cell's files (TII Falcon-H1-34B-Instruct): the
reference's own checks (against ``references/nemotron_h.py``'s Mamba-2 where
the two describe the same mixer), required state work at twice cell 7's
state, the new reader on a hand-made trace and on a program without the
layers, the configuration against the catalog's row and the program's preset,
the rehearsal fixtures through ``serve_falcon_h1``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, flops, flops_nemotron_h
from chipbench.references import falcon_h1 as ref
from chipbench.references import nemotron_h as ref_nemotron

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "falcon-h1-34b-instruct"
CELL = "falcon-h1-34b.serve.reason-closed"
NEW_METRIC = "hybrid_mixer_device_pct"
# the tiny twin's sizes and constants, as the rehearsal fixture publishes them
with open(os.path.join(HERE, "fixtures", "configs", "tiny-falcon-h1.json")) as _f:
    HP = ref.kwargs_for(json.load(_f))


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config(CONFIG)).cfg


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def test_reference_mixer_is_cell_7s_with_the_projection_scaled():
    """``mu`` over ``[z ; x ; B ; C ; dt]`` is a scaling of W_in's column
    blocks: this reference's Mamba-2 on W_in is ``references/nemotron_h.py``'s
    on ``W_in * mu``; a key scaled before rotation is W_k scaled (the rotation
    is linear), and the rotation sees positions: moving a key changes no
    earlier output."""
    ks = jax.random.split(jax.random.key(0), 8)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    H, nh, hd, N, G, W = 16, 4, 8, 16, 2, 4
    di, cc = nh * hd, nh * hd + 2 * G * N
    lp = dict(w_in=n(ks[0], H, di + cc + nh), conv=n(ks[1], cc, W), conv_b=n(ks[2], cc),
              dt_bias=jnp.zeros(nh), a_log=jnp.log(jnp.arange(1.0, nh + 1)), d=jnp.ones(nh),
              norm_w=jnp.ones(di), w_out=n(ks[3], di, H))
    u = jax.random.normal(ks[4], (1, 10, H))
    mu = jnp.concatenate([jnp.full((w, ), m) for w, m in zip(
        (di, di, G * N, G * N, nh), HP["ssm_multipliers"])])
    theirs = ref_nemotron.mamba2(u, dict(lp, w_in=lp["w_in"] * mu), HP)
    assert jnp.allclose(ref.mamba2(u, lp, HP), theirs, atol=1e-6)
    assert not jnp.allclose(ref.mamba2(u, lp, dict(HP, ssm_multipliers=(1.0, ) * 5)), theirs,
                            atol=1e-3)
    ap = dict(wq=n(ks[5], H, 5, 8), wk=n(ks[6], H, 1, 8), wv=n(ks[7], H, 1, 8),
              wo=n(ks[0], 5, 8, H))
    out = ref.attention(u, ap, HP)
    scaled = ref.attention(u, dict(ap, wk=ap["wk"] * HP["key_multiplier"]),
                           dict(HP, key_multiplier=1.0))
    assert jnp.allclose(out, scaled, atol=1e-6)
    moved = ref.attention(u.at[:, 7].add(1.0), ap, HP)
    assert jnp.allclose(out[:, :7], moved[:, :7], atol=1e-6)
    assert not jnp.allclose(out[:, 7:], moved[:, 7:], atol=1e-4)
    # the rotation is there: other frequencies, other scores
    assert not jnp.allclose(out, ref.attention(u, ap, dict(HP, theta=1e2)), atol=1e-4)


def test_required_state_work_at_twice_cell_7s_state(served):
    """A slot's Mamba-2 state and window are 1,063,936 values a layer
    (2,127,872 B in bf16, twice cell 7's 1,085,440); 64 live slots read and
    written in 6 layers are 1.63 GB a step, the memory's."""
    assert flops_nemotron_h.ssd_state_values(served) == 32 * 128 * 256
    assert flops_nemotron_h.ssd_slot_values(served) == 1_063_936
    ops, nbytes = flops_nemotron_h.ssd_state_call(served, 6 * 64, 0, 2)
    assert nbytes == 2 * 6 * 64 * 2_127_872 and abs(nbytes / 1e9 - 1.63) < 0.005
    assert ops == 5 * 1_048_576 * 6 * 64
    assert flops.roofline_seconds(ops, nbytes, cells.load_peaks()["TPU v5 lite"])[1] == "memory"


def test_readers_on_a_hand_made_trace(served):
    """The scope holds both branches: the shares that read its parts cannot
    add up to more than it."""
    layer = "jit(fused)/layer_0/hybrid_mixer/"
    evs = [("fusion.1 bf16[64,32,128,256]", 0.00, 0.10, layer + "mamba2/ssd_state/mul"),
           ("fusion.2 bf16[64,9248]", 0.10, 0.05, layer + "mamba2/ssd_proj/dot_general"),
           ("fusion.3 bf16[64,5120]", 0.15, 0.05, layer + "mamba2/ssd_out/dot_general"),
           ("fusion.4 bf16[64,20,128]", 0.20, 0.04, layer + "attn/attn_proj/q_proj/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.24, 0.05, layer + "attn/dstpu_decode_attn"),
           ("dstpu_kv_commit.2 custom-call", 0.29, 0.01, layer + "attn/kv_commit/dstpu_kv_commit"),
           ("fusion.5 bf16[64,5120]", 0.30, 0.02, layer + "add"),
           ("fusion.6 bf16[64,21504]", 0.32, 0.30, "jit(fused)/layer_0/mlp/gate_proj/dot_general"),
           ("fusion.9 bf16[64,261120]", 0.62, 0.20, "jit(fused)/lm_head/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
           "values": {"ssd_state_updates_traced": 6 * 64 * 4, "ssd_chunk_tokens_traced": 6 * 512}}
    whole = _reader(NEW_METRIC)(obs)
    assert whole == pytest.approx(32.0)
    ssd, attn = _reader("ssd_mixer_device_pct")(obs), _reader("full_attention_device_pct")(obs)
    assert (ssd, attn) == (pytest.approx(20.0), pytest.approx(6.0)) and ssd + attn <= whole
    assert _reader("lm_head_device_pct")(obs) == pytest.approx(20.0)
    # (6 x 64 x 4 updates + 6 x 512 / 128 carries) x 2 x 2,127,872 B / 819 GB/s over 0.1 s
    least = (6 * 64 * 4 + 24) * 2 * 2_127_872 / peaks["hbm_bytes_per_s"]
    roofline = _reader("ssd_state_roofline")(obs)
    assert roofline == pytest.approx(100 * least / 0.1) and 0 < roofline < 100


def test_reader_finds_nothing_in_a_program_without_the_layers():
    """The parent's traces have no such scope: the new reader returns None and
    raises nothing (the line then leaves the metric out)."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.5, 0.3, "jit(fused)/layer_5/attn/dstpu_decode_attn")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {}},
                {"program_trace": None}, {"program_trace": trace}):
        assert _reader(NEW_METRIC)(dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, f"chipbench/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == (6, 4096)
    # every published width and constant, unchanged, is what the program builds
    assert served.layer_types == ("parallel_hybrid", ) * 6 == tuple(
        cfg["expect_lists"]["layer_types"])
    assert (served.hidden_size, served.num_heads, served.kv_heads, served.head_size,
            served.ffn_size, served.vocab_size, served.rope_theta, served.layernorm_epsilon,
            served.tie_embeddings) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["intermediate_size"], pub["vocab_size"], pub["rope_theta"],
        pub["rms_norm_eps"], pub["tie_word_embeddings"])
    assert (served.ssm_num_heads, served.ssm_head_dim, served.ssm_state_size, served.ssm_groups,
            served.ssm_conv_kernel, served.ssm_chunk_size, served.mamba2_inner) == (
        pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"], pub["mamba_n_groups"],
        pub["mamba_d_conv"], pub["mamba_chunk_size"], pub["mamba_d_ssm"])
    assert served.mamba2_conv_channels == 4096 + 2 * 2 * 256 == 5120
    for key in ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        assert getattr(served, key) == pub[key] == cfg["expect"][key], key
    assert list(served.ssm_multipliers) == pub["ssm_multipliers"] == cfg["expect_lists"][
        "ssm_multipliers"]
    assert [served.mlp_gate_multiplier, served.mlp_down_multiplier] == pub["mlp_multipliers"]
    assert not (pub["attention_bias"] or pub["mlp_bias"] or pub["mamba_proj_bias"]
                or pub["projectors_bias"]) and pub["mamba_conv_bias"]
    assert (served.attn_bias, served.mlp_bias) == (False, False)
    assert pub["attn_layer_indices"] is None and pub["mamba_use_mlp"] and pub["mamba_rms_norm"]
    assert not pub["mamba_norm_before_gate"] and pub["rope_scaling"] is None
    # the reference reads the same keys, from the file alone
    hp = ref.kwargs_for(cfg)
    assert hp["ssm_multipliers"] == tuple(pub["ssm_multipliers"]) and hp["theta"] == 1e11
    assert (hp["mlp_gate_multiplier"], hp["mlp_down_multiplier"]) == tuple(pub["mlp_multipliers"])
    sizes = cfg["sizes"]
    assert sizes["parameters_here"] == served.num_params() == 6 * sizes["parameters_per_layer"] + (
        sizes["embedding"] + sizes["head"] + sizes["final_norm"])
    assert sum(sizes["per_layer"].values()) == sizes["parameters_per_layer"] == 430_120_032
    assert "12 v5e chips" in cfg["deployment"] and "26%" in cfg["deployment"]
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed"):
        assert cfg[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(cfg["reduced"]) and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] and entry["file"] == f"chipbench/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and "26%" in cell["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    serve_rate = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve_rate["workloads"]
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"] and workload["job"] == "serve_falcon_h1"
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed
    assert {NEW_METRIC, "ssd_mixer_device_pct", "ssd_state_device_pct", "ssd_state_roofline",
            "full_attention_device_pct", "attn_walk_live_pct", "lm_head_device_pct",
            "hbm_peak_pct.serve", "step_rows_live_pct"} <= reported
    assert not {m for m in reported if m.startswith(("moe_", "gdn_", "mla_"))}
    new = next(m for m in bench["per_layer"] if m["name"] == NEW_METRIC)
    assert new["workloads"] == [CELL] and new["moves"] == "serve_tokens_per_s"
    sv, tr = workload["serve"], workload["serve"]["traffic"]
    assert (tr["clients"], sv["num_slots"], sv["max_len"], sv["steps_per_sync"],
            sv["prefill_chunk"], tr["pool"], tr["think_time_s"], tr["stagger_first"]) == (
        64, 64, 4096, 4, 512, 64, 0, True)
    assert (tr["prompt_len"], tr["output_len"], tr["max_total"], tr["sampling"],
            tr["sharing"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64, "max": 1024},
        {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}, 4088,
        "greedy", "none")
    # one prompt inside a chunk, one over three chunks with a partial last
    short, long_ = sv["collect_prompt_lens"]
    assert short < sv["prefill_chunk"] and 2 * sv["prefill_chunk"] < long_ < 3 * sv["prefill_chunk"]
    assert cfg["reference"] == {"module": "falcon_h1", "kv_bytes_per_token": 12288,
                                "state_bytes_per_slot": 12767232}


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.falcon-h1", True),
                                              ("tiny.serve.falcon-h1.wrong", False)])
def test_serve_falcon_h1_rehearsal(fixture, correct):
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
    # a reference that leaves attention_out_multiplier out differs from the program alone
    assert checks.pop("logits_match_reference") is correct
    assert all(checks.values()), checks
    assert {"lower_precision_fails", "no_attention_program_fails", "no_mu_program_fails",
            "zero_state_program_fails", "kv_commit_in_place"} <= set(checks)
    assert note["info"]["state_bytes_per_slot"] == 12800
    assert note["info"]["kv_bytes_per_token"] == 512
