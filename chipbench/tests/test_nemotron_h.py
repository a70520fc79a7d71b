"""The ``nemotron_h`` cell's files (NVIDIA-Nemotron-3-Nano-30B-A3B): the
reference's own checks, required work from shapes, the four readers on a
hand-made trace and on a program without the layers, the configuration
against the catalog's row and the program's preset, the rehearsal fixtures
through ``serve_nemotron_h``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, flops, flops_nemotron_h
from chipbench.references import nemotron_h as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "nemotron-3-nano-30b-a3b"
CELL = CONFIG + ".serve.reason-closed"
NEW_METRICS = ("ssd_mixer_device_pct", "ssd_state_device_pct", "ssd_state_roofline",
               "relu2_experts_roofline")
HP = {"eps": 1e-5, "top_k": 2, "routed_scale": 2.5, "ssm_heads": 4, "ssm_head_dim": 8,
      "ssm_state": 16, "ssm_groups": 2, "first": 0}


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config(CONFIG)).cfg


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def _mamba2_lp(key, H=16, nh=4, hd=8, N=16, G=2, W=4):
    ks = jax.random.split(key, 6)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    di, cc = nh * hd, nh * hd + 2 * G * N
    return dict(w_in=n(ks[0], H, di + cc + nh), conv=n(ks[1], cc, W), conv_b=n(ks[2], cc),
                dt_bias=jnp.zeros(nh), a_log=jnp.log(jnp.arange(1.0, nh + 1)), d=jnp.ones(nh),
                norm_w=jnp.ones(di), w_out=n(ks[3], di, H))


def test_reference_recurrence_is_causal_and_carries_state():
    """A later token changes no earlier output; with Delta's bias far below
    zero the state stands still and the mixer is the gated norm of D x."""
    lp = _mamba2_lp(jax.random.key(0))
    u = jax.random.normal(jax.random.key(1), (1, 10, 16))
    out = ref.mamba2(u, lp, HP)
    out2 = ref.mamba2(u.at[:, 7].add(1.0), lp, HP)
    assert jnp.allclose(out[:, :7], out2[:, :7], atol=1e-6)
    assert not jnp.allclose(out[:, 7:], out2[:, 7:])
    still = ref.mamba2(u, dict(lp, dt_bias=jnp.full(4, -40.0)), HP)
    assert not jnp.allclose(still, out, atol=1e-3)
    zxd = u @ lp["w_in"]
    z, xbc = zxd[..., :32], zxd[..., 32:32 + 96]
    padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, j:j + 10] * lp["conv"][:, j] for j in range(4))
                    + lp["conv_b"])[..., :32]
    g = (x * jax.nn.silu(z)).reshape(1, 10, 2, 16)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + 1e-5)
    assert jnp.allclose(still, g.reshape(1, 10, 32) @ lp["w_out"], atol=1e-5)


def test_reference_follows_a_near_tie_and_refuses_a_far_choice():
    """Scores 0.60, 0.595, 0.30, 0.20 with top-1: the program's choice of
    expert 1 (0.005 under the reference's own, a near tie in a row whose
    scores spread by 0.18) is followed with the reference's own weight; its
    choice of expert 3 is refused."""
    s = jnp.asarray([[[0.60, 0.595, 0.30, 0.20]]])
    lp = {"gate": jnp.eye(4), "bias": jnp.zeros(4)}
    u = jnp.log(s / (1 - s))
    hp = dict(HP, top_k=1)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[1]]]))
    assert bool(info["followed"][0, 0]) and not bool(info["refused"][0, 0])
    assert jnp.allclose(w[0, 0], jnp.asarray([0.0, 2.5, 0.0, 0.0]), atol=1e-6)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[3]]]))
    assert bool(info["refused"][0, 0]) and jnp.allclose(w[0, 0, 0], 2.5, atol=1e-6)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[0]]]))
    assert not bool(info["followed"][0, 0]) and float(info["reach"][0, 0]) == 0.0
    assert ref.compare(jnp.ones((2, 8)), jnp.ones((2, 8)), jnp.ones(4, bool), tol=1e-3)[
        "ok"] is False  # every pair followed: over MAX_FOLLOWED_SHARE
    # one scale for the block: 127 levels of 1.27, so 0.004 rounds away
    assert jnp.allclose(ref.int8_state(jnp.asarray([[[1.0, 0.004], [0.5, -1.27]]])),
                        jnp.asarray([[[1.0, 0.0], [0.5, -1.27]]]), atol=1e-6)


def test_required_work(served):
    """The issue's arithmetic: a slot's Mamba-2 state and window are 542,720
    values a layer (1,085,440 B); 192 live slots read and written in 7 layers
    are 2.92 GB a step; a routed expert's two matrices are 19.96 MB, the 64
    held in 7 layers 8.94 GB."""
    assert flops_nemotron_h.ssd_state_values(served) == 64 * 64 * 128
    assert flops_nemotron_h.ssd_slot_values(served) == 542_720
    ops, nbytes = flops_nemotron_h.ssd_state_call(served, 7 * 192, 0, 2)
    assert nbytes == 2 * 7 * 192 * 1_085_440 and abs(nbytes / 1e9 - 2.92) < 0.005
    assert ops == 5 * 524_288 * 7 * 192
    peaks = cells.load_peaks()["TPU v5 lite"]
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"
    # a chunk of 512 positions: the state carried four times, the same operations a position
    ops_c, nbytes_c = flops_nemotron_h.ssd_state_call(served, 0, 512, 2)
    assert nbytes_c == 2 * 4 * 1_085_440 and ops_c == 5 * 524_288 * 512
    assert flops_nemotron_h.relu2_expert_weight_bytes(served, 2) == 2 * 2688 * 1856 * 2
    ops, nbytes = flops_nemotron_h.relu2_experts_call(served, 7 * 64, 7 * 576, 2)
    assert ops == 4 * 2688 * 1856 * 7 * 576
    assert abs(7 * 64 * flops_nemotron_h.relu2_expert_weight_bytes(served, 2) / 1e9 - 8.94) < 0.005
    assert nbytes == 7 * 64 * 19_955_712 + 7 * 576 * 2 * 2688 * 2
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


def test_readers_on_a_hand_made_trace(served):
    evs = [("fusion.1 f32[192,64,64,128]", 0.00, 0.10, "jit(fused)/layer_0/mamba2/ssd_state/mul"),
           ("fusion.2 bf16[192,10304]", 0.10, 0.05, "jit(fused)/layer_0/mamba2/ssd_proj/dot_general"),
           ("fusion.3 bf16[192,2688]", 0.15, 0.05, "jit(fused)/layer_0/mamba2/ssd_out/dot_general"),
           ("ragged-dot-none.1 custom-call", 0.20, 0.30, ""),
           ("fusion.5 bf16[1152,1856]", 0.50, 0.02, "jit(fused)/layer_1/moe/moe_experts/mul"),
           ("fusion.6 f32[192,128]", 0.52, 0.03, "jit(fused)/layer_1/moe/moe_router/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.55, 0.05,
            "jit(fused)/layer_5/attn/dstpu_decode_attn"),
           ("fusion.9 bf16[192,65536]", 0.60, 0.10, "jit(fused)/lm_head/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
           "values": {"ssd_state_updates_traced": 7 * 192 * 4, "ssd_chunk_tokens_traced": 7 * 256,
                      "moe_experts_touched_traced": 7 * 64 * 4, "moe_pairs_here_traced": 7 * 576 * 4}}
    assert _reader("ssd_mixer_device_pct")(obs) == pytest.approx(20.0)
    assert _reader("ssd_state_device_pct")(obs) == pytest.approx(10.0)
    # (7 x 192 x 4 updates + 7 x 256 / 128 carries) x 2 x 1,085,440 B / 819 GB/s over 0.1 s
    least = (7 * 192 * 4 + 14) * 2 * 1_085_440 / peaks["hbm_bytes_per_s"]
    assert _reader("ssd_state_roofline")(obs) == pytest.approx(100 * least / 0.1)
    # 7 x 64 x 4 experts' two matrices and the pairs' rows over 0.32 s
    nbytes = 7 * 64 * 4 * 19_955_712 + 7 * 576 * 4 * 2 * 2688 * 2
    assert _reader("relu2_experts_roofline")(obs) == pytest.approx(
        100 * nbytes / peaks["hbm_bytes_per_s"] / 0.32)
    assert _reader("ssd_state_roofline")(obs) < 100 and _reader("relu2_experts_roofline")(obs) < 100


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's traces have no such scope, its model no such sizes and
    its jobs no such values: every new reader returns None and raises
    nothing (the line then leaves the metric out). The experts' roofline of
    two matrices reads nothing of a model whose experts have three."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("ragged-dot-none.1 custom-call", 0.5, 0.3, "")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    swiglu = types.SimpleNamespace(activation="swiglu", hidden_size=4096, expert_ffn_size=2048)
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": peaks},
                {"program_trace": trace, "model_cfg": swiglu, "peaks": peaks, "itemsize": 2,
                 "values": {"moe_experts_touched_traced": 100, "moe_pairs_here_traced": 100}},
                {"program_trace": None}, {"program_trace": trace}):
        for name in NEW_METRICS:
            assert _reader(name)(dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, f"chipbench/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed - {"hybrid_override_pattern"} == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"}
    pattern = cfg["published"]["hybrid_override_pattern"]
    assert cfg["hybrid_override_pattern"] == pattern[:16] == "MEMEM*EMEMEM*EME"
    from deepspeed_tpu.models import nemotron_h_layers
    assert served.layer_types == nemotron_h_layers(pattern[:16])
    table = cfg["sizes"]["layers"]
    for kind in ("mamba2", "moe", "attention"):
        assert [i for i, t in enumerate(served.layer_types) if t == kind] == table[kind]
    # every published width, unchanged, is what the program builds
    pub = cfg["published"]
    assert (served.hidden_size, served.ssm_num_heads, served.ssm_head_dim, served.ssm_state_size,
            served.ssm_groups, served.ssm_conv_kernel, served.ssm_chunk_size) == (
        pub["hidden_size"], pub["mamba_num_heads"], pub["mamba_head_dim"], pub["ssm_state_size"],
        pub["n_groups"], pub["conv_kernel"], pub["chunk_size"])
    assert (served.num_heads, served.kv_heads, served.head_size) == (
        pub["num_attention_heads"], pub["num_key_value_heads"], pub["head_dim"])
    assert (served.expert_ffn_size, served.shared_ffn_size, served.num_experts, served.moe_top_k,
            served.moe_routed_scale, served.layernorm_epsilon) == (
        pub["moe_intermediate_size"], pub["moe_shared_expert_intermediate_size"],
        pub["n_routed_experts"], pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["layer_norm_epsilon"])
    assert (served.experts_held, served.moe_first_expert, served.vocab_size, served.num_layers,
            served.max_seq_len) == (64, 0, 65536, 16, 4096)
    assert cfg["sizes"]["parameters_here"] == served.num_params()
    assert "2 v5e chips that share each layer" in cfg["deployment"]
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed"):
        assert cfg[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(cfg["reduced"]) and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] and entry["file"] == f"chipbench/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    serve_rate = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve_rate["workloads"]
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"]
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported
    # the counts of three matrices an expert and of Mamba-1's sizes are not this model's
    assert not {"moe_experts_roofline", "ssm_state_roofline"} & reported
    sv, tr = workload["serve"], workload["serve"]["traffic"]
    assert (tr["clients"], sv["num_slots"], sv["max_len"], sv["steps_per_sync"],
            sv["prefill_chunk"], tr["pool"]) == (192, 192, 4096, 4, 512, 64)
    assert (tr["prompt_len"], tr["output_len"], tr["max_total"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64, "max": 1024},
        {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}, 4088)
    # one prompt inside a chunk, one over three chunks with a partial last
    short, long_ = sv["collect_prompt_lens"]
    assert short < sv["prefill_chunk"] and 2 * sv["prefill_chunk"] < long_ < 3 * sv["prefill_chunk"]


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.nemotron-h", True),
                                              ("tiny.serve.nemotron-h.wrong", False)])
def test_serve_nemotron_h_rehearsal(fixture, correct):
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
    assert checks.pop("logits_match_reference") is correct
    # the reference without its selection bias agrees with the program without it
    assert checks.pop("no_selection_bias_program_fails") is correct
    assert all(checks.values()), checks
    assert note["info"]["state_bytes_per_slot"] == 9600
