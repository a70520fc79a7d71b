"""The ``exaone_moe`` cell's files (K-EXAONE-236B-A23B): the reference's own
checks, required work from shapes, the new readers on a hand-made trace and on
a program without the layers, the configuration against the catalog's row and
the program's preset, the rehearsal fixtures through ``serve_exaone_moe``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, flops, flops_exaone_moe, reducers
from chipbench.references import exaone_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "k-exaone-236b-a23b"
CELL = CONFIG + ".serve.reason-closed"
NEW_READERS = ("mtp_draft_device_pct", "gated_experts_roofline")
NEW_METRICS = NEW_READERS + ("mtp_accept_pct", "spec_rows_void_pct")
HP = {"eps": 1e-5, "top_k": 2, "routed_scale": 2.5, "theta": 1e6, "first": 0}


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config(CONFIG)).cfg


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def _attn_lp(key, H=16, nh=4, nkv=2, d=8):
    ks = jax.random.split(key, 4)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    return dict(wq=n(ks[0], H, nh, d), wk=n(ks[1], H, nkv, d), wv=n(ks[2], H, nkv, d),
                wo=n(ks[3], nh, d, H), qn=jnp.ones(d), kn=jnp.ones(d))


def test_reference_window_is_causal_and_forgets_and_only_it_rotates():
    """A later token changes no earlier output; a token more than the window
    back changes nothing under a window and something without one; shifting
    every position by one changes a windowed layer's output only through
    which keys are seen, never a full layer's (it takes no positional term)."""
    lp = _attn_lp(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 12, 16))
    for window in (0, 4):
        out = ref.attention(x, lp, HP, window)
        late = ref.attention(x.at[:, 9].add(1.0), lp, HP, window)
        assert jnp.allclose(out[:, :9], late[:, :9], atol=1e-6)
        assert not jnp.allclose(out[:, 9:], late[:, 9:])
        early = ref.attention(x.at[:, 2].add(1.0), lp, HP, window)
        # position 11 sees keys 8-11 under a window of 4, and key 2 without one
        assert bool(jnp.allclose(out[:, 11], early[:, 11], atol=1e-6)) is bool(window)
    # a permutation of the keys a full layer's last query sees changes nothing
    perm = jnp.concatenate([jnp.arange(11)[::-1], jnp.asarray([11])])
    assert jnp.allclose(ref.attention(x, lp, HP, 0)[:, 11],
                        ref.attention(x[:, perm], lp, HP, 0)[:, 11], atol=1e-5)
    assert not jnp.allclose(ref.attention(x, lp, HP, 12)[:, 11],
                            ref.attention(x[:, perm], lp, HP, 12)[:, 11], atol=1e-3)
    # the per-head norm: scaling one head's query projection changes nothing
    scaled = dict(lp, wq=lp["wq"].at[:, 1].multiply(3.0))
    assert jnp.allclose(ref.attention(x, lp, HP, 0), ref.attention(x, scaled, HP, 0), atol=1e-4)


def test_reference_follows_a_near_tie_and_blocks_the_dense_layer():
    """Scores 0.60, 0.595, 0.30, 0.20 with top-1: the program's choice of
    expert 1 is followed with the reference's own weight, its choice of
    expert 3 refused; and the dense layer's FFN in blocks of its width is the
    FFN."""
    s = jnp.asarray([[[0.60, 0.595, 0.30, 0.20]]])
    lp = {"gate": jnp.eye(4), "bias": jnp.zeros(4)}
    u = jnp.log(s / (1 - s))
    hp = dict(HP, top_k=1)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[1]]]))
    assert bool(info["followed"][0, 0]) and not bool(info["refused"][0, 0])
    assert jnp.allclose(w[0, 0], jnp.asarray([0.0, 2.5, 0.0, 0.0]), atol=1e-6)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[3]]]))
    assert bool(info["refused"][0, 0]) and jnp.allclose(w[0, 0, 0], 2.5, atol=1e-6)
    # the selection bias chooses, the score weighs
    w, _ = ref.route(u, dict(lp, bias=jnp.asarray([0.0, 0.0, 0.5, 0.0])), hp)
    assert jnp.allclose(w[0, 0], jnp.asarray([0.0, 0.0, 2.5, 0.0]), atol=1e-6)
    assert ref.compare(jnp.ones((2, 8)), jnp.ones((2, 8)), jnp.ones(4, bool), tol=1e-3)[
        "ok"] is False  # every pair followed: over MAX_FOLLOWED_SHARE
    ks = jax.random.split(jax.random.key(2), 4)
    H, F = 8, 2 * ref.DENSE_BLOCK
    n = lambda k, *shape: 0.1 * jax.random.normal(k, shape)
    tree = {"attn": {name + "_proj": {"kernel": jnp.zeros((1, ))} for name in "qkvo"}
            | {"q_norm": {"scale": 0}, "k_norm": {"scale": 0}},
            "attn_norm": {"scale": 0}, "mlp_norm": {"scale": 0},
            "mlp": {"gate_proj": {"kernel": n(ks[0], H, F)}, "up_proj": {"kernel": n(ks[1], H, F)},
                    "down_proj": {"kernel": n(ks[2], F, H)}}}
    lp = ref._block(tree)
    assert lp["m_gate"].shape == (2, H, ref.DENSE_BLOCK) and lp["m_down"].shape == (
        2, ref.DENSE_BLOCK, H)
    h = jax.random.normal(ks[3], (1, 5, H))
    m = tree["mlp"]
    with jax.default_matmul_precision("highest"):
        want = ref._gated_ffn(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                              m["down_proj"]["kernel"])
        assert jnp.allclose(ref.dense_ffn(h, lp), want, atol=1e-5)


def test_required_work(served):
    """The issue's arithmetic: a gated expert's three matrices are 75.5 MB in
    bf16, the 16 held in each of five sparse blocks (the module's among them)
    6.04 GB a step; 256 rows a step x top-8 / 128 = 16 pairs an expert."""
    assert flops_exaone_moe.gated_expert_weight_bytes(served, 2) == 3 * 6144 * 2048 * 2 == 75_497_472
    pairs = 5 * 256 * 8 // 8  # an eighth of the pairs land on this chip's sixteen
    ops, nbytes = flops_exaone_moe.gated_experts_call(served, 5 * 16, pairs, 2)
    assert ops == 6 * 6144 * 2048 * pairs
    assert nbytes == 5 * 16 * 75_497_472 + pairs * 2 * 6144 * 2
    assert abs(5 * 16 * 75_497_472 / 1e9 - 6.04) < 0.005
    peaks = cells.load_peaks()["TPU v5 lite"]
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


def test_readers_on_a_hand_made_trace(served):
    evs = [("fusion.1 bf16[16,256,2048]", 0.00, 0.20, "jit(draft)/layer_1/moe/moe_experts/dot_general"),
           ("fusion.2 f32[256,128]", 0.20, 0.05, "jit(draft)/layer_1/moe/moe_router/dot_general"),
           ("fusion.3 bf16[16,256,2048]", 0.25, 0.05,
            "jit(draft)/mtp_draft/block/moe/moe_experts/dot_general"),
           ("fusion.4 bf16[256,6144]", 0.30, 0.05, "jit(draft)/mtp_draft/eh_proj/dot_general"),
           ("fusion.5 f32[128,8,258]", 0.35, 0.10, "jit(draft)/layer_0/attn/swa_attn/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.45, 0.05,
            "jit(draft)/layer_3/attn/dstpu_decode_attn"),
           ("fusion.9 bf16[256,19200]", 0.50, 0.10, "jit(draft)/lm_head/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
           "values": {"moe_experts_touched_traced": 5 * 16 * 4, "moe_pairs_here_traced": 1280 * 4,
                      "moe_layer_calls_traced": 20},
           "telemetry": {"counters": {"serving/spec_draft_tokens": {"total": 1000},
                                      "serving/spec_accepted_tokens": {"total": 10},
                                      "serving/spec_verify_columns": {"total": 2000},
                                      "serving/spec_rows_void": {"total": 990}}}}
    assert _reader("mtp_draft_device_pct")(obs) == pytest.approx(10.0)
    # 5 x 16 x 4 experts' three matrices and the pairs' rows over 0.25 s, the module's among them
    nbytes = 5 * 16 * 4 * 75_497_472 + 1280 * 4 * 2 * 6144 * 2
    assert _reader("gated_experts_roofline")(obs) == pytest.approx(
        100 * nbytes / peaks["hbm_bytes_per_s"] / 0.25)
    assert _reader("gated_experts_roofline")(obs) < 100
    for name, want in (("mtp_accept_pct", 1.0), ("spec_rows_void_pct", 49.5)):
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            m = json.load(f)
        assert reducers.BUILTIN[m["reducer"]](m["args"], obs) == pytest.approx(want)
    # cell 6's reader of the windowed layers' scope reads this model's too
    assert _reader("window_attention_device_pct")(obs) == pytest.approx(10.0)


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's traces have no such scope, its jobs no such values and its
    sink no such counters: every new reader returns None and raises nothing
    (the line then leaves the metric out). The gated experts' roofline reads
    nothing of a model whose experts have two matrices or a softmax router."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("ragged-dot-none.1 custom-call", 0.5, 0.3, "")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    relu2 = types.SimpleNamespace(activation="relu2", moe_scoring="sigmoid", hidden_size=2688,
                                  expert_ffn_size=1856)
    softmax = types.SimpleNamespace(activation="swiglu", moe_scoring="softmax", hidden_size=4096,
                                    expert_ffn_size=2048)
    counted = {"moe_experts_touched_traced": 100, "moe_pairs_here_traced": 100}
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": peaks, "telemetry": {"counters": {}}},
                {"program_trace": trace, "model_cfg": relu2, "peaks": peaks, "itemsize": 2,
                 "values": counted},
                {"program_trace": trace, "model_cfg": softmax, "peaks": peaks, "itemsize": 2,
                 "values": counted},
                {"program_trace": None}, {"program_trace": trace}):
        for name in NEW_READERS:
            assert _reader(name)(dict(obs)) is None
        for name in ("mtp_accept_pct", "spec_rows_void_pct"):
            with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
                m = json.load(f)
            assert reducers.BUILTIN[m["reducer"]](m["args"], dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, f"chipbench/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"}
    pub = cfg["published"]
    # the published lists are copied whole; the program is given entries 0-4
    run = cfg["layers_run"]
    assert run["layer_types"] == pub["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert run["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert list(served.layer_windows) == run["sliding_windows"] == pub["sliding_windows"][:5]
    assert [served.layer_parts(i)[1] for i in range(5)] == ["mlp"] + ["moe"] * 4
    assert [served.layer_rotates(i) for i in range(5)] == [True, True, True, False, True]
    # every published width, unchanged, is what the program builds
    assert (served.hidden_size, served.ffn_size, served.expert_ffn_size, served.shared_ffn_size,
            served.num_heads, served.kv_heads, served.head_size, served.ring_rows(0)) == (
        pub["hidden_size"], pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_shared_experts"] * pub["moe_intermediate_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["sliding_window"])
    assert (served.num_experts, served.moe_top_k, served.moe_routed_scale, served.moe_scoring,
            served.layernorm_epsilon, served.rope_theta, served.moe_first_dense,
            served.mtp_layers, served.tie_embeddings) == (
        pub["num_experts"], pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["scoring_func"], pub["rms_norm_eps"], pub["rope_parameters"]["rope_theta"],
        pub["first_k_dense_replace"], pub["num_nextn_predict_layers"],
        pub["tie_word_embeddings"])
    assert (served.experts_held, served.moe_first_expert, served.vocab_size, served.num_layers,
            served.max_seq_len) == (16, 0, 19200, 5, 4096)
    assert served.vocab_size * 8 == pub["vocab_size"] and served.experts_held * 8 == pub["num_experts"]
    assert cfg["sizes"]["parameters_here"] == served.num_params() == 4_543_318_144
    assert "8 v5e chips that share each layer" in cfg["deployment"]
    assert "16 rows a held expert a step where the deployment's would see 128" in cfg["deployment"]
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed"):
        assert cfg[key]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(cfg["reduced"]) and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] and entry["file"] == f"chipbench/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    serve_rate = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert serve_rate["workloads"][-1] == CELL
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"]
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported
    assert "window_attention_device_pct" in reported
    # other models' yardsticks are not this model's
    assert not {"moe_experts_roofline", "relu2_experts_roofline", "ssd_state_roofline"} & reported
    sv, tr = workload["serve"], workload["serve"]["traffic"]
    assert (tr["clients"], sv["num_slots"], sv["max_len"], sv["steps_per_sync"],
            sv["prefill_chunk"], tr["pool"], sv["spec_tokens"], sv["spec_draft"]) == (
        128, 128, 4096, 4, 512, 64, 1, "module")
    assert (tr["prompt_len"], tr["output_len"], tr["max_total"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64, "max": 1024},
        {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}, 4080)
    # a sync may advance a row by 8: the longest request still fits its slot
    assert tr["max_total"] + 2 * sv["steps_per_sync"] <= sv["max_len"]
    # both compared prompts pass the window; one inside a chunk, one over three
    short, long_ = sv["collect_prompt_lens"]
    assert pub["sliding_window"] < short < sv["prefill_chunk"]
    assert 2 * sv["prefill_chunk"] < long_ < 3 * sv["prefill_chunk"]


@pytest.mark.parametrize("fixture, correct", [
    ("tiny.serve.exaone-moe", True),
    # the same flow again, 2.5 minutes of the tier-1 run's 24.5: the slow lane's
    pytest.param("tiny.serve.exaone-moe.wrong", False, marks=pytest.mark.slow)])
def test_serve_exaone_moe_rehearsal(fixture, correct):
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
    assert all(checks.values()), checks
    assert note["info"]["window_bytes_per_slot"] == 8192
