"""The ``bailing_hybrid`` cell's files (inclusionAI Ling-3.0-flash): the
reference's own checks (the recurrence against the literal matrix rule, every
control not ok, the router against a literal top-k-in-groups and its
following of near ties), the required work from shapes at the published
widths, the readers on a hand-made trace and on a program without the layers,
the configuration against the catalog's row and the program's preset, the
rehearsal fixtures through ``serve_ling_hybrid``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, flops, flops_exaone_moe, flops_gdn, flops_mla_moe
from chipbench.references import ling_hybrid as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "ling-3.0-flash"
CELL = CONFIG + ".serve.reason-closed"
NEW_METRICS = ("latent_rows_roofline", )
HP = {"eps": 1e-6, "top_k": 4, "routed_scale": 2.5, "renorm_eps": 1e-20, "n_group": 4,
      "topk_group": 2, "decay_lower_bound": -5.0, "theta": 1e4, "first": 0}
TOL = ref.TOL["float32"]


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config(CONFIG)).cfg


@pytest.fixture(scope="module")
def tiny():
    """(the reference's tree of the tiny preset on the benchmark's draw, ids)."""
    from chipbench.jobs.serve_ling_hybrid import ling_params
    from deepspeed_tpu.models import get_model
    model = get_model("tiny-ling", dtype=jnp.float32)
    params = ling_params(model, 5, jnp.dtype("float32"))
    ids = jax.random.randint(jax.random.key(1), (1, 45), 0, model.cfg.vocab_size)
    return ref.from_tree(params, model.cfg.layer_types), ids


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def test_reference_recurrence_is_the_literal_matrix_rule():
    """``S_t = (I - beta k k^T) Diag(e^g) S_(t-1) + beta k v^T``, ``o = S^T q``
    with matrices, one head, in numpy: what :func:`ref.kda` scans."""
    n, dk, H, T = 2, 4, 12, 9
    ks = jax.random.split(jax.random.key(0), 12)
    rnd = lambda i, *shape: np.asarray(0.5 * jax.random.normal(ks[i], shape), np.float64)
    lp = dict(wq=rnd(0, H, n * dk), wk=rnd(1, H, n * dk), wv=rnd(2, H, n * dk), wb=rnd(3, H, n),
              wf=rnd(4, H, n * dk), wg=rnd(5, H, n * dk), wo=rnd(6, n, dk, H),
              a_log=rnd(7, n), dt_bias=rnd(8, n * dk), taps=rnd(9, 3 * n * dk, 4),
              o_ln=1.0 + rnd(10, dk))
    u = rnd(11, 1, T, H)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.kda(jnp.asarray(u, jnp.float32),
                                 {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}, HP))

    def conv(z, taps):  # (T, C), (C, 4)
        pad = np.concatenate([np.zeros((3, z.shape[1])), z])
        return np.stack([sum(taps[:, j] * pad[t + j] for j in range(4)) for t in range(T)])
    silu = lambda x: x / (1 + np.exp(-x))
    sig = lambda x: 1 / (1 + np.exp(-x))
    x = u[0]
    q = silu(conv(x @ lp["wq"], lp["taps"][:n * dk])).reshape(T, n, dk)
    k = silu(conv(x @ lp["wk"], lp["taps"][n * dk:2 * n * dk])).reshape(T, n, dk)
    v = silu(conv(x @ lp["wv"], lp["taps"][2 * n * dk:])).reshape(T, n, dk)
    unit = lambda y: y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = sig(x @ lp["wb"])
    g = -5.0 * sig(np.exp(lp["a_log"])[:, None] * (x @ lp["wf"] + lp["dt_bias"]).reshape(T, n, dk))
    assert g.min() > -5 and g.max() < 0 and np.ptp(g[:, 0], axis=-1).min() > 0  # a channel's own
    out = np.zeros((T, n, dk))
    for h in range(n):
        S = np.zeros((dk, dk))
        for t in range(T):
            kk = k[t, h][:, None]
            S = (np.eye(dk) - beta[t, h] * kk @ kk.T) @ np.diag(np.exp(g[t, h])) @ S \
                + beta[t, h] * kk @ v[t, h][None, :]
            out[t, h] = S.T @ q[t, h]
    out = out / np.sqrt((out * out).mean(-1, keepdims=True) + 1e-6) * lp["o_ln"]
    out = out * sig(x @ lp["wg"]).reshape(T, n, dk)
    want = np.einsum("tnd,ndh->th", out, lp["wo"])
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_every_control_comes_out_not_ok(tiny):
    tree, ids = tiny
    want, _ = ref.forward(tree, ids, HP)
    again, _ = ref.forward(tree, ids, HP, first=20)
    assert ref.compare(again[0], want[0, 20:], tol=TOL)["ok"]  # in blocks = the whole
    for control in (dict(levels=127.0), dict(head_decay=True), dict(group_limit=False)):
        other, _ = ref.forward(tree, ids, HP, **control)
        res = ref.compare(other[0], want[0], tol=TOL)
        assert not res["ok"] and res["error"] > 100 * TOL, control


def test_reference_router_follows_near_ties_of_groups_and_of_experts():
    """16 experts in 4 groups, 2 kept, top-2: the reference's own choice, a
    program's choice in another group that is a near tie (followed), the same
    far off (refused), an expert's near tie inside the kept groups."""
    E = 16
    c = np.full(E, 0.10)
    c[[0, 1]] = 0.90, 0.80      # group 0: score 1.70
    c[[4, 5]] = 0.70, 0.60      # group 1: score 1.30
    c[[8, 9]] = 0.70, 0.598     # group 2: score 1.298, a near tie with group 1
    c[[12, 13]] = 0.30, 0.20    # group 3: far off
    logit = lambda p: np.log(p / (1 - p))
    lp = {"gate": jnp.eye(E), "bias": jnp.zeros((E, ))}
    hp = dict(HP, top_k=3, routed_scale=1.0)
    u = jnp.asarray(logit(c), jnp.float32)[None, None]
    w, info = ref.route(u, lp, hp)
    assert set(np.flatnonzero(np.asarray(w[0, 0]))) == {0, 1, 4}
    assert not bool(info["followed"][0, 0]) and not bool(info["refused"][0, 0])
    near = jnp.asarray([[[0, 1, 8]]], jnp.int32)  # group 2 for group 1: a near tie
    w, info = ref.route(u, lp, hp, follow=near)
    assert set(np.flatnonzero(np.asarray(w[0, 0]))) == {0, 1, 8} and bool(info["followed"][0, 0])
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-6)
    far = jnp.asarray([[[0, 1, 12]]], jnp.int32)  # group 3: whole deviations under
    w, info = ref.route(u, lp, hp, follow=far)
    assert set(np.flatnonzero(np.asarray(w[0, 0]))) == {0, 1, 4} and bool(info["refused"][0, 0])
    # experts: 4 (0.70) against 5 (0.60) is no near tie; equal scores would be
    inside = jnp.asarray([[[0, 1, 5]]], jnp.int32)
    assert bool(ref.route(u, lp, hp, follow=inside)[1]["refused"][0, 0])
    c[5] = 0.6999
    u = jnp.asarray(logit(c), jnp.float32)[None, None]
    w, info = ref.route(u, lp, hp, follow=inside)
    assert set(np.flatnonzero(np.asarray(w[0, 0]))) == {0, 1, 5} and bool(info["followed"][0, 0])


def test_required_work(served):
    """The issue's arithmetic at the published widths, through the accepted
    functions: a latent row is 1,152 B and 2 x 32 x (576 + 512) operations; a
    state 1,048,576 B each way; a routed expert's three matrices 11,796,480 B,
    the 128 held in 6 layers 9.06 GB if every one is touched."""
    ops, nbytes = flops_mla_moe.mla_attention_call(served, 1000, 2)
    assert (ops, nbytes) == (1000 * 2 * 32 * (576 + 512), 1000 * 1152)
    assert flops_gdn.state_bytes(served, 2) == 1_048_576
    ops, nbytes = flops_gdn.gdn_state_call(served, 192, 2)
    assert nbytes == 2 * 192 * 1_048_576
    assert flops_exaone_moe.gated_expert_weight_bytes(served, 2) == 11_796_480
    assert abs(6 * 128 * 11_796_480 / 1e9 - 9.06) < 0.005
    peaks = cells.load_peaks()["TPU v5 lite"]
    ops, nbytes = flops_exaone_moe.gated_experts_call(served, 6 * 128, 6 * 384, 2)
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


def _hand_made(served):
    evs = [("fusion.1 bf16[192,12288]", 0.00, 0.10, "jit(fused)/layer_1/gdn/gdn_proj/dot_general"),
           ("dstpu_gdn_step.1 custom-call", 0.10, 0.20, "jit(fused)/layer_1/gdn/gdn_state/x"),
           ("fusion.3 bf16[192,2560]", 0.30, 0.05, "jit(fused)/layer_1/gdn/gdn_out/dot_general"),
           ("fusion.4 bf16[192,6144]", 0.35, 0.05, "jit(fused)/layer_5/attn/mla_proj/dot_general"),
           ("fusion.5 f32[192,32,1,256]", 0.40, 0.10, "jit(fused)/layer_5/attn/mla_attn/while"),
           ("fusion.6 bf16[128,192,768]", 0.50, 0.30, "jit(fused)/layer_5/moe/moe_experts/mul"),
           ("fusion.7 f32[192,512]", 0.80, 0.05, "jit(fused)/layer_5/moe/moe_router/dot_general"),
           ("fusion.9 bf16[192,39296]", 0.85, 0.05, "jit(fused)/lm_head/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    return {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
            "num_slots": 192,
            "series": {"live_kv_rows": [200_000, 240_000], "slot_occupancy_pct": [100.0, 100.0]},
            "values": {"column_forwards_traced": 40}}, peaks


def test_readers_on_a_hand_made_trace(served):
    obs, peaks = _hand_made(served)
    # ONE latent layer of the seven: 40 forwards x 1 layer x 220,000 rows x 1,152 B
    least = 40 * 1 * 220_000 * 1152 / peaks["hbm_bytes_per_s"]
    assert _reader("latent_rows_roofline")(obs) == pytest.approx(100 * least / 0.10)
    assert 0 < _reader("latent_rows_roofline")(obs) < 100
    # the accepted readers on the same trace: six KDA layers' states both ways
    least = 40 * 6 * 2 * 192 * 1_048_576 / peaks["hbm_bytes_per_s"]
    assert _reader("gdn_state_roofline")(obs) == pytest.approx(100 * least / 0.20)
    assert _reader("gdn_mixer_device_pct")(obs) == pytest.approx(35.0)
    assert _reader("gdn_state_device_pct")(obs) == pytest.approx(20.0)
    assert _reader("mla_attention_device_pct")(obs) == pytest.approx(10.0)


def test_readers_find_nothing_in_a_program_without_the_layers(served):
    """The parent's traces have no such scope, its models no such size and
    its jobs no such values: the new reader returns None and raises nothing
    (the line then leaves the metric out)."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("fusion.2 bf16[64,2048]", 0.5, 0.3, "jit(fused)/layer_0/mamba2/ssd_proj/conv")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    full, _ = _hand_made(served)
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": peaks},
                {"program_trace": None}, {"program_trace": trace},
                dict(full, program_trace=trace),  # the sizes and no such scope
                dict(full, values={}),  # no forwards counted
                dict(full, model_cfg=types.SimpleNamespace(layer_types=("full_attention", ),
                                                           kv_lora_rank=0))):
        for name in NEW_METRICS:
            assert _reader(name)(dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, f"chipbench/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"}
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert cfg["expert_swiglu_limit_list"] == pub["expert_swiglu_limit_list"][:7] == [0] * 7
    assert cfg["share_expert_swiglu_limit_list"] == pub["share_expert_swiglu_limit_list"][:7]
    # the seven kinds, stated outright: the published layers 0-6 by the family's rule
    from deepspeed_tpu.models import bailing_hybrid_layers
    kinds = {"kda": "linear_attention", "mla": "full_attention"}
    assert served.layer_types == bailing_hybrid_layers(42, pub["layer_group_size"])[:7] == tuple(
        kinds[t] for t in cfg["layers_run"]["layer_types"])
    assert [served.layer_parts(i)[1] for i in range(7)] == [
        {"dense": "mlp", "experts": "moe"}[f] for f in cfg["layers_run"]["ffn"]]
    # every published width, unchanged, is what the program builds
    assert (served.hidden_size, served.num_heads, served.linear_num_heads,
            served.linear_key_head_dim, served.linear_value_head_dim, served.linear_conv_kernel,
            served.kv_lora_rank, served.qk_rope_head_dim, served.qk_nope_head_dim,
            served.v_head_dim, served.head_size, served.ffn_size, served.expert_ffn_size,
            served.shared_ffn_size, served.moe_top_k, served.moe_n_group, served.moe_topk_group,
            served.moe_routed_scale, served.rope_theta, served.layernorm_epsilon,
            served.linear_decay_lower_bound) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_attention_heads"],
        pub["head_dim"], pub["head_dim"], pub["short_conv_kernel_size"], pub["kv_lora_rank"],
        pub["qk_rope_head_dim"], pub["qk_nope_head_dim"], pub["v_head_dim"], pub["qk_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["num_experts_per_tok"], pub["n_group"],
        pub["topk_group"], pub["routed_scaling_factor"], pub["rope_theta"], pub["rms_norm_eps"],
        pub["kda_lower_bound"])
    assert served.q_lora_rank == 0 and pub["q_lora_rank"] is None
    assert (served.num_experts, served.experts_held, served.moe_first_expert,
            served.moe_first_dense, served.vocab_size, served.num_layers, served.max_seq_len,
            served.mtp_layers, served.tie_embeddings) == (
        pub["num_experts"], cfg["num_experts"], 0, cfg["first_k_dense_replace"],
        cfg["vocab_size"], 7, 4096, 0, pub["tie_word_embeddings"])
    assert served.experts_held == 2 * (pub["num_experts"] // pub["n_group"])  # two whole groups
    assert 4 * cfg["vocab_size"] == pub["vocab_size"]
    # the sizes the file states, from the widths
    sizes, h = cfg["sizes"], served.hidden_size
    assert sizes["parameters_here"] == served.num_params() == 5_231_790_016
    assert sizes["kda_mixer"] == 6 * h * 4096 + h * 32 + 32 + 4096 + 12288 * 4 + 128
    assert sizes["latent_mixer"] == h * 32 * 192 + h * 576 + 512 + 512 * 32 * 256 + h * 32 \
        + 4096 * h
    assert sizes["dense_ffn"] == 3 * h * served.ffn_size
    assert sizes["expert"] == sizes["shared_expert"] == 3 * h * 768
    assert sizes["router_and_bias"] == h * 512 + 512
    assert sizes["expert_layer_ffn_here"] == (128 * sizes["expert"] + sizes["shared_expert"]
                                              + sizes["router_and_bias"])
    assert sizes["layer_0"] == sizes["kda_mixer"] + sizes["dense_ffn"] + 2 * h
    assert sizes["kda_expert_layer"] == sizes["kda_mixer"] + sizes["expert_layer_ffn_here"] + 2 * h
    assert sizes["latent_expert_layer"] == (sizes["latent_mixer"] + sizes["expert_layer_ffn_here"]
                                            + 2 * h)
    assert sizes["parameters_here"] == (
        sizes["layer_0"] + 5 * sizes["kda_expert_layer"] + sizes["latent_expert_layer"]
        + sizes["embedding_and_head"] + sizes["final_norm"])
    assert sizes["kv_bytes_per_position"] == cfg["reference"]["kv_bytes_per_token"] == 576 * 2
    assert sizes["state_bytes_per_slot"] == cfg["reference"]["state_bytes_per_slot"] == 6 * (
        sizes["state_bytes_a_layer"] + sizes["window_bytes_a_layer"]) == 6_733_824
    assert sizes["state_bytes_a_layer"] == 32 * 128 * 128 * 2
    assert sizes["window_bytes_a_layer"] == 3 * 12288 * 2
    assert "one chip of the 4 v5e that share each layer" in cfg["deployment"]
    for key in ("source", "reduced", "reduced_how", "deployment", "assumed", "not_served"):
        assert cfg[key]
    assert {"use_qk_norm", "group_norm_size", "use_mla_nope"} <= set(cfg["assumed"])
    assert {"num_nextn_predict_layers", "expert_swiglu_limit_list",
            "share_expert_swiglu_limit_list"} <= set(cfg["not_served"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(cfg["reduced"]) and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] and entry["file"] == f"chipbench/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    serve_rate = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve_rate["workloads"]  # (by membership: later cells are appended behind it)
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"] and workload["job"] == "serve_ling_hybrid"
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported
    assert {"gated_experts_roofline", "moe_experts_device_pct", "moe_router_device_pct",
            "moe_pairs_here_pct", "gdn_mixer_device_pct", "gdn_state_device_pct",
            "gdn_state_roofline", "mla_attention_device_pct", "lm_head_device_pct",
            "hbm_peak_pct.serve", "pump_host_busy_pct", "pump_wait_ms"} <= reported
    # one latent call a MoE layer call would read six times too high here
    assert not {"mla_attention_roofline", "moe_experts_roofline", "mtp_accept_pct",
                "full_attention_device_pct", "attn_walk_live_pct"} & reported
    # cells 7-9's traffic but for the clients and the longest request: a client a slot
    _, cell8, _ = cells.load_workload("k-exaone-236b-a23b.serve.reason-closed")
    sv, tr = workload["serve"], workload["serve"]["traffic"]
    assert dict(tr, clients=128, max_total=4080) == cell8["serve"]["traffic"]
    slots = sv["num_slots"]
    assert tr["clients"] == slots and slots % 32 == 0 and 96 <= slots <= 192
    assert tr["max_total"] == 4088
    assert f"{slots} clients = {slots} slots x 4096" in cell["why"]
    assert (sv["max_len"], sv["steps_per_sync"], sv["prefill_chunk"], tr["pool"],
            sv["dtype"]) == (4096, 4, 512, 64, "bfloat16")
    # one prompt inside a chunk, one over three chunks with a partial last
    short, long_ = sv["collect_prompt_lens"]
    assert short < sv["prefill_chunk"] and 2 * sv["prefill_chunk"] < long_ < 3 * sv["prefill_chunk"]


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _benchmark()["workloads"]])
def test_cell_reports_what_the_benchmark_lists(cell):
    """Every cell, the older ones behind this PR's appended list members too:
    the per-layer metrics its run reports are the ones ``BENCHMARK.json``
    lists for it, and the two files give the same ``why``."""
    bench = _benchmark()
    _, workload, root = cells.load_workload(cell)
    assert workload["why"] == next(w for w in bench["workloads"] if w["name"] == cell)["why"]
    reported = set(cells.per_layer_metrics(cell, workload, root))
    assert reported == {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [cell])}
    rate = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert (cell in rate["workloads"]) == ("serve_tokens_per_s" in workload["end_to_end"])


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.ling-hybrid", True),
                                              ("tiny.serve.ling-hybrid.wrong", False)])
def test_serve_ling_hybrid_rehearsal(fixture, correct):
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
    assert {"lower_precision_fails", "head_decay_fails", "no_group_limit_fails",
            "zeroed_state_program_fails", "state_update_in_place"} <= set(checks)
    assert note["info"]["state_bytes_per_slot"] == 32000
    assert note["info"]["kv_bytes_per_token"] == 192
