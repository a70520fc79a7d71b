"""The ``lfm2_moe`` cell's files (LiquidAI LFM2-8B-A1B): the reference's own
checks (in blocks = whole, both controls), the experts' required work from
shapes at the published widths, the readers on a hand-made trace and on a
program without the layers, the configuration against the catalog's row and the
program's preset, the rehearsal fixtures through ``serve_lfm2_moe``."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, flops, flops_exaone_moe
from chipbench.references import lfm2_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "lfm2-8b-a1b"
CELL = CONFIG + ".serve.reason-closed"
NEW_METRICS = ("short_conv_device_pct", )
HP = {"eps": 1e-5, "top_k": 2, "routed_scale": 1.0, "renorm_eps": 1e-6, "theta": 1e6,
      "first": 0}
TOL = ref.TOL["float32"]


@pytest.fixture(scope="module")
def served():
    return cells.build_model(cells.load_config(CONFIG)).cfg


@pytest.fixture(scope="module")
def tiny():
    """(the reference's tree of the tiny preset on the benchmark's draw, ids)."""
    from chipbench.jobs.serve_nemotron_h import nemotron_params
    from deepspeed_tpu.models import get_model
    model = get_model("tiny-lfm2-moe", dtype=jnp.float32)
    params = nemotron_params(model, 5, jnp.dtype("float32"))
    ids = jax.random.randint(jax.random.key(1), (1, 45), 0, model.cfg.vocab_size)
    return ref.from_tree(params, model.cfg.layer_types), ids


def _reader(name):
    return cells.custom_reducer({"name": name, "dir": os.path.join(cells.HERE, "metrics")})


def _conv_lp(key, H=16, W=3):
    ks = jax.random.split(key, 3)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    return dict(w_in=n(ks[0], H, 3 * H), taps=n(ks[1], H, W), w_out=n(ks[2], H, H))


def test_reference_convolution_is_causal_and_three_taps_deep():
    """A later token changes no earlier output, a token changes its own and
    the next two and nothing after them; the plain loop over positions gives
    the same numbers."""
    lp = _conv_lp(jax.random.key(0))
    u = jax.random.normal(jax.random.key(1), (1, 10, 16))
    out = ref.short_conv(u, lp)
    out2 = ref.short_conv(u.at[:, 4].add(1.0), lp)
    assert jnp.allclose(out[:, :4], out2[:, :4], atol=1e-6)
    assert all(not jnp.allclose(out[:, t], out2[:, t]) for t in (4, 5, 6))
    assert jnp.allclose(out[:, 7:], out2[:, 7:], atol=1e-6)
    bcx = u @ lp["w_in"]
    z = bcx[..., :16] * bcx[..., 32:]
    loop = jnp.stack([sum(lp["taps"][:, k] * z[:, t - 2 + k] for k in range(3) if t - 2 + k >= 0)
                      for t in range(10)], axis=1)
    assert jnp.allclose(out, (bcx[..., 16:32] * loop) @ lp["w_out"], atol=1e-5)


def test_reference_drops_the_carried_rows_at_call_boundaries():
    """``serving_calls``: a prompt of 11 in chunks of 4, then a call a token.
    The first position of a call reads its own input alone, the second one
    row back; inside a call nothing changes."""
    calls = ref.serving_calls(11, 14, 4)
    assert calls.tolist() == [0, 0, 0, 0, 4, 4, 4, 4, 8, 8, 8, 11, 12, 13]
    lp = _conv_lp(jax.random.key(2))
    u = jax.random.normal(jax.random.key(3), (1, 14, 16))
    whole, dropped = ref.short_conv(u, lp), ref.short_conv(u, lp, calls)
    same = [bool(jnp.allclose(whole[0, t], dropped[0, t], atol=1e-6)) for t in range(14)]
    assert same == [True, True, True, True, False, False, True, True, False, False, True,
                    False, False, False]
    alone = ref.short_conv(u[:, 12:13], lp)  # a call of one position from zeros
    assert jnp.allclose(dropped[0, 12], alone[0, 0], atol=1e-6)


def test_reference_in_blocks_is_the_whole_forward(tiny):
    """``forward`` (a compiled program a kind of block, experts one at a time,
    the head a block of the vocabulary at a time, the positions asked for)
    against the same equations in one piece, the experts all at once."""
    p, ids = tiny
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: {k: jnp.asarray(v, jnp.float32) for k, v in t.items()}
        x = jnp.asarray(p["embed"], jnp.float32)[ids]
        for kind, lp in zip(p["layer_types"], map(f32, p["layers"])):
            u = ref._rms(x, lp["op_ln"], HP["eps"])
            x = x + (ref.short_conv(u, lp) if kind == "short_conv" else ref.attention(u, lp, HP))
            g = ref._rms(x, lp["ffn_ln"], HP["eps"])
            if "gate" in lp:
                w, _ = ref.route(g, lp, HP)
                act = (jax.nn.silu(jnp.einsum("bth,ehf->btef", g, lp["w_gate"]))
                       * jnp.einsum("bth,ehf->btef", g, lp["w_up"]))
                x = x + jnp.einsum("bte,bted->btd", w,
                                   jnp.einsum("btef,efd->bted", act, lp["w_down"]))
            else:
                x = x + ref._gated_ffn(g, lp["m_gate"], lp["m_up"], lp["m_down"])
        whole = ref._rms(x, jnp.asarray(p["final_norm"], jnp.float32), HP["eps"]) @ jnp.asarray(
            p["embed"], jnp.float32).T
    blocks, routing = ref.forward(p, ids, HP, first=20)
    assert blocks.shape == (1, 25, 256) and routing["followed"].shape == (4, 1, 45)
    assert ref.compare(blocks[0], whole[0, 20:], tol=TOL)["ok"]


def test_both_controls_come_out_not_ok(tiny):
    """The reference with its weight matrices at int8, and the reference that
    drops the carried rows at every call boundary (a prompt of 40 in chunks
    of 16, then a call a token), each against itself: not ok, and far over."""
    p, ids = tiny
    want, _ = ref.forward(p, ids, HP, first=39)
    low, _ = ref.forward(p, ids, HP, first=39, levels=127.0)
    res = ref.compare(low[0], want[0], tol=TOL)
    assert not res["ok"] and res["min_error"] > 100 * TOL
    dropped, _ = ref.forward(p, ids, HP, first=39, call_starts=ref.serving_calls(40, 45, 16))
    res = ref.compare(dropped[0], want[0], tol=TOL)
    assert not res["ok"] and res["error"] > 1000 * TOL
    # position 39 sits 8 into its chunk: its own taps are whole, what it attends over is not
    assert res["errors"][0] < min(res["errors"][1:])
    assert ref.compare(want[0], want[0], tol=TOL)["ok"]


def test_reference_follows_a_near_tie_with_the_published_epsilon():
    """Scores 0.60, 0.595, 0.30, 0.20 with top-1: the program's choice of
    expert 1 is followed with the reference's own weight, s / (s + 1e-6);
    its choice of expert 3 is refused."""
    s = jnp.asarray([[[0.60, 0.595, 0.30, 0.20]]])
    lp = {"gate": jnp.eye(4), "bias": jnp.zeros(4)}
    u = jnp.log(s / (1 - s))
    hp = dict(HP, top_k=1)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[1]]]))
    assert bool(info["followed"][0, 0]) and not bool(info["refused"][0, 0])
    assert float(w[0, 0, 1]) == pytest.approx(0.595 / (0.595 + 1e-6), rel=1e-6)
    w, info = ref.route(u, lp, hp, follow=jnp.asarray([[[3]]]))
    assert bool(info["refused"][0, 0]) and float(w[0, 0, 0]) == pytest.approx(
        0.6 / (0.6 + 1e-6), rel=1e-6)
    # a tie goes to the lowest id, and a selection bias chooses without weighing
    tie = jnp.log(jnp.asarray([[[0.5, 0.5, 0.5, 0.2]]]) / (1 - jnp.asarray([[[0.5, 0.5, 0.5, 0.2]]])))
    w, _ = ref.route(tie, lp, dict(HP, top_k=2))
    assert jnp.allclose(w[0, 0], jnp.asarray([0.5, 0.5, 0.0, 0.0]), atol=1e-5)
    w, _ = ref.route(tie, dict(lp, bias=jnp.asarray([0.0, 0.0, 0.0, 0.4])), dict(HP, top_k=2))
    assert jnp.allclose(w[0, 0], jnp.asarray([0.5 / 0.7, 0.0, 0.0, 0.2 / 0.7]), atol=1e-5)


def test_required_work(served):
    """The issue's arithmetic at the published widths, through cell 8's
    functions: a routed expert's three matrices are 22,020,096 B, the 32 held
    in 10 layers 7.05 GB a decode step, which is memory-bound at 8 rows an
    expert."""
    assert flops_exaone_moe.gated_expert_weight_bytes(served, 2) == 22_020_096
    ops, nbytes = flops_exaone_moe.gated_experts_call(served, 10 * 32, 10 * 256, 2)
    assert ops == 6 * 2048 * 1792 * 10 * 256
    assert abs(10 * 32 * 22_020_096 / 1e9 - 7.05) < 0.005
    assert nbytes == 10 * 32 * 22_020_096 + 10 * 256 * 2 * 2048 * 2
    peaks = cells.load_peaks()["TPU v5 lite"]
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


def test_readers_on_a_hand_made_trace(served):
    evs = [("fusion.1 bf16[64,6144]", 0.00, 0.06, "jit(fused)/layer_0/conv/conv_proj/dot_general"),
           ("fusion.2 f32[64,2048]", 0.06, 0.01, "jit(fused)/layer_0/conv/conv_state/mul"),
           ("fusion.3 bf16[64,2048]", 0.07, 0.03, "jit(fused)/layer_0/conv/conv_out/dot_general"),
           ("fusion.4 bf16[64,7168]", 0.10, 0.05, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("fusion.5 bf16[32,64,1792]", 0.15, 0.45, "jit(fused)/layer_2/moe/moe_experts/mul"),
           ("fusion.6 f32[64,32]", 0.60, 0.03, "jit(fused)/layer_2/moe/moe_router/dot_general"),
           ("dstpu_decode_attn.3 custom-call", 0.65, 0.05,
            "jit(fused)/layer_2/attn/dstpu_decode_attn"),
           ("fusion.9 bf16[64,65536]", 0.70, 0.10, "jit(fused)/lm_head/dot_general")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    obs = {"program_trace": trace, "model_cfg": served, "itemsize": 2, "peaks": peaks,
           "values": {"moe_experts_touched_traced": 10 * 32 * 4 * 10,
                      "moe_pairs_here_traced": 10 * 256 * 4 * 10,
                      "moe_layer_calls_traced": 10 * 4 * 10}}
    # the three scopes and nothing of the block's FFN beside them
    assert _reader("short_conv_device_pct")(obs) == pytest.approx(10.0)
    # the cell's experts through cell 8's reader: three matrices under a sigmoid router
    nbytes = 10 * 32 * 4 * 10 * 22_020_096 + 10 * 256 * 4 * 10 * 2 * 2048 * 2
    assert _reader("gated_experts_roofline")(obs) == pytest.approx(
        100 * nbytes / peaks["hbm_bytes_per_s"] / 0.45)
    assert _reader("gated_experts_roofline")(obs) < 100


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's traces have no such scope, its models no such size and
    its jobs no such values: the new reader returns None and raises nothing
    (the line then leaves the metric out)."""
    evs = [("fusion.9 bf16[64,11008]", 0.0, 0.5, "jit(fused)/layer_0/mlp/up_proj/dot_general"),
           ("fusion.2 bf16[64,2048]", 0.5, 0.3, "jit(fused)/layer_0/mamba2/ssd_proj/conv")]
    trace = {"devices": {"/device:TPU:0": evs}, "host": [], "t0": 0.0, "t1": 1.0}
    peaks = cells.load_peaks()["TPU v5 lite"]
    for obs in ({"program_trace": trace, "model_cfg": types.SimpleNamespace(), "values": {},
                 "series": {}, "peaks": peaks},
                {"program_trace": None}, {"program_trace": trace}):
        for name in NEW_METRICS:
            assert _reader(name)(dict(obs)) is None


def test_configuration_keeps_every_published_number(served):
    with open(os.path.join(ROOT, f"chipbench/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        assert cfg["published"] == row["config"] and cfg["source"] == row["source_url"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    from deepspeed_tpu.models import get_model
    pub = cfg["published"]
    kinds = {"conv": "short_conv", "full_attention": "full_attention"}
    whole = get_model("lfm2-8b-a1b").cfg
    assert whole.layer_types == tuple(kinds[t] for t in pub["layer_types"])
    assert served.layer_types == whole.layer_types[:12] == tuple(
        kinds[t] for t in cfg["layers_run"]["layer_types"])
    assert [served.layer_parts(i)[1] for i in range(12)] == [
        {"dense": "mlp", "experts": "moe"}[f] for f in cfg["layers_run"]["ffn"]]
    # every published width, unchanged, is what the program builds
    assert (served.hidden_size, served.ffn_size, served.expert_ffn_size, served.num_heads,
            served.kv_heads, served.head_size, served.short_conv_kernel) == (
        pub["hidden_size"], pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["hidden_size"] // pub["num_attention_heads"], pub["conv_L_cache"])
    assert (served.num_experts, served.experts_held, served.moe_first_expert, served.moe_top_k,
            served.moe_first_dense, served.moe_routed_scale, served.moe_shared_experts,
            served.layernorm_epsilon, served.rope_theta, served.vocab_size) == (
        pub["num_experts"], pub["num_experts"], 0, pub["num_experts_per_tok"],
        pub["num_dense_layers"], pub["routed_scaling_factor"], 0, pub["norm_eps"],
        pub["rope_theta"], pub["vocab_size"])
    assert (served.moe_renorm_eps, served.num_layers, served.max_seq_len,
            served.tie_embeddings) == (cfg["reference"]["renorm_eps"], 12, 4096, True)
    # the sizes the file states, from the widths
    sizes, h = cfg["sizes"], served.hidden_size
    assert sizes["parameters_published"] == whole.num_params() == 8_339_930_560
    assert sizes["parameters_here"] == served.num_params() == 3_928_728_256
    assert sizes["embedding_tied"] == served.vocab_size * h
    assert sizes["short_conv_operator"] == 4 * h * h + 3 * h
    assert sizes["attention"] == 2 * h * h + 2 * h * 512 + 2 * 64
    assert sizes["dense_ffn"] == 3 * h * served.ffn_size
    assert sizes["expert"] == 3 * h * served.expert_ffn_size
    assert sizes["expert_layer_ffn"] == 32 * sizes["expert"] + h * 32 + 32
    assert sizes["parameters_active_a_token"] == (
        sizes["embedding_tied"] + 18 * sizes["short_conv_operator"] + 6 * sizes["attention"]
        + 2 * sizes["dense_ffn"] + 22 * (4 * sizes["expert"] + h * 32 + 32)
        + 24 * sizes["norms_a_layer"] + h) == 1_557_740_992
    assert sizes["kv_bytes_per_position"] == cfg["reference"]["kv_bytes_per_token"] == 3 * 8 * 128 * 2
    assert sizes["state_bytes_per_slot"] == cfg["reference"]["state_bytes_per_slot"] == 9 * 2 * h * 2
    assert "first of 2 v5e chips as pipeline stages" in cfg["deployment"]
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
    assert CELL in serve_rate["workloads"]  # (by membership: later cells are appended behind it)
    # every per-layer metric the cell reports names it in BENCHMARK.json
    _, workload, root = cells.load_workload(CELL)
    assert workload["why"] == cell["why"] and workload["job"] == "serve_lfm2_moe"
    reported = set(cells.per_layer_metrics(CELL, workload, root))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reported == listed and set(NEW_METRICS) <= reported
    assert {"gated_experts_roofline", "moe_experts_device_pct", "moe_router_device_pct",
            "moe_pairs_here_pct", "full_attention_device_pct", "attn_walk_live_pct",
            "lm_head_device_pct", "pump_host_busy_pct", "pump_wait_ms"} <= reported
    # no window, no drafting module, experts of three matrices under a sigmoid router
    assert not {"window_attention_device_pct", "mtp_accept_pct", "moe_experts_roofline",
                "relu2_experts_roofline", "ssd_state_roofline"} & reported
    # cell 8's traffic but for the clients: a client a slot, ISSUE 50's 128 lowered in its steps
    # of 16 (PERF.md section 6 has the sweep); the ``why`` gives the rows an expert that leaves
    _, cell8, _ = cells.load_workload("k-exaone-236b-a23b.serve.reason-closed")
    sv, tr = workload["serve"], workload["serve"]["traffic"]
    assert dict(tr, clients=128) == cell8["serve"]["traffic"] and tr["max_total"] == 4080
    slots = sv["num_slots"]
    assert tr["clients"] == slots and slots % 16 == 0 and 80 <= slots <= 128
    assert f"{slots} clients = {slots} slots x 4096" in cell["why"]
    assert f"at {slots * served.moe_top_k // served.num_experts} rows each" in cell["why"]
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


@pytest.mark.parametrize("fixture, correct", [("tiny.serve.lfm2-moe", True),
                                              ("tiny.serve.lfm2-moe.wrong", False)])
def test_serve_lfm2_moe_rehearsal(fixture, correct):
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
    assert note["info"]["state_bytes_per_slot"] == 10240
    assert note["info"]["kv_bytes_per_token"] == 1024
