"""Latent attention + routed experts with a shared one (``mistral4``), on the
serving path, at a small size on the CPU against the plain reference the
chip benchmark uses (``chipbench/references/mistral_small_4.py``: one
reference, not two): full forward, chunked prefill and decode through the
scheduler's latent pool, absorbed against expanded attention, the shares of
an expert-parallel deployment adding up, the comparison failing for left-out
mathematics and lower precision, and the latent pool's bookkeeping."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench.references import mistral_small_4 as ref
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model
from deepspeed_tpu.moe import layer as moe_layer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
F32_TOL = 1e-4  # float32 on the CPU against float32 "highest": 2e-7 measured


def _config():
    with open(os.path.join(ROOT, "chipbench/tests/fixtures/configs/tiny-mla-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(model, params, hyper-parameters of the reference, ids) of the
    ``tiny-mla-moe`` preset, all 8 experts held, float32."""
    model = get_model("tiny-mla-moe", dtype=jnp.float32)
    params = model.init_params(jax.random.key(3))
    hp = ref.kwargs_for(_config(), model.cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 56)), jnp.int32)
    return model, params, hp, ids


def _engine(model="tiny-mla-moe", params=None, **cb):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    return deepspeed_tpu.init_inference(model, params=params, config={
        "dtype": "float32", "max_out_tokens": 128,
        "continuous_batching": dict({"enabled": True, "num_slots": 4, "prefill_chunk": 16}, **cb)})


def test_preset_builds_the_published_sizes():
    cfg = get_model("mistral-small-4-119b").cfg
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (36, 4096, 32, 131072)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1024, 256)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (64, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_top_k) == (128, 128, 4)
    assert (cfg.expert_ffn_size, cfg.moe_shared_experts, cfg.latent_width) == (2048, 1, 320)
    assert abs(cfg.num_params() / 1e9 - 119) < 1  # "119B" as published
    # the benchmark's cut: overrides, no other preset, no width changed
    with open(os.path.join(ROOT, "chipbench/configs/mistral-small-4-119b.json")) as f:
        cut = get_model("mistral-small-4-119b", **json.load(f)["overrides"]).cfg
    assert (cut.num_layers, cut.experts_held, cut.num_experts, cut.vocab_size) == (6, 32, 128, 32768)
    assert cut.num_params() * 2 / 1e9 == pytest.approx(10.85, abs=0.05)  # GB of bf16 weights


@pytest.mark.parametrize("path", ["expanded_no_cache", "absorbed_through_cache"])
def test_full_forward_matches_reference(tiny, path):
    """(a) and (c): the full forward in float32, tight, by the expanded
    attention (no cache) and by the absorbed one (through a cache)."""
    model, params, hp, ids = tiny
    if path == "expanded_no_cache":
        got = model.apply(params, ids)
    else:
        got, _ = model.apply_with_cache(params, ids, model.init_cache(2, 64), 0)
    want, _ = ref.forward(ref.from_tree(params, model.cfg.num_layers), ids, hp)
    assert float(jnp.max(ref.position_errors(got.reshape(-1, 256), want.reshape(-1, 256)))) < F32_TOL
    # positions past original_max_position_embeddings (16) are in it: g(t) > 1 there
    assert hp["rope"]["original_max_position_embeddings"] < ids.shape[1]


def test_absorbed_and_expanded_attention_agree_in_bf16(tiny):
    """(c) at the serving dtype: the two forms round differently and agree
    to bf16's resolution."""
    model, params, _, ids = tiny
    bf = type(model)(dataclasses.replace(model.cfg, dtype=jnp.bfloat16))
    pb = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    expanded = bf.apply(pb, ids).astype(jnp.float32)
    absorbed, _ = bf.apply_with_cache(pb, ids, bf.init_cache(2, 64), 0)
    err = jnp.max(ref.position_errors(absorbed.reshape(-1, 256), expanded.reshape(-1, 256)))
    assert float(err) < 3e-2


def _assert_matches_reference(tree, hp, prompt, handle):
    """The request's logits (the row that chose each token) against the
    reference's full forward, which is handed the program's routing choice:
    every position within the float32 limit, no choice refused."""
    toks = [int(t) for t in handle.result()]
    ids = jnp.asarray([prompt + toks[:-1]], jnp.int32)
    choice = handle.result_choice()
    assert choice.shape[0] == len(tree["layers"]) and choice.shape[1] >= ids.shape[1]
    want, routing = ref.forward(tree, ids, hp, choice=choice[:, None, :ids.shape[1]])
    res = ref.compare(handle.result_logits(), want[0, len(prompt) - 1:], routing["followed"],
                      routing["refused"], tol=F32_TOL)
    assert res["ok"] and res["routing_refused_rows"] == 0, res


def test_scheduler_prefill_chunks_and_decode_match_reference(tiny):
    """(b): chunked prefill (16-token chunks of a 40-token prompt beside a
    decoding row) then decode through the latent pool, logits not tokens,
    against the reference's full forward."""
    model, params, hp, _ = tiny
    eng = _engine(params=params)
    sched = eng.scheduler()
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(0, 256, n)] for n in (9, 40)]
    handles = [sched.submit(p, max_new_tokens=12, collect_logits=True) for p in prompts]
    tree = ref.from_tree(eng.params, model.cfg.num_layers)
    for p, h in zip(prompts, handles):
        _assert_matches_reference(tree, hp, p, h)
    assert sched.moe_dispatch_programs["dense"] == 0 and sched.moe_dispatch_programs["sparse"] > 0


def _moe_part(model, params, x, first, held):
    """Layer 0's MoE output (routed part of the experts held + shared
    expert) as the program computes it for a share."""
    cfg = dataclasses.replace(model.cfg, moe_first_expert=first, moe_experts_held=held)
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    p = dict(p, experts={k: v[first:first + held] for k, v in p["experts"].items()})
    return moe_layer.MoE(cfg).apply({"params": p}, x, serving=True)


def test_shares_add_up_to_the_uncut_layer(tiny):
    """(d), the guide's share test: 8 experts cut into 4 shares; the routed
    parts the shares give, plus the shared expert counted once, are the
    reference's uncut layer."""
    model, params, hp, _ = tiny
    x = jax.random.normal(jax.random.key(5), (3, 7, 64), jnp.float32)
    lp = ref.from_tree(params, 2)["layers"][0]
    with jax.default_matmul_precision("highest"):
        lp32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), lp)
        shared = ref.shared(x, lp32)
        uncut = ref.routed(x, lp32, hp)[0] + shared
        # the reference given a share leaves the same experts out
        ref_parts = [ref.routed(x, dict(lp32, **{k: lp32[k][2 * s:2 * s + 2] for k in ("w1", "w3", "w2")}),
                                hp, first=2 * s)[0] for s in range(4)]
    parts = [_moe_part(model, params, x, 2 * s, 2) - shared for s in range(4)]
    for got, want in zip(parts, ref_parts):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(sum(parts) + shared - uncut))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3  # a share is not nothing


@pytest.mark.parametrize("fault", ["top_k", "renormalise", "interleave", "int8_pool"])
def test_comparison_fails_for(tiny, fault):
    """(e): a wrong top-k, a missing renormalisation, a non-interleaved
    rotation and an int8 pool (latent rows alone rounded) each fail the
    comparison that the right mathematics passes."""
    model, params, hp, ids = tiny
    got, _ = model.apply_with_cache(params, ids, model.init_cache(2, 64), 0)
    tree = ref.from_tree(params, model.cfg.num_layers)
    wrong = {"top_k": dict(hp, top_k=3), "renormalise": dict(hp, renormalise=False),
             "interleave": dict(hp, interleave=False), "int8_pool": hp}[fault]
    want, _ = ref.forward(tree, ids, wrong, pool_levels=127.0 if fault == "int8_pool" else 0.0)
    res = ref.compare(got.reshape(-1, 256), want.reshape(-1, 256), tol=F32_TOL)
    assert not res["ok"] and res["error"] > 10 * F32_TOL, res
    right, _ = ref.forward(tree, ids, hp)
    assert ref.compare(got.reshape(-1, 256), right.reshape(-1, 256), tol=F32_TOL)["ok"]


def test_reference_follows_a_near_tie_and_refuses_a_far_choice(tiny):
    """The rule for routing-margin rows: a program's set that differs from
    the reference's own top-k is taken where its lowest choice is a near tie
    with the reference's k-th, with the reference's own probabilities; one
    further off is refused and the reference keeps its own; ``compare`` fails
    on too many followed pairs and on ANY position over the limit."""
    _, _, hp, _ = tiny
    # a router whose logits are the row's first 8 values: top-2 of 8
    lp = {"gate": jnp.eye(64, 8, dtype=jnp.float32)}
    z = np.tile(np.arange(3.0, -5.0, -1.0, dtype=np.float32), (3, 1))  # 3, 2, 1, ... -4
    z[1, 2] = 1.99  # row 1: the 3rd expert all but ties with the 2nd
    m = jnp.zeros((1, 3, 64), jnp.float32).at[0, :, :8].set(z)
    # the "program" swaps the 2nd choice for the 3rd in rows 0 and 1, for the last in row 2
    follow = jnp.asarray([[[0, 2], [0, 2], [0, 7]]], jnp.int32)
    w, info = ref.route(m, lp, hp, follow)
    assert info["followed"].tolist() == [[False, True, False]]
    assert info["refused"].tolist() == [[True, False, True]]
    assert float(w[0, 1, 2]) > 0 and float(w[0, 1, 1]) == 0  # followed
    assert float(w[0, 0, 2]) == 0 and float(w[0, 2, 7]) == 0 and float(w[0, 0, 1]) > 0  # kept its own
    assert abs(float(jnp.sum(w[0, 1])) - hp["routed_scale"]) < 1e-6  # own probabilities, renormalised
    same, none = ref.route(m, lp, hp, jnp.asarray([[[1, 0]] * 3], jnp.int32))  # the same set
    assert not bool(none["followed"].any() | none["refused"].any())
    assert jnp.array_equal(same, ref.route(m, lp, hp)[0])
    logits = jax.random.normal(jax.random.key(4), (5, 256), jnp.float32)
    assert ref.compare(logits, logits, jnp.zeros((2, 5), bool))["ok"]
    assert not ref.compare(logits, logits, jnp.ones((2, 5), bool))["ok"]  # all pairs followed
    one_off = logits.at[3].multiply(1.2)
    res = ref.compare(one_off, logits, tol=0.1)
    assert not res["ok"] and res["median_error"] == 0.0  # one position of five is enough


def test_latent_pool_bytes_copy_and_radix_hit(tiny):
    """(f): 2 bytes x (16 + 8) values a position a layer (4 in float32), one
    leaf a layer; ``copy_slot`` moves a slot's latent rows; a repeated prompt
    is served through the radix copy and gives the same logits."""
    from deepspeed_tpu.inference.kv_cache import copy_slot
    model, params, _, _ = tiny
    eng = _engine(params=params)
    sched = eng.scheduler()
    cfg = model.cfg
    assert sched.cache.bytes_per_token() == cfg.num_layers * cfg.latent_width * 4
    bf16 = _engine(params=params, kv_cache_dtype="bfloat16").scheduler()
    assert bf16.cache.bytes_per_token() == cfg.num_layers * cfg.latent_width * 2
    leaves = jax.tree_util.tree_leaves(sched.cache.pool)
    assert len(leaves) == 1 and leaves[0].shape[-3:] == (1, sched.cache.max_len, cfg.latent_width)
    prompt = [int(t) for t in np.random.default_rng(2).integers(0, 256, 40)]
    first = sched.submit(prompt, max_new_tokens=6, collect_logits=True)
    a, la = first.result(), first.result_logits()
    again = sched.submit(prompt, max_new_tokens=6, collect_logits=True)
    b, lb = again.result(), again.result_logits()
    assert sched.radix.hits >= 1 and list(a) == list(b) and np.array_equal(la, lb)
    sched.cache.check_invariants()
    pool = copy_slot(sched.cache.pool, 0, 3)
    leaf = jax.tree_util.tree_leaves(pool)[0]
    assert np.array_equal(leaf[:, 3], leaf[:, 0]) and float(jnp.abs(leaf[:, 0]).max()) > 0


@pytest.mark.parametrize("option, message", [
    ({"kv_cache_dtype": "int8"}, "int8 KV pool"),
    ({"max_extents": 2}, "extent chains"),
])
def test_latent_pool_refuses_what_it_does_not_support(tiny, option, message):
    model, params, _, _ = tiny
    with pytest.raises(ValueError, match="latent KV pool does not support.*" + message):
        _engine(params=params).scheduler(**option)


def test_latent_model_refuses_int8_weights():
    comm._state["mesh"] = None
    with pytest.raises(ValueError, match="latent attention is served in its float dtype"):
        deepspeed_tpu.init_inference("tiny-mla-moe", config={"dtype": "int8"})


def test_sparse_dispatch_computes_only_the_pairs_held_here(tiny):
    """The router has its full width; the layer computes the pairs routed to
    the experts it holds and no others; rows past a slot's span are not
    dispatched (they add nothing and are not counted)."""
    model, params, _, ids = tiny
    cfg = dataclasses.replace(model.cfg, moe_first_expert=2, moe_experts_held=4)
    share = type(model)(cfg)
    p = dict(params)
    p["layers"] = dict(p["layers"], moe=dict(p["layers"]["moe"], experts={
        k: v[:, 2:6] for k, v in p["layers"]["moe"]["experts"].items()}))
    assert p["layers"]["moe"]["gate"].shape[-1] == 8  # router width as published
    spans = jnp.asarray([5, 0], jnp.int32)
    _, _, counts = share.apply_with_cache(
        p, ids[:, :8], share.init_cache(2, 64), 0, position_ids=jnp.tile(jnp.arange(8), (2, 1)),
        write_index=jnp.zeros((2, ), jnp.int32), q_spans=spans, expert_stats=True)
    assert counts.shape == (2, 8)
    assert int(counts.sum()) == cfg.num_layers * 5 * cfg.moe_top_k  # live rows only
    assert 0 < int(counts[:, 2:6].sum()) < int(counts.sum())  # some here, some elsewhere


def test_sparse_dispatch_tiles_agree(tiny, monkeypatch):
    """Pairs beyond one grouped product's tile are walked tile by tile, only
    as far as pairs held here reach; the result is the single tile's."""
    model, params, _, _ = tiny
    x = jax.random.normal(jax.random.key(6), (4, 16, 64), jnp.float32)
    whole = _moe_part(model, params, x, 2, 4)
    monkeypatch.setattr(moe_layer, "SPARSE_TILE", 24)
    tiled = _moe_part(model, params, x, 2, 4)
    assert float(jnp.max(jnp.abs(whole - tiled))) < 1e-5


def test_sparse_dispatch_serves_the_other_moe_presets():
    """On one device every MoE preset takes the sparse path: ``tiny-moe``
    (Mixtral style, no shared expert) equals, BIT FOR BIT, the dense
    broadcast that a live expert axis and paged experts keep (each row's
    chosen results added in expert order by the one ``combine_chosen``),
    whatever else shares the block and wherever the row sits in it."""
    from deepspeed_tpu.moe.sharded_moe import top_k_serving_choice
    model = get_model("tiny-moe", dtype=jnp.float32)
    params = model.init_params(jax.random.key(0))
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(1), (2, 9, 64), jnp.float32)
    before = moe_layer.traced_dispatches()
    serve = jax.jit(lambda x, spans: moe_layer.MoE(model.cfg).apply(
        {"params": p}, x, serving=True, q_spans=spans))
    got = serve(x, jnp.asarray([9, 9]))
    after = moe_layer.traced_dispatches()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)

    def dense(tokens):
        ids, w = top_k_serving_choice(tokens @ p["gate"], model.cfg.moe_top_k)
        eo = moe_layer.expert_ffn(jnp.broadcast_to(tokens[None], (4, ) + tokens.shape),
                                  p["experts"], "swiglu", jnp.float32)
        rank = moe_layer.expert_rank(ids)
        ids, w = moe_layer.in_expert_order(ids, rank), moe_layer.in_expert_order(w, rank)
        return moe_layer.combine_chosen(eo[ids, jnp.arange(tokens.shape[0])[:, None]], w)

    assert np.array_equal(got, jax.jit(dense)(x.reshape(-1, 64)).reshape(x.shape))
    some = jnp.asarray([[3, 0, 2], [1, 2, 0]], jnp.int32)
    assert moe_layer.in_expert_order(some, moe_layer.expert_rank(some)).tolist() == [[0, 2, 3], [0, 1, 2]]
    # a row alone in another block, at another place: the same bits
    other = jax.random.normal(jax.random.key(2), (2, 9, 64), jnp.float32).at[1, 4].set(x[0, 2])
    assert np.array_equal(serve(other, jnp.asarray([0, 5]))[1, 4], got[0, 2])


def test_moe_counters_by_where_the_expert_lives(tmp_path):
    """``serving/moe_*``: pairs here and elsewhere add up to live rows x
    top-k x layers; touched experts and layer calls are summed over layers
    and forwards; the step programs are counted by their dispatch."""
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    eng = deepspeed_tpu.init_inference(
        get_model("tiny-mla-moe", dtype=jnp.float32, moe_first_expert=2, moe_experts_held=4),
        config={"dtype": "float32", "max_out_tokens": 128,
                "continuous_batching": {"enabled": True, "num_slots": 4, "prefill_chunk": 16},
                "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    h = sched.submit(list(range(1, 21)), max_new_tokens=9)
    assert len(h.result()) == 9
    c = {k: v["total"] for k, v in eng.telemetry.snapshot()["counters"].items()
         if k.startswith("serving/moe_")}
    eng.telemetry.close()
    set_sink(None)
    assert c["serving/moe_pairs_here"] + c["serving/moe_pairs_elsewhere"] >= (20 + 8) * 2 * 2
    assert 0 < c["serving/moe_pairs_here"] < c["serving/moe_pairs_elsewhere"] * 4
    assert c["serving/moe_layer_calls"] % 2 == 0 and c["serving/moe_layer_calls"] >= 2 * 4
    assert 0 < c["serving/moe_experts_touched"] <= 4 * c["serving/moe_layer_calls"]
    assert c.get("serving/moe_sparse_programs", 0) >= 1 and "serving/moe_dense_programs" not in c


def test_two_chunk_prompts_beside_decoding_rows_match_reference(tiny):
    """A (16, 64) block: 100-token prompts (two chunks) admitted beside rows
    that decode, every request's logits against the reference that follows
    its routing choice (one entry a position the programs ran, in order)."""
    model, params, hp, _ = tiny
    eng = _engine(params=params, num_slots=16, prefill_chunk=64)
    sched = eng.scheduler()
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(0, 256, n)] for n in (12, 100, 70, 100)]
    handles = [sched.submit(p, max_new_tokens=10, collect_logits=True) for p in prompts]
    tree = ref.from_tree(eng.params, model.cfg.num_layers)
    for p, h in zip(prompts, handles):
        _assert_matches_reference(tree, hp, p, h)
    sched.cache.check_invariants()


@pytest.mark.parametrize("ksteps", [1, 4])
def test_split_chunk_program_keeps_choice_shape_and_counts_its_forwards(tiny, tmp_path, ksteps):
    """An (8, 64) collecting chunk program that runs its first forward as two
    over the live rows: the routing choice comes back in the block's
    (L, N, C, k) shape with the column's choices in column 0 and the chunk's in
    its row, equal to the whole-block program's, and the stats count two layer
    calls a layer for the first phase and one a substep."""
    model, params, _, _ = tiny
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)

    def run(whole_block):
        eng = deepspeed_tpu.init_inference("tiny-mla-moe", params=params, config={
            "dtype": "float32", "max_out_tokens": 128,
            "continuous_batching": {"enabled": True, "num_slots": 8, "prefill_chunk": 64},
            "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
        sched = eng.scheduler()
        if whole_block:
            sched._splits_chunk = lambda key: False
        key = ("fused", False, True, 64, ksteps)
        assert sched._splits_chunk(key) is not whole_block
        rng = np.random.default_rng(4)
        ids = rng.integers(0, 256, (8, 64)).astype(np.int32)
        spans = np.array([1, 0, 1, 40, 0, 1, 0, 0], np.int32)  # three decode rows, a chunk of 40
        lens = np.array([5, 0, 9, 0, 0, 3, 0, 0], np.int32)
        zeros = np.zeros(8, np.int32)
        fn = sched._fused_fn(False, True, ksteps, 64)
        out = fn(eng.params, sched.cache.pool, ids, lens, spans, zeros.astype(np.uint32), zeros,
                 zeros.astype(bool), np.ones(8, np.float32), zeros, np.ones(8, np.float32))
        eng.telemetry.close()
        set_sink(None)
        return [np.asarray(x) for x in out[1:]]

    toks, logits, first, substeps, stats = run(False)
    btoks, blogits, bfirst, bsubsteps, bstats = run(True)
    L, k = model.cfg.num_layers, model.cfg.moe_top_k
    assert first.shape == bfirst.shape == (L, 8, 64, k)
    assert substeps.shape == bsubsteps.shape == (ksteps, L, 8, k)
    live = [0, 2, 5]
    assert np.array_equal(first[:, live, 0], bfirst[:, live, 0])
    assert np.array_equal(first[:, 3, :40], bfirst[:, 3, :40])
    assert np.array_equal(substeps[:, :, live + [3]], bsubsteps[:, :, live + [3]])
    assert np.array_equal(toks[:, live + [3]], btoks[:, live + [3]])
    np.testing.assert_allclose(logits[:, live + [3]], blogits[:, live + [3]], rtol=0, atol=1e-6)
    assert (stats[:, -1] == 2 + (ksteps - 1)).all() and (bstats[:, -1] == ksteps).all()
    # the routed pairs of the live rows are the same whichever way they ran
    assert np.array_equal(stats[:, :-2], bstats[:, :-2])
    assert stats[:, :-2].sum() == L * k * ((3 + 40) + 4 * (ksteps - 1))
