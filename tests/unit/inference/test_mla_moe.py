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

from . import _serving

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
F32_TOL = 1e-4  # float32 on the CPU against float32 "highest": 2e-7 measured
RULE = moe_layer.dense_held_pays  # the rule itself, for the tests of it


@pytest.fixture(autouse=True)
def by_rows_an_expert(monkeypatch):
    """The tiny models' widths (64 x 48, 64 x 128) are no multiples of 256,
    and the rule sends every call of such a model to the dense product. These
    tests steer it by rows an expert alone, as it goes at widths that are, so
    that a scheduler's column of decoding rows stays sparse and its chunk
    goes dense: both dispatches run, each forward by its own shape."""
    monkeypatch.setattr(moe_layer, "dense_held_pays", lambda N, k, E, H, F: 2 * E < N * k)


def _config():
    with open(os.path.join(ROOT, "chipbench/tests/fixtures/configs/tiny-mla-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(model, params, hyper-parameters of the reference, ids) of the
    ``tiny-mla-moe`` preset, all 8 experts held, float32."""
    model = get_model("tiny-mla-moe", dtype=jnp.float32)
    params = jax.jit(model.init_params)(jax.random.key(3))  # (jitted: seconds less a worker)
    hp = ref.kwargs_for(_config(), model.cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 56)), jnp.int32)
    return model, params, hp, ids


def _served(params, slots=4, chunk=16, **cb):
    """An engine over the preset; every case of this file runs under the
    patched rule, so its programs are its own (``fresh``)."""
    return _serving.engine(("tiny-mla-moe", params), slots, chunk, fresh=True, **cb)


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
    eng = _served(params)
    sched = eng.scheduler()
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(0, 256, n)] for n in (9, 40)]
    handles = [sched.submit(p, max_new_tokens=12, collect_logits=True) for p in prompts]
    tree = ref.from_tree(eng.params, model.cfg.num_layers)
    for p, h in zip(prompts, handles):
        _assert_matches_reference(tree, hp, p, h)
    # one device: no program broadcasts its rows; the block of 4 x 16 rows (16 an expert) goes
    # by the dense product over the held experts, the column of 4 (1 an expert) stays sparse
    programs = sched.moe_dispatch_programs
    assert programs["dense"] == 0 and 0 < programs["dense_held"] < programs["sparse"]


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
    eng = _served(params)
    sched = eng.scheduler()
    cfg = model.cfg
    assert sched.cache.bytes_per_token() == cfg.num_layers * cfg.latent_width * 4
    bf16 = _served(params, kv_cache_dtype="bfloat16").scheduler()
    assert bf16.cache.bytes_per_token() == cfg.num_layers * cfg.latent_width * 2
    leaves = jax.tree_util.tree_leaves(sched.cache.pool)
    # position-last: a position is a column (the same bytes a token as rows of 24)
    assert len(leaves) == 1 and leaves[0].shape[-3:] == (1, cfg.latent_width, sched.cache.max_len)
    assert set(sched.cache.leaf_kinds) == {"columns"}
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


def _latent_bits(sched):
    """The latent leaf (scanned: layers, slots, 1, width, positions) as bytes."""
    return np.asarray(jax.tree_util.tree_leaves(sched.cache.pool)[0].view(jnp.uint8))


def test_latent_columns_commit_in_place_and_a_retained_prefix_stays_byte_stable(tiny):
    """The scheduler's programs write the latent columns through the in-place
    kernel (128 positions: one lane block; ``scatter`` 0); a finished request's
    slot is retained for the radix cache and not a byte of it moves while two
    other requests prefill and decode beside it (it rides every sync with
    span 0)."""
    _, params, _, _ = tiny
    sched = _served(params).scheduler()
    rng = np.random.default_rng(5)
    kept = [int(t) for t in rng.integers(0, 256, 40)]
    sched.submit(kept, max_new_tokens=4).result()
    sched.drain()
    slot = next(i for i, st in enumerate(sched.cache.state) if st == "cached")
    before = _latent_bits(sched)[:, slot]
    assert before.any()
    for n in (21, 9):
        sched.submit([int(t) for t in rng.integers(0, 256, n)], max_new_tokens=10)
    sched.drain()
    assert sched.cache.state[slot] == "cached"
    np.testing.assert_array_equal(_latent_bits(sched)[:, slot], before)
    assert sched.kv_commit_programs["inplace"] > 0 and sched.kv_commit_programs["scatter"] == 0


def test_a_reused_slot_serves_from_position_zero_whatever_columns_it_held(tiny):
    """A slot's reset on a latent leaf is its write head back at 0: the
    columns a longer request left behind the new one's head are never
    attended, and the new request's logits are a fresh pool's, bit for bit."""
    _, params, _, _ = tiny
    rng = np.random.default_rng(6)
    long_, short = ([int(t) for t in rng.integers(0, 256, n)] for n in (70, 12))
    used = _served(params, 1, prefix_cache=False).scheduler()
    used.submit(long_, max_new_tokens=20).result()
    h = used.submit(short, max_new_tokens=8, collect_logits=True)
    fresh = _served(params, 1, prefix_cache=False).scheduler()
    g = fresh.submit(short, max_new_tokens=8, collect_logits=True)
    assert list(h.result()) == list(g.result())
    assert np.array_equal(h.result_logits(), g.result_logits())
    held, clean = _latent_bits(used)[:, 0], _latent_bits(fresh)[:, 0]
    live = 4 * (len(short) + 8 - 1)  # bytes a channel; the last token's column is never written
    np.testing.assert_array_equal(held[..., :live], clean[..., :live])
    # (a pump that runs ahead may have written a sync's columns past the end)
    assert held[..., live + 32:].any() and not clean[..., live + 32:].any()


@pytest.mark.parametrize("T, masked", [(1, False), (5, True), (24, False)],
                         ids=["column", "span-masked", "chunk"])
def test_block_walk_on_the_position_last_leaf_matches_the_plain_softmax(T, masked):
    """``_latent_attention_xla`` over ``(B, D, S)`` (positions last, a walk of
    64-position blocks to the longest live row) against one softmax over
    every position, float32: same scores, same masks, same values."""
    from deepspeed_tpu.models.transformer import _latent_attention_xla
    B, nh, D, rank, S = 3, 4, 24, 16, 256
    ks = jax.random.split(jax.random.key(T), 2)
    qf = jax.random.normal(ks[0], (B, nh, T, D), jnp.float32)
    lat = jax.random.normal(ks[1], (B, D, S), jnp.float32)
    heads = jnp.asarray([0, 77, S - T])
    qpos = heads[:, None] + jnp.arange(T)[None]
    key_mask = (jnp.arange(S)[None] >= jnp.asarray([0, 3, 130])[:, None]) if masked else None
    scale = jnp.full((B, T), 0.2, jnp.float32)
    got = _latent_attention_xla(qf, lat, qpos, jnp.max(qpos) + 1, key_mask, scale,
                                rank=rank, block_kv=64, dtype=jnp.float32)
    s = jnp.einsum("bntd,bds->bnts", qf, lat) * 0.2
    keep = jnp.arange(S)[None, None] <= qpos[:, :, None]
    if masked:
        keep = keep & key_mask[:, None]
    probs = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bnts,brs->bntr", probs, lat[:, :rank])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("option, message", [
    ({"kv_cache_dtype": "int8"}, "int8 KV pool"),
    ({"max_extents": 2}, "extent chains"),
])
def test_latent_pool_refuses_what_it_does_not_support(tiny, option, message):
    model, params, _, _ = tiny
    with pytest.raises(ValueError, match="latent KV pool does not support.*" + message):
        _served(params).scheduler(**option)


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
    monkeypatch.setattr(moe_layer, "dense_held_pays", lambda *shape: False)
    before = moe_layer.traced_dispatches()
    whole = _moe_part(model, params, x, 2, 4)
    assert moe_layer.traced_dispatches() == (before[0] + 1, ) + before[1:]
    monkeypatch.setattr(moe_layer, "SPARSE_TILE", 24)
    tiled = _moe_part(model, params, x, 2, 4)
    assert float(jnp.max(jnp.abs(whole - tiled))) < 1e-5


def _pairs(N, k, E, seed):
    """A router's choice for N rows: k distinct experts of E a row, weights."""
    logits = jax.random.normal(jax.random.key(seed), (N, E), jnp.float32)
    w, ids = jax.lax.top_k(jax.nn.softmax(logits), k)
    return ids.astype(jnp.int32), w


@pytest.mark.parametrize("first, held", [(0, 8), (2, 4)], ids=["all_held", "a_share"])
@pytest.mark.parametrize("activation, bias", [("swiglu", False), ("relu2", False), ("gelu", True)])
def test_dense_held_product_is_the_sparse_dispatch_bit_for_bit(activation, bias, first, held):
    """Every held expert on every row, each row's k results gathered by local
    expert id and added by ``combine_chosen``, against the grouped product
    over the sorted pairs: the same bits in float32 on the CPU, for all 8
    experts held and for experts 2-5 of 8 (pairs routed elsewhere weigh 0),
    with rows past their span (not dispatched there, weighed 0 here), for
    gated experts, experts of two matrices and biased ones. 40 rows x top-3:
    one tile of 120 pairs; then three tiles of 48."""
    N, k, E, H, F = 40, 3, 8, 64, 48
    keys = jax.random.split(jax.random.key(11), 6)
    kernels = {"up_proj": 0.1 * jax.random.normal(keys[0], (held, H, F)),
               "down_proj": 0.1 * jax.random.normal(keys[1], (held, F, H))}
    if activation == "swiglu":
        kernels["gate_proj"] = 0.1 * jax.random.normal(keys[2], (held, H, F))
    if bias:
        kernels.update(up_bias=0.1 * jax.random.normal(keys[3], (held, F)),
                       down_bias=0.1 * jax.random.normal(keys[4], (held, H)))
    tokens = jax.random.normal(keys[5], (N, H), jnp.float32)
    ids, w = _pairs(N, k, E, 12)
    valid = (jnp.arange(N) % 5) != 3  # every fifth row lies past its span
    dense = jax.jit(lambda t: moe_layer.combine_held(
        moe_layer.expert_ffn(jnp.broadcast_to(t[None], (held, N, H)), kernels, activation,
                             jnp.float32), ids, w, valid, first))(tokens)
    sparse = jax.jit(lambda t: moe_layer.sparse_expert_ffn(
        t, ids, w, valid, kernels, first, activation, jnp.float32))
    assert np.array_equal(dense, sparse(tokens))
    assert dense.dtype == jnp.float32 and float(jnp.abs(dense[valid]).max()) > 1e-3
    assert not np.any(np.asarray(dense)[~np.asarray(valid)])  # a row past its span adds nothing
    elsewhere = (ids < first) | (ids >= first + held)
    assert held == E or 0 < int(elsewhere.sum()) < N * k
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_layer, "SPARSE_TILE", 48)
        assert np.array_equal(dense, sparse(tokens))


@pytest.mark.parametrize("cell, shape, dense", [
    ("cell 7 decode", (192, 6, 128, 2688, 1856), True),
    ("cell 7 chunk", (512, 6, 128, 2688, 1856), True),
    ("cell 4 decode", (64, 4, 128, 4096, 2048), False),
    ("cell 4 chunk", (256, 4, 128, 4096, 2048), True),
    ("tiny-moe", (18, 2, 4, 64, 128), True),
    # both widths multiples of 256: from MORE than 2 rows an expert ...
    ("2 rows an expert and one pair more", (65, 4, 128, 4096, 2048), True),
    ("1.5 rows an expert", (48, 4, 128, 4096, 2048), False),
    # ... up to 608 rows, past which the dense product's arithmetic loses
    ("608 rows", (608, 4, 128, 4096, 2048), True),
    ("609 rows", (609, 4, 128, 4096, 2048), False),
    ("704 rows, 64 held of 256", (704, 4, 256, 4096, 2048), False),
    # one width that is not: at any rows an expert, up to 896 rows
    ("one such width, 1 row an expert", (24, 6, 128, 3072, 1856), True),
    ("one such width, 896 rows", (896, 6, 128, 2688, 2048), True),
    ("one such width, 897 rows", (897, 6, 128, 2688, 2048), False),
    # neither: up to 2,560 rows
    ("cell 7's widths, one row", (1, 6, 128, 2688, 1856), True),
    ("cell 7's widths, 2,560 rows", (2560, 6, 128, 2688, 1856), True),
    ("cell 7's widths, 2,561 rows", (2561, 6, 128, 2688, 1856), False),
    ("cell 7's widths, a whole block of 192 x 512", (98304, 6, 128, 2688, 1856), False),
    ("the tests' own widths", (4, 2, 8, 64, 48), True),
])
def test_the_dispatch_rule_at_the_cells_shapes(cell, shape, dense):
    """``dense_held_pays`` at the shapes the benchmark's cells run (cell 4's
    decode program, 2 rows an expert, stays sparse; cell 7's programs and
    cell 4's chunk go dense) and on either side of each of its bounds."""
    assert RULE(*shape) is dense, cell


def test_sparse_dispatch_serves_the_other_moe_presets():
    """On one device every MoE preset takes the dispatch its shapes are
    given (``dense_held_pays``): ``tiny-moe`` (Mixtral style, no shared
    expert) equals, BIT FOR BIT and by either dispatch, the dense broadcast
    that a live expert axis and paged experts keep (each row's chosen
    results added in expert order by the one ``combine_chosen``), whatever
    else shares the block and wherever the row sits in it."""
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
    # 18 rows x top-2 over 4 experts: 9 rows an expert, the dense product over the 4 held
    assert RULE(18, 2, 4, 64, model.cfg.expert_ffn_size)
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
    forced = jax.jit(lambda x, spans: moe_layer.MoE(model.cfg).apply(
        {"params": p}, x, serving=True, q_spans=spans))
    with pytest.MonkeyPatch.context() as mp:  # the same rows through the sparse dispatch
        mp.setattr(moe_layer, "dense_held_pays", lambda *shape: False)
        assert np.array_equal(forced(x, jnp.asarray([9, 9])), got)
        assert moe_layer.traced_dispatches()[0] == after[0] + 1

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


def _moe_counters(tmp_path, monkeypatch, pays=None):
    """(the ``serving/moe_*`` counters, the scheduler's tally of its step
    programs, the tokens) of one 20-token prompt and 9 new tokens through a
    share of ``tiny-mla-moe`` (experts 2-5 of 8, 4 slots, chunks of 16), the
    dispatch left to the rule or steered (``pays``)."""
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    if pays is not None:
        monkeypatch.setattr(moe_layer, "dense_held_pays", lambda *shape: pays)
    eng = deepspeed_tpu.init_inference(
        get_model("tiny-mla-moe", dtype=jnp.float32, moe_first_expert=2, moe_experts_held=4),
        config={"dtype": "float32", "max_out_tokens": 128,
                "continuous_batching": {"enabled": True, "num_slots": 4, "prefill_chunk": 16},
                "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    h = sched.submit(list(range(1, 21)), max_new_tokens=9)
    tokens = list(h.result())
    c = {k: v["total"] for k, v in eng.telemetry.snapshot()["counters"].items()
         if k.startswith("serving/moe_")}
    eng.telemetry.close()
    set_sink(None)
    return c, dict(sched.moe_dispatch_programs), tokens


def test_moe_counters_by_where_the_expert_lives(tmp_path, monkeypatch):
    """``serving/moe_*``: pairs here and elsewhere add up to live rows x
    top-k x layers; touched experts and layer calls are summed over layers
    and forwards; the step programs are counted by their dispatch: the
    column of 4 decoding rows (1 row an expert) sparse, the block of 4 x 16
    (16 rows an expert) by the dense product over the 4 experts held."""
    c, programs, tokens = _moe_counters(tmp_path, monkeypatch)
    assert len(tokens) == 9
    assert c["serving/moe_pairs_here"] + c["serving/moe_pairs_elsewhere"] >= (20 + 8) * 2 * 2
    assert 0 < c["serving/moe_pairs_here"] < c["serving/moe_pairs_elsewhere"] * 4
    assert c["serving/moe_layer_calls"] % 2 == 0 and c["serving/moe_layer_calls"] >= 2 * 4
    assert 0 < c["serving/moe_experts_touched"] <= 4 * c["serving/moe_layer_calls"]
    assert c["serving/moe_sparse_programs"] >= 1 and c["serving/moe_dense_programs"] >= 1
    # nothing was broadcast over a mesh axis or to paged experts
    assert programs["dense"] == 0 and programs["dense_held"] == c["serving/moe_dense_programs"]
    assert programs["sparse"] == c["serving/moe_sparse_programs"] + programs["dense_held"]


def test_moe_counters_read_the_same_under_either_dispatch(tmp_path, monkeypatch):
    """The same request with every step program steered to the dense product
    over the held experts, then to the sparse dispatch: the programs are
    counted as what they took, and the pairs held here and elsewhere, the
    experts touched, the layer calls and the tokens are the same (the counts
    are sown before the dispatch branches)."""
    got = {pays: _moe_counters(tmp_path / str(pays), monkeypatch, pays) for pays in (True, False)}
    (dense, dense_programs, dense_tokens), (sparse, sparse_programs, sparse_tokens) = got[True], got[False]
    assert dense["serving/moe_dense_programs"] >= 2 and "serving/moe_sparse_programs" not in dense
    assert sparse["serving/moe_sparse_programs"] >= 2 and "serving/moe_dense_programs" not in sparse
    assert dense_programs == {"sparse": dense["serving/moe_dense_programs"], "dense": 0,
                              "dense_held": dense["serving/moe_dense_programs"]}
    assert sparse_programs == {"sparse": sparse["serving/moe_sparse_programs"], "dense": 0,
                               "dense_held": 0}
    for name in ("pairs_here", "pairs_elsewhere", "experts_touched", "layer_calls"):
        assert dense["serving/moe_" + name] == sparse["serving/moe_" + name] > 0, name
    assert dense_tokens == sparse_tokens and len(dense_tokens) == 9


def test_two_chunk_prompts_beside_decoding_rows_match_reference(tiny):
    """A (16, 64) block: 100-token prompts (two chunks) admitted beside rows
    that decode, every request's logits against the reference that follows
    its routing choice (one entry a position the programs ran, in order)."""
    model, params, hp, _ = tiny
    eng = _served(params, 16, 64)
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
