"""A ``bailing_hybrid`` stack (``layer_types`` with Kimi-delta
``linear_attention`` layers, a decay a KEY CHANNEL, beside latent
``full_attention`` layers with ONE query projection and a head-wise gate; a
leading dense layer, then gated experts chosen inside a few groups under a
sigmoid router, a shared expert; Ling-3.0-flash's kinds) at a small size on
the CPU in float32: the program against the plain reference
(``chipbench/references/ling_hybrid.py``: one causal forward, no cache, the
recurrence a token at a time), the chunked scan against the one-token rule at
decays at both ends of (-5, 0), the slot pool's span programs over recurrent
state beside latent rows in ONE tree, the group-limited choice against a
literal top-k-in-groups, the four shares of an expert layer against the uncut
layer, the refusals, and what cells 4's and 5's models built before.

Weights: the benchmark's own draw (``serve_ling_hybrid.ling_params``) with norm
scales moved off 1, so that a dropped scale shows. ``TOL``: the reference's
float32 limit, 1e-5; the served path reads 2e-6 at worst."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe.sharded_moe import sigmoid_serving_choice

from . import _ladder
from ._serving import VOCAB
from ._serving import prompts as _prompts

NAME = "tiny-ling"
ref, HP, TOL = _ladder.reference(NAME)
CUT = dict(num_layers=7, layer_types=["linear_attention"] * 5 + ["full_attention",
                                                                 "linear_attention"],
           moe_first_dense=1, moe_experts_held=128, moe_first_expert=0, vocab_size=39296,
           max_seq_len=4096, mtp_layers=0, moe_swiglu_limits=[0] * 7,
           moe_shared_swiglu_limits=[0] * 7)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


def _tree(model, params):
    return _ladder.tree_of(NAME, model, params)


class TestLadder(_ladder.Ladder):
    """(70 positions: four of the scan's chunks of 16 and a padded fifth. The
    served prompts: one inside a chunk or two (9), one over boundaries with a
    partial last (37), last chunks of ONE and of TWO live positions (33, 34:
    fewer than the window's three carried inputs); with two slots for four
    requests, a slot freed and taken by a new request, which starts from a
    zero state and window whatever the last one left. The refusals: the latent
    pool's and the state pool's, both; the first that applies is named.)"""
    twin = NAME

    def served_pool(self, case, sched):
        # two latent layers' rows of 16 + 8 values; five KDA layers' state and window
        assert sched.cache.bytes_per_token() == 2 * 24 * 4
        assert sched.cache.state_bytes_per_slot() == 5 * (4 * 16 * 16 + 3 * 3 * 64) * 4
        # the tiny heads do not tile: the definition serves the decode column
        assert sched.gdn_step_programs["kernel"] == 0 < sched.gdn_step_programs["xla"]


def test_a_span_0_slot_is_bit_for_bit_unchanged(tiny):
    """A row with no live column: its state, its window and its latent rows
    come out as they went in, garbage and all."""
    model, params = tiny
    pool = model.init_cache(3, 32, dtype=jnp.float32)
    pool = jax.tree_util.tree_map(
        lambda leaf: jax.random.normal(jax.random.key(leaf.ndim), leaf.shape, leaf.dtype), pool)
    ids = jax.random.randint(jax.random.key(2), (3, 4), 0, VOCAB)
    spans, at = jnp.asarray([4, 0, 2]), jnp.asarray([5, 7, 0])
    _, new = model.apply_with_cache(params, ids, pool, None, write_index=at, q_spans=spans)
    for old, got in zip(jax.tree_util.tree_leaves(pool), jax.tree_util.tree_leaves(new)):
        np.testing.assert_array_equal(np.asarray(old[1]), np.asarray(got[1]))
        assert not np.array_equal(np.asarray(old[0]), np.asarray(got[0]))


def _scan_operands(ends, B=2, n=3, T=37, dk=8, dv=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    l2 = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (B, n, T, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (B, n, T, dk)))
    v = jax.random.normal(ks[2], (B, n, T, dv))
    u = jax.random.uniform(ks[3], (B, n, T, dk))
    g = {"near_floor": -5.0 + 1e-3 * u, "near_zero": -1e-4 * u,
         "both_ends": jnp.where(u < 0.5, -4.999, -1e-5), "spread": -5.0 * u}[ends]
    beta = jax.random.uniform(ks[4], (B, n, T))
    S = 0.3 * jax.random.normal(ks[5], (B, n, dk, dv))
    return S, q, k, v, g, beta


@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("ends", ["near_floor", "near_zero", "both_ends", "spread"])
def test_chunked_scan_is_the_one_token_rule(ends, chunk):
    """A decay a key channel at both ends of (-5, 0): sixteen positions at
    the floor reach e^80 inside a chunk, in float32; 37 positions pad the
    last chunk."""
    S, q, k, v, g, beta = _scan_operands(ends)
    with jax.default_matmul_precision("highest"):
        o, S_end = tfm.gated_delta_chunked(S, q, k, v, g, beta, chunk=chunk)
        want, state = [], S
        for t in range(q.shape[2]):
            o_t, state = tfm.gated_delta_step(state, q[:, :, t], k[:, :, t], v[:, :, t],
                                              g[:, :, t], beta[:, :, t])
            want.append(o_t)
    want = jnp.stack(want, axis=2)
    # (a running sum of 40 is known to 4e-6 in float32: at the floor the
    # chunk's factors e^(G_t - G_m), e^(G_m - G_s) carry that)
    tol = 4e-5 if ends in ("near_floor", "both_ends") else 1e-5
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(S_end)))
    assert float(jnp.abs(o - want).max()) < tol * float(jnp.abs(want).max())
    assert float(jnp.abs(S_end - state).max()) < tol * float(jnp.abs(state).max())


def test_a_constant_channel_decay_is_the_decay_a_head():
    """The head's scalar is the constant vector: both scans, both one-token rules."""
    S, q, k, v, g, beta = _scan_operands("spread")
    g_head = g[..., 0]
    wide = jnp.broadcast_to(g_head[..., None], g.shape)
    with jax.default_matmul_precision("highest"):
        o_c, S_c = tfm.gated_delta_chunked(S, q, k, v, wide, beta)
        o_h, S_h = tfm.gated_delta_chunked(S, q, k, v, g_head, beta)
    assert float(jnp.abs(o_c - o_h).max()) < 2e-5 * float(jnp.abs(o_h).max())
    assert float(jnp.abs(S_c - S_h).max()) < 2e-5 * float(jnp.abs(S_h).max())
    col = lambda x: x[:, :, 0]
    o1, S1 = tfm.gated_delta_step(S, col(q), col(k), col(v), col(wide), col(beta))
    o2, S2 = tfm.gated_delta_step(S, col(q), col(k), col(v), col(g_head), col(beta))
    np.testing.assert_array_equal(np.asarray(S1), np.asarray(S2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def _literal_choice(c, s, k, n_group, topk_group, scale, eps):
    """Top-k in groups, written out: numpy, one row at a time."""
    ids, ws = [], []
    for row_c, row_s in zip(np.asarray(c, np.float64), np.asarray(s, np.float64)):
        groups = row_c.reshape(n_group, -1)
        score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        open_ = np.full_like(row_c, -np.inf).reshape(n_group, -1)
        open_[kept] = groups[kept]
        chosen = np.argsort(-open_.reshape(-1), kind="stable")[:k]
        ids.append(chosen)
        ws.append(scale * row_s[chosen] / (row_s[chosen].sum() + eps))
    return np.asarray(ids), np.asarray(ws)


@pytest.mark.parametrize("E, n_group, topk_group, k", [(16, 4, 2, 4), (64, 8, 4, 8),
                                                       (512, 8, 4, 8), (12, 3, 1, 2)])
def test_group_limited_choice_is_top_k_in_groups(E, n_group, topk_group, k):
    logits = 2.0 * jax.random.normal(jax.random.key(E), (40, E))
    bias = 0.3 * jax.random.normal(jax.random.key(E + 1), (E, ))
    ids, w = sigmoid_serving_choice(logits, bias, k, 1e-20, n_group, topk_group)
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    want_ids, want_w = _literal_choice(s + bias, s, k, n_group, topk_group, 1.0, 1e-20)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(np.asarray(w), -1), np.sort(want_w, -1), rtol=1e-6)
    # every choice inside topk_group groups; the reference's router agrees
    assert (np.asarray([len(set(r)) for r in np.asarray(ids) // (E // n_group)])
            <= topk_group).all()
    hp = dict(HP, top_k=k, n_group=n_group, topk_group=topk_group, routed_scale=1.0)
    lp = {"gate": jnp.eye(E), "bias": bias}
    w_ref, _ = ref.route(logits[None], lp, hp)
    np.testing.assert_array_equal(np.asarray(w_ref[0] > 0).sum(-1), k)
    assert all(set(np.flatnonzero(np.asarray(r))) == set(i)
               for r, i in zip(w_ref[0], np.asarray(ids)))


def _router_before_groups(logits, bias, k, eps=1e-20):
    """``sigmoid_serving_choice`` as it stood before it knew groups."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    masked = s + bias.astype(jnp.float32)
    ids = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        ids.append(idx)
        masked = jnp.where(jnp.arange(masked.shape[-1])[None, :] == idx[:, None], -jnp.inf,
                           masked)
    ids = jnp.stack(ids, axis=-1)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (jnp.sum(w, axis=-1, keepdims=True) + eps)


@pytest.mark.parametrize("eps", [1e-20, 1e-6])
def test_one_group_is_the_router_as_it_was_bit_for_bit(eps):
    logits = 2.0 * jax.random.normal(jax.random.key(5), (64, 32))
    bias = 0.3 * jax.random.normal(jax.random.key(6), (32, ))
    for got, want in zip(sigmoid_serving_choice(logits, bias, 4, eps),
                         _router_before_groups(logits, bias, 4, eps)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(sigmoid_serving_choice(logits, bias, 4, eps, 1, 1),
                         _router_before_groups(logits, bias, 4, eps)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dispatch", ["sparse", "dense"])
def test_the_four_shares_sum_to_the_uncut_expert_layer(tiny, monkeypatch, dispatch):
    """One expert layer of the stack as four chips hold it (a group each):
    every chip routes over all 16 experts, computes its own four's part and
    the shared expert; the four routed parts and ONE shared part add up to the
    uncut layer, in the program and in the reference alike."""
    from deepspeed_tpu.moe import layer as moe_layer
    from deepspeed_tpu.moe.layer import MoE
    monkeypatch.setattr(moe_layer, "dense_held_pays", lambda *a: dispatch == "dense")
    model, params = tiny
    cfg, lp = model.cfg, params["layer_3"]["moe"]
    x = jax.random.normal(jax.random.key(4), (2, 9, cfg.hidden_size))
    apply = lambda c, p: MoE(c).apply({"params": p}, x, serving=True)
    with jax.default_matmul_precision("highest"):
        whole = apply(cfg, lp)
        only_shared = apply(dataclasses.replace(cfg, moe_experts_held=4), dict(
            lp, experts=jax.tree_util.tree_map(lambda a: jnp.zeros_like(a[:4]), lp["experts"])))
        parts = []
        for chip in range(4):
            held = jax.tree_util.tree_map(lambda a: a[4 * chip:4 * chip + 4], lp["experts"])
            share = dataclasses.replace(cfg, moe_experts_held=4, moe_first_expert=4 * chip)
            parts.append(apply(share, dict(lp, experts=held)) - only_shared)
    total = sum(parts) + only_shared
    assert float(jnp.abs(total - whole).max()) < 1e-5 * float(jnp.abs(whole).max())
    # ... and the reference's share, given the same experts
    tree = _tree(model, params)["layers"][3]
    tree = {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}
    with jax.default_matmul_precision("highest"):
        ref_whole, _ = ref.routed(x, tree, HP)
        ref_parts = [ref.routed(x, dict(tree, **{k: tree[k][4 * c:4 * c + 4] for k in
                                                 ("w_gate", "w_up", "w_down")}),
                                HP, first=4 * c)[0] for c in range(4)]
    assert float(jnp.abs(sum(ref_parts) - ref_whole).max()) < 1e-5 * float(
        jnp.abs(ref_whole).max())
    routed_whole = whole - only_shared
    assert float(jnp.abs(routed_whole - ref_whole).max()) < 1e-5 * float(
        jnp.abs(ref_whole).max())


def test_what_each_layer_kind_declares(tiny):
    model, _ = tiny
    spec = model.cache_spec(3, 32)
    assert [[k for k, *_ in layer] for layer in spec] == [
        ["state", "state"] if t == "linear_attention" else ["columns"]
        for t in model.cfg.layer_types]
    kinds = model.cache_kinds()
    assert kinds[1][2] is None and kinds[1][6] is None and kinds[0][2] == "columns"
    # the cut the benchmark serves: a state (32, 128, 128), a window of 3, a column of 576
    served = get_model("ling-3.0-flash", **CUT)
    spec = served.cache_spec(192, 4096, dtype=jnp.bfloat16)
    shapes = [[shape for _, shape, *_ in layer] for layer in spec]
    assert shapes[0] == [(192, 32, 128, 128), (192, 1, 3, 12288)] and shapes[5] == [
        (192, 1, 576, 4096)]
    per_slot = sum(2 * int(np.prod(s[1:])) for layer in shapes for s in layer if len(layer) == 2)
    assert per_slot == 6_733_824 and 2 * 576 == 1_152
    assert served.cfg.num_params() == 5_231_790_016 == sum(
        x.size for x in jax.tree_util.tree_leaves(
            jax.eval_shape(served.init_params, jax.random.key(0))))


@pytest.mark.parametrize("overrides, message", [
    ({}, "expert_swiglu_limit_list"),
    (dict(CUT, mtp_layers=1), "num_nextn_predict_layers"),
    (dict(CUT, moe_shared_swiglu_limits=[0] * 6 + [7]), "publishes by value and not by form"),
    (dict(CUT, moe_experts_held=96), "WHOLE groups"),
    (dict(CUT, moe_first_expert=32), "WHOLE groups"),
    (dict(CUT, moe_topk_group=9), "kept <= groups"),
    (dict(CUT, moe_swiglu_limits=[0] * 6), "for each of the 7 layers"),
    (dict(CUT, qk_norm=True), "latent attention under layer_types"),
    (dict(CUT, linear_out_gate="tanh"), "linear_out_gate"),
    (dict(CUT, linear_decay_lower_bound=5.0), "LOG decay"),
])
def test_what_the_preset_refuses(overrides, message):
    with pytest.raises(ValueError, match=message):
        get_model("ling-3.0-flash", **overrides)


@pytest.mark.parametrize("chunk", [1, 8], ids=["column", "chunk"])
def test_state_beside_latent_columns_through_a_sync_of_each_width(tiny, monkeypatch, chunk):
    """Cell 10's tree (a state leaf and a window beside a latent leaf of
    columns) through the scheduler's own syncs, the decode column's and a
    chunk's with its decode substeps: after every landing the pool the
    in-place column commit leaves is, leaf for leaf and byte for byte, the
    pool the XLA scatter leaves, and so are the logits; the programs are
    tallied by the commit they were built with."""
    from deepspeed_tpu.ops.pallas import kv_commit
    prompts = _prompts((13, 5) if chunk > 1 else (3, 2))

    def serve(in_place):
        with monkeypatch.context() as mp:
            if not in_place:
                mp.setattr(kv_commit, "commits_columns_in_place", lambda leaf: False)
            sched = _ladder.engine(NAME, slots=3, chunk=chunk, steps=4, fresh=True).scheduler()
            handles = [sched.submit(p, max_new_tokens=6, collect_logits=True) for p in prompts]
            pools = []
            while not all(h.done for h in handles):
                sched.step()
                pools.append(jax.tree_util.tree_map(np.asarray, sched.cache.pool))
            sched.drain()
        return sched, pools, [h.result_logits() for h in handles]

    kernel, pools, logits = serve(True)
    scatter, pools_scatter, logits_scatter = serve(False)
    assert set(kernel.cache.leaf_kinds) == {"state", "columns"}
    assert kernel.kv_commit_programs["scatter"] == 0 < kernel.kv_commit_programs["inplace"]
    assert scatter.kv_commit_programs["inplace"] == 0 < scatter.kv_commit_programs["scatter"]
    assert len(pools) == len(pools_scatter) > 2
    for a, b in zip(pools, pools_scatter):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(x.view(np.uint8), y.view(np.uint8))
    for a, b in zip(logits, logits_scatter):
        assert np.array_equal(a, b)
