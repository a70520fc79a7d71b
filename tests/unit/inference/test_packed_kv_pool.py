"""The packed K/V pool (head size 64): K and V of a layer side by side in
one 128-lane leaf, ``CausalLMModel.init_cache``'s rule (``kv_packs``).

The rule and ``init_cache``'s geometries; the packed kernels against the
split ones, bit for bit; a model step's logits and pool bytes packed
against split; what the scheduler reports; and rows of another geometry
refused. ``test_packed_kv_serving.py`` and ``test_packed_kv_features.py``
hold a scheduler over the packed pool to one over split leaves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import decode_attention as da

from ._packed_kv import FILLERS, HD, LONG, engine, fresh_process_state, split_rule


def _pack(k, v):
    return jnp.concatenate([k, v], axis=-1)


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("hd, packed", [(64, True), (80, False), (96, False), (128, False),
                                        (192, True), (256, False)])
def test_kv_packs_rule(hd, packed):
    assert tfm.kv_packs(hd) == packed


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("hd", [64, 80, 128])
def test_init_cache_geometry(hd, scan, quantized):
    """``init_cache`` at head sizes 64, 80 and 128: one packed leaf a layer
    at 64, a K and a V leaf otherwise; the int8 tier's scale leaf last; the
    slot axis at ``ndim - 4`` and the row axis at ``ndim - 2`` throughout;
    the same bytes a position either way."""
    model = get_model("tiny", head_dim=hd, scan_layers=scan)
    cfg = model.cfg
    tree = jax.eval_shape(lambda: model.init_cache(3, 32, quantized=quantized))
    lead = (cfg.num_layers, ) if scan else ()
    kv_dtype = jnp.int8 if quantized else cfg.dtype
    lanes = 2 * hd if hd == 64 else hd
    n_kv = 1 if hd == 64 else 2
    assert tfm.kv_pool_geometry(cfg, tree) == ("packed" if hd == 64 else "split")
    assert len(tree) == n_kv + quantized
    for comp in tree[:n_kv]:
        leaves = [comp] if scan else list(comp)
        assert len(leaves) == (1 if scan else cfg.num_layers)
        assert all(x.shape == lead + (3, cfg.kv_heads, 32, lanes) and x.dtype == kv_dtype
                   for x in leaves)
    if quantized:
        scales = [tree[-1]] if scan else list(tree[-1])
        assert all(x.shape == lead + (3, 1, 32, 1) and x.dtype == jnp.float16 for x in scales)
    values = sum(x.size for x in jax.tree_util.tree_leaves(tree[:n_kv])) // (3 * 32)
    assert values == cfg.num_layers * 2 * cfg.kv_heads * hd
    # one layer's leaves, as every reader takes them apart
    layer = tuple(jax.ShapeDtypeStruct(comp.shape[1:], comp.dtype) if scan else comp[0]
                  for comp in tree)
    kv, scale, split = tfm.kv_layer_leaves(cfg, layer)
    assert len(kv) == n_kv and (scale is not None) == quantized
    assert split == (hd if hd == 64 else 0)


def test_latent_geometry_is_named():
    model = get_model("tiny-mla-moe")
    tree = jax.eval_shape(lambda: model.init_cache(2, 16))
    assert tfm.kv_pool_geometry(model.cfg, tree) == "latent"


def test_layer_leaves_refuses_a_foreign_width():
    cfg = get_model("tiny", head_dim=64).cfg
    wrong = jax.ShapeDtypeStruct((2, cfg.kv_heads, 16, 96), jnp.float32)
    with pytest.raises(ValueError, match="neither the split nor the packed"):
        tfm.kv_layer_leaves(cfg, (wrong, wrong))


# ------------------------------------------------------------------- kernels
def _cache(dtype, B, nkv, S, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    if dtype == jnp.int8:
        k = jax.random.randint(ks[0], (B, nkv, S, HD), -127, 128, jnp.int8)
        v = jax.random.randint(ks[1], (B, nkv, S, HD), -127, 128, jnp.int8)
        scale = (jax.random.uniform(ks[2], (B, 1, S, 1)) * 0.05 + 0.01).astype(jnp.float16)
        return k, v, scale
    return (jax.random.normal(ks[0], (B, nkv, S, HD), dtype),
            jax.random.normal(ks[1], (B, nkv, S, HD), dtype), None)


@pytest.mark.parametrize("operands", ["plain", "extents", "lossy"])
@pytest.mark.parametrize("span", [0, 1, 64], ids=["decode", "span1", "span64"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.float32],
                         ids=["bf16", "int8", "f32"])
def test_packed_attention_equals_split(dtype, span, operands):
    """``paged_decode_attention`` (``span`` 0) and ``paged_span_attention``
    over the packed leaf against the K and V leaves it joins: the same
    bits, with a row whose window is empty, one of a single key, one that
    ends inside a KV block and (spans) columns that straddle blocks; int8
    with its scale leaf; an extent table; sink and window."""
    B, H, nkv, S = 4, 4, 2, 128
    k, v, scale = _cache(dtype, B + 1, nkv, S, seed=span + 3)
    q_shape = (B, H, HD) if span == 0 else (B, H, span, HD)
    q = jax.random.normal(jax.random.key(9), q_shape, jnp.float32).astype(
        jnp.bfloat16 if dtype == jnp.int8 else dtype)
    start = jnp.asarray([0, 3, 0, 0], jnp.int32)
    heads = jnp.asarray([0, 17, 63, 100], jnp.int32)  # write heads / one-past ends
    kw = {"block_kv": 32, "k_scale": scale, "v_scale": scale}
    if operands == "extents":
        # two extents a row over the five pool rows, one left unreserved
        kw["ext"] = jnp.asarray([[2, 4], [0, -1], [3, 1], [1, 0]], jnp.int32)
        heads = jnp.asarray([140, 17, 200, 100], jnp.int32)
    elif operands == "lossy":
        kw["sink"] = jnp.asarray([0, 2, 4, 4], jnp.int32)
        kw["window"] = jnp.asarray([0, 8, 0, 40], jnp.int32)
    else:
        k, v, scale = k[:B], v[:B], None if scale is None else scale[:B]
        kw.update(k_scale=scale, v_scale=scale)
    fn = da.paged_decode_attention if span == 0 else da.paged_span_attention
    want = fn(q, k, v, start, heads, **kw)
    got = fn(q, _pack(k, v), None, start, heads, **kw)
    assert got.shape == want.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()


def test_packed_dense_decode_and_block_choice():
    """The static-batch entry point takes the packed leaf too, and the
    block picker counts ONE lane-dense KV operand where the split form has
    two half-empty ones: never fewer heads a step."""
    k, v, _ = _cache(jnp.bfloat16, 2, 2, 64, seed=1)
    q = jax.random.normal(jax.random.key(2), (2, 4, HD), jnp.bfloat16)
    start = jnp.asarray([0, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(da.decode_attention(q, _pack(k, v), None, start, 40, block_kv=16).astype(jnp.float32)),
        np.asarray(da.decode_attention(q, k, v, start, 40, block_kv=16).astype(jnp.float32)))
    for g in (1, 64):
        args = (20, g, 64, 1024, 256, jnp.bfloat16, jnp.bfloat16, False)
        assert da._vmem_estimate(20, 256, g, 64, 2, 2, False, True) < da._vmem_estimate(
            20, 256, g, 64, 2, 2, False, False)
        assert da._pick_blocks(*args, True)[0] >= da._pick_blocks(*args, False)[0]
    with pytest.raises(ValueError, match="do not hold heads of width"):
        da.paged_decode_attention(q, k, None, start, start + 1)  # a split leaf is not packed


# ---------------------------------------------------------------- model step
def _as_split(tree):
    """A packed cache tree as the split tree of the same bytes."""
    kv = tree[0]
    k = jax.tree_util.tree_map(lambda x: x[..., :HD], kv)
    v = jax.tree_util.tree_map(lambda x: x[..., HD:], kv)
    return (k, v) + tuple(tree[1:])


@pytest.mark.parametrize("path", ["xla", "flash", "flash_int8kv", "scan_xla"])
def test_model_step_packed_equals_split(path):
    """``apply_with_cache`` over a packed pool against the split pool of the
    same bytes: a chunk's span write and attention, then a one-column
    decode step; logits bit for bit, and the packed pool holds the split
    pool's K and V side by side (the XLA fallback, the paged kernels with
    the in-place commit, the int8 tier, stacked layers)."""
    model = get_model("tiny", head_dim=HD, dtype=jnp.float32,
                      attention_impl="flash" if path.startswith("flash") else "xla",
                      scan_layers=path == "scan_xla")
    params = model.init_params(jax.random.key(0))
    quantized = path.endswith("int8kv")
    N, C, S = 3, 16, 64
    packed = model.init_cache(N, S, quantized=quantized)
    assert tfm.kv_pool_geometry(model.cfg, packed) == "packed"
    ids = jax.random.randint(jax.random.key(1), (N, C), 0, 256)
    lengths = jnp.asarray([0, 5, 40], jnp.int32)
    spans = jnp.asarray([C, 1, 0], jnp.int32)

    def step(pool, ids, lengths, spans):
        pos = lengths[:, None] + jnp.arange(ids.shape[1])[None, :]
        return model.apply_with_cache(params, ids, pool, 0, position_ids=pos,
                                      write_index=lengths, q_spans=spans)

    lg_p, pool_p = step(packed, ids, lengths, spans)
    lg_s, pool_s = step(_as_split(packed), ids, lengths, spans)
    live = np.asarray([[j < s for j in range(C)] for s in np.asarray(spans)])
    np.testing.assert_array_equal(np.asarray(lg_p)[live], np.asarray(lg_s)[live])
    lg_p2, pool_p = step(pool_p, ids[:, :1], lengths + spans, jnp.asarray([1, 1, 0], jnp.int32))
    lg_s2, pool_s = step(pool_s, ids[:, :1], lengths + spans, jnp.asarray([1, 1, 0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_p2)[:2], np.asarray(lg_s2)[:2])
    for a, b in zip(jax.tree_util.tree_leaves(_as_split(pool_p)),
                    jax.tree_util.tree_leaves(pool_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(jax.tree_util.tree_leaves(pool_p)[0].astype(jnp.float32)).max()) > 0


# ----------------------------------------------------------------- scheduler
@pytest.mark.parametrize("model, kw, geometry", [
    ("tiny", {"head_dim": 64}, "packed"),       # cell 2's head size
    ("tiny", {"head_dim": 128}, "split"),
    ("tiny", {}, "split"),                      # 16-wide heads: dense in neither form
    ("tiny-mla-moe", {}, "latent"),             # cell 4's pool
])
def test_scheduler_reports_its_pool_geometry(tmp_path, model, kw, geometry):
    """``kv_pool_geometry`` on the scheduler, in the gateway's scheduler
    stats beside ``kv_commit_programs``, and as the ``serving/kv_pool_packed``
    gauge of the sink."""
    import json
    fresh_process_state()
    eng = deepspeed_tpu.init_inference(get_model(model, **kw), config={
        "dtype": "float32", "max_out_tokens": 128,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)},
        "continuous_batching": {"enabled": True, "num_slots": 2, "prefill_chunk": 16}})
    sched = eng.scheduler()
    assert sched.kv_pool_geometry == geometry
    from deepspeed_tpu.serving.gateway import Gateway
    stats = Gateway(eng, port=0)._metrics()["scheduler"]  # never started: nothing to close
    assert stats["kv_pool_geometry"] == geometry and "kv_commit_programs" in stats
    eng.telemetry.flush()
    fresh_process_state()
    events = [json.loads(line) for line in open(tmp_path / "telemetry.jsonl")]
    gauge = [e for e in events if e.get("name") == "serving/kv_pool_packed"]
    assert gauge and gauge[-1]["value"] == int(geometry == "packed")


# ------------------------------------------------------------- foreign rows
def test_split_form_rows_are_refused_by_a_packed_pool(monkeypatch):
    """A prefix-store entry (or a migrated slot) written by a split pool is
    refused by the packed pool's shape check, not read as packed: same
    store, same prompt, other geometry."""
    from deepspeed_tpu.memory.prefix_store import GlobalPrefixStore
    store = GlobalPrefixStore(capacity_bytes=1 << 26)
    with monkeypatch.context() as m:
        split_rule(m)
        old = engine(hierarchical_kv={"enabled": True}).scheduler(
            num_slots=2, prefill_chunk=16, prefix_store=store)
        assert old.kv_pool_geometry == "split"
        old.submit(LONG, max_new_tokens=4).result()
        for f in FILLERS:
            old.submit(f, max_new_tokens=4).result()
        assert store.stats()["entries"] >= 1
    new = engine(hierarchical_kv={"enabled": True}).scheduler(
        num_slots=2, prefill_chunk=16, prefix_store=store)
    assert new.kv_pool_geometry == "packed"
    with pytest.raises(ValueError, match="do not have this pool's geometry"):
        new.submit(LONG, max_new_tokens=4).result()
