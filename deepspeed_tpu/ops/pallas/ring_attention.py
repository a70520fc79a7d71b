"""Ring attention: sequence parallelism for contexts beyond one chip.

The long-context half of the SP story (SURVEY §2.3 first-class requirement;
the v0.9.2 reference's long-sequence surface is block-sparse attention —
``deepspeed/ops/sparse_attention`` — and this framework also ships Ulysses
head-scatter in ``models/transformer._ulysses_specs``). Ulysses re-gathers
the full sequence per head, so VMEM/HBM still see O(T); ring attention keeps
every chip at O(T/n): each chip holds one sequence chunk of Q/K/V, KV chunks
rotate around the ``seq`` ring via ``ppermute`` (ICI neighbor traffic), and
each step's local flash-attention result merges into a running (out, lse)
pair — the online-softmax identity across chips instead of across blocks.

Causal scheduling, two variants behind one API (``schedule=``):

- ``unbalanced``: at ring step ``s`` chip ``i`` holds KV chunk ``i−s`` mod
  ``n``. Step 0 is the causal diagonal; step ``s≥1`` is a full (non-causal)
  block that only chips ``i >= s`` keep — wrapped chunks are future context,
  discarded by an lse=−inf merge, so ~half the non-diagonal block compute is
  wasted.
- ``zigzag`` (default): the global sequence splits into 2n chunks and chip
  ``i`` holds the PAIR (chunk i, chunk 2n−1−i) — one early, one late. At
  every non-diagonal step each chip does exactly one half-block of useful
  work (received-from-behind: full-Q x early-KV-half; received-from-ahead:
  late-Q-half x full-KV), recovering the ~2x causal efficiency. The
  contiguous→zig-zag chunk relayout (and its inverse on the output) runs as
  four ppermutes of half-chunks — O(T/n) neighbor traffic, amortized over
  the n ring steps.

Differentiable end-to-end: the per-step kernel is
``flash_attention_with_lse`` (custom VJP with the lse cotangent folded into
the dq/dkv kernels) and the merge/ppermute are plain JAX; each step is
``jax.checkpoint``-ed so backward recomputes block attention instead of
storing n per-step residuals.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .flash_attention import flash_attention_with_lse

_NEG_INF = -jnp.inf


def _merge(o1, lse1, o2, lse2):
    """Combine two normalized attention results over disjoint KV sets.
    -inf lse means 'attended nothing'; fully guarded against nan grads.
    Returns fp32 — the ring carry stays fp32 so only the final result
    rounds to the model dtype (n-1 intermediate roundings would otherwise
    accumulate in bf16)."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(jnp.minimum(lse1 - m_safe, 0.0)), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(jnp.minimum(lse2 - m_safe, 0.0)), 0.0)
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0, 1.0, denom)
    out = (o1.astype(jnp.float32) * w1[..., None] + o2.astype(jnp.float32) * w2[..., None]) / \
        denom_safe[..., None]
    lse = jnp.where(denom == 0, _NEG_INF, m_safe + jnp.log(denom_safe))
    return out, lse


def ring_attention_local(q, k, v, axis_name="seq", causal=True, block_q=512, block_kv=512,
                         scale=None):
    """Per-chip body — call inside ``shard_map`` with ``axis_name`` bound.

    q: (B, H, Tc, D); k/v: (B, Hkv, Tc, D) — this chip's sequence chunk
    (global position = chip index * Tc + local). Returns (B, H, Tc, D)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Tc, D = q.shape

    def attend(kv, causal_flag):
        kk, vv = kv
        return flash_attention_with_lse(q, kk, vv, causal_flag, block_q, block_kv, scale)

    # step 0: the causal diagonal chunk (fp32 carry; one rounding at the end)
    out0, lse = jax.checkpoint(functools.partial(attend, causal_flag=causal))((k, v))
    out = out0.astype(jnp.float32)

    if n == 1:
        return out.astype(q.dtype)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(s, carry):
        out, lse, kv = carry
        kv = jax.tree_util.tree_map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        o_s, lse_s = jax.checkpoint(functools.partial(attend, causal_flag=False))(kv)
        if causal:
            # chip i now sees chunk (i - s) mod n; wrapped chunks are future
            keep = (idx >= s)[None, None, None]
            lse_s = jnp.where(keep, lse_s, _NEG_INF)
        out, lse = _merge(out, lse, o_s, lse_s)
        return out, lse, kv

    out, lse, _ = jax.lax.fori_loop(1, n, body, (out, lse, (k, v)))
    return out.astype(q.dtype)


# --------------------------------------------------------------------- zigzag
def _zigzag_mapping(n, inverse=False):
    """Half-chunk routing tables: ``mapping[dst_slot]`` is a list of
    ``(src_chip, src_slot, dst_chip)``. Forward: contiguous layout (chip s
    holds chunks 2s, 2s+1) -> zig-zag (chip i holds chunks i, 2n-1-i)."""
    mapping = {0: [], 1: []}
    for i in range(n):
        for dst_slot, chunk in ((0, i), (1, 2 * n - 1 - i)):
            src_chip, src_slot = chunk // 2, chunk % 2
            if inverse:
                # transpose: contiguous chip src_chip slot src_slot receives
                # chunk back from zig-zag chip i slot dst_slot
                mapping[src_slot].append((i, dst_slot, src_chip))
            else:
                mapping[dst_slot].append((src_chip, src_slot, i))
    return mapping


def _permute_halves(halves, mapping, axis_name):
    """Route local half-chunks by the mapping (<=2 ppermutes per dst slot;
    a chip that is no pair's destination receives zeros, so summing the
    slot-wise ppermutes reassembles every destination exactly once)."""
    out = []
    for dst_slot in (0, 1):
        acc = None
        for src_slot in (0, 1):
            pairs = [(sc, dc) for sc, ss, dc in mapping[dst_slot] if ss == src_slot]
            if not pairs:
                continue
            moved = jax.lax.ppermute(halves[src_slot], axis_name, pairs)
            acc = moved if acc is None else acc + moved
        out.append(acc)
    return tuple(out)


def _zigzag_relayout(x, axis_name, n, inverse=False):
    """(B, H, 2c, D) local chunk-pair -> re-routed chunk-pair."""
    c = x.shape[2] // 2
    halves = (x[:, :, :c], x[:, :, c:])
    h0, h1 = _permute_halves(halves, _zigzag_mapping(n, inverse), axis_name)
    return jnp.concatenate([h0, h1], axis=2)


def zigzag_ring_attention_local(q, k, v, axis_name="seq", block_q=512, block_kv=512,
                                scale=None):
    """Per-chip body over the ZIG-ZAG layout: local tensors hold (chunk i,
    chunk 2n-1-i) of the 2n-chunk causal sequence. Every element of the early
    chunk precedes every element of the late chunk, so the local diagonal is
    a plain causal flash call on the concatenation; non-diagonal steps are
    exactly one balanced half-block each (see module docstring)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, T2, D = q.shape
    c = T2 // 2

    def attend(qq, kk, vv, causal_flag):
        return flash_attention_with_lse(qq, kk, vv, causal_flag, block_q, block_kv, scale)

    out0, lse = jax.checkpoint(functools.partial(attend, causal_flag=True))(q, k, v)
    out = out0.astype(jnp.float32)
    if n == 1:
        return out.astype(q.dtype)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def from_behind(kv):
        # kv came from chip j < i: its early chunk precedes BOTH local q
        # chunks; its late chunk follows both. Full Q x early-KV-half.
        kk, vv = kv
        o, l = jax.checkpoint(functools.partial(attend, causal_flag=False))(
            q, kk[:, :, :c], vv[:, :, :c])
        return o.astype(jnp.float32), l

    def from_ahead(kv):
        # kv came from chip j > i: both its chunks sit between local q's
        # early and late chunks. Late-Q-half x full KV; early half attends
        # nothing (lse=-inf so the merge ignores it).
        kk, vv = kv
        o, l = jax.checkpoint(functools.partial(attend, causal_flag=False))(
            q[:, :, c:], kk, vv)
        pad_o = jnp.zeros((B, H, c, D), jnp.float32)
        pad_l = jnp.full((B, H, c), _NEG_INF, l.dtype)
        return (jnp.concatenate([pad_o, o.astype(jnp.float32)], axis=2),
                jnp.concatenate([pad_l, l], axis=2))

    def body(s, carry):
        out, lse, kv = carry
        kv = jax.tree_util.tree_map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        o_s, lse_s = jax.lax.cond(idx >= s, from_behind, from_ahead, kv)
        out, lse = _merge(out, lse, o_s, lse_s)
        return out, lse, kv

    out, lse, _ = jax.lax.fori_loop(1, n, body, (out, lse, (k, v)))
    return out.astype(q.dtype)


def ring_attention(q, k, v, causal=True, block_q=512, block_kv=512, scale=None,
                   schedule="zigzag"):
    """Mesh-level entry: q (B, H, T, D), k/v (B, Hkv, T, D) sequence-sharded
    over the ``seq`` axis, batch over data axes, heads over ``tensor`` (when
    divisible). Runs the ring inside ``shard_map``; falls back to a plain
    flash call on a trivial mesh. ``schedule``: 'zigzag' (balanced causal,
    default) or 'unbalanced'; non-causal attention always uses the plain
    rotation (every block is useful there)."""
    from ...comm import comm as dist

    def local_fn(n_ring, local_t):
        use_zigzag = (schedule == "zigzag" and causal and n_ring > 1
                      and local_t % 2 == 0)

        def fn(q, k, v):
            if use_zigzag:
                q_z = _zigzag_relayout(q, dist.SEQ_AXIS, n_ring)
                k_z = _zigzag_relayout(k, dist.SEQ_AXIS, n_ring)
                v_z = _zigzag_relayout(v, dist.SEQ_AXIS, n_ring)
                out = zigzag_ring_attention_local(q_z, k_z, v_z, dist.SEQ_AXIS,
                                                  block_q, block_kv, scale)
                return _zigzag_relayout(out, dist.SEQ_AXIS, n_ring, inverse=True)
            return ring_attention_local(q, k, v, dist.SEQ_AXIS, causal, block_q, block_kv,
                                        scale)

        return fn

    if dist.in_manual_region():
        # already inside someone's shard_map: run the ring only if the seq
        # axis is actually bound there
        if dist.SEQ_AXIS in dist.get_manual_axes():
            n_ring = dist.get_mesh().shape[dist.SEQ_AXIS] if dist.has_mesh() else 1
            return local_fn(n_ring, q.shape[2])(q, k, v)
        return _dense_fallback(q, k, v, causal, block_q, block_kv, scale)
    if not dist.has_mesh() or dist.get_mesh().shape[dist.SEQ_AXIS] == 1:
        return _dense_fallback(q, k, v, causal, block_q, block_kv, scale)

    mesh = dist.get_mesh()
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    dp_axes, _ = dist.attention_partition_axes(B, H)
    # heads ride the tensor axis so TP shards attention instead of
    # regathering it (the auto partitioner cannot split a pallas_call)
    tdeg = mesh.shape[dist.TENSOR_AXIS]
    head_axis = dist.TENSOR_AXIS if (tdeg > 1 and H % tdeg == 0) else None
    if head_axis and Hkv % tdeg != 0:  # GQA narrower than TP: expand KV heads
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    spec = P(dp_axes or None, head_axis, dist.SEQ_AXIS, None)
    # Full-manual over every mesh axis: axes the spec does not name just see
    # replicated blocks. A partial-manual region (axis_names ⊂ mesh axes)
    # cannot use check_vma=False — None spec entries are then read as
    # replicated-over-ALL-mesh-axes and shard_map rejects the out_specs for
    # every auto axis — and check_vma=True needs vma-annotated out_shapes
    # all the way into the pallas_call, so full-manual is the simple shape.
    axes = set(mesh.axis_names)

    n_ring = mesh.shape[dist.SEQ_AXIS]
    with dist.manual_axes(axes):
        fn = local_fn(n_ring, T // n_ring)
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)


def _dense_fallback(q, k, v, causal, block_q, block_kv, scale):
    from .flash_attention import sharded_flash_attention
    return sharded_flash_attention(q, k, v, causal, block_q, block_kv, scale)
