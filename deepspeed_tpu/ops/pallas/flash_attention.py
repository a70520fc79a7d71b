"""Pallas flash attention (TPU).

TPU-native replacement for the reference's fused CUDA attention kernels
(training: ``csrc/transformer/softmax_kernels.cu`` + strided-batch-GEMM
attention in ``csrc/transformer/ds_transformer_cuda.cpp``; the Triton
block-sparse path in ``deepspeed/ops/sparse_attention/matmul.py``).

FlashAttention-2-style online softmax: O(T) memory, fp32 accumulators in
VMEM, bf16 MXU matmuls — operands stay in the input dtype (bf16) and every
``dot_general`` accumulates in fp32 via ``preferred_element_type``; softmax
probabilities are cast back to the operand dtype before the P·V / Pᵀ·dO
matmuls (the MXU contracts bf16×bf16→fp32 natively; an fp32 operand path
would run at ~1/4 rate). Operates natively on the model's ``(B, H, T, D)``
("bhtd") layout — blocks are carved by BlockSpec index maps over the
sequence dim, so no transposes/copies appear around the kernel (those
copies cost ~7% of a train step in the packed ``(B*H, T, D)`` formulation
this replaces; the model computes attention in bhtd end-to-end).

Grouped-query attention is native: K/V keep their ``kv_heads`` dimension and
the index maps point query head ``h`` at KV head ``h // group``; nothing is
repeated in HBM. The backward dk/dv kernel accumulates per *query* head and
the group-sum is folded outside (a cheap reduce over the group dim).

K/V for one (batch, head) program live in VMEM — ~2·T·D·2 bytes, which fits
tens-of-k tokens at D=64..128; beyond that, sequence parallelism (ring /
Ulysses over the ``seq`` axis) splits T across chips before the kernel runs.

Backward follows the standard two-kernel split (dq; dkv) with the saved
softmax log-sum-exp and delta = rowsum(dO * O).

Kernels run interpreted on CPU (tests) and compiled on TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .. import pallas as _pallas

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_kv, causal, q_len,
                kv_len):
    """Grid: (B, H, num_q_blocks). Blocks: q/o (1, 1, bq, D);
    k/v (1, 1, Tkv, D) — the full (padded) KV head in VMEM; lse (1, 1, bq)."""
    block_q = q_ref.shape[2]
    d = q_ref.shape[-1]
    qi = pl.program_id(2)
    q_start = qi * block_q

    q = q_ref[0, 0]  # (bq, D) operand dtype; accumulation is fp32

    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    num_kv = pl.cdiv(k_ref.shape[2], block_kv)
    if causal:
        num_kv_eff = jax.lax.min(num_kv, pl.cdiv(q_start + block_q, block_kv))
    else:
        num_kv_eff = num_kv
    # loop-invariant local iotas: mask = (ik - iq) <= q_start - kv_start —
    # one scalar-broadcast compare per iteration instead of two iota adds
    iq = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    ikq = ik - iq

    def body(j, carry):
        m, l, acc = carry
        kv_start = j * block_kv
        k = k_ref[0, 0, pl.ds(kv_start, block_kv), :]
        v = v_ref[0, 0, pl.ds(kv_start, block_kv), :]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq, bkv)

        mask = ik < kv_len - kv_start
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # rows whose every visited entry is masked exist only when the
        # sequence is padded (causal rows always see the diagonal): only then
        # pay for the explicit zero that yields l=0 -> zero output, -inf lse
        # (otherwise exp(MASK - m_new) underflows to 0 on its own)
        if kv_len % block_kv or q_len % block_q:
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        else:
            p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                                preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, num_kv_eff, body, (m, l, acc))

    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l == 0, -jnp.inf, m + jnp.log(l_safe))  # (bq, 1)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_kv, causal,
                   kv_len):
    block_q = q_ref.shape[2]
    d = q_ref.shape[-1]
    qi = pl.program_id(2)
    q_start = qi * block_q

    q = q_ref[0, 0]
    do = do_ref[0, 0]
    # -inf marks attended-nothing (padding) rows; neutralize so exp(s - lse)
    # stays finite — their dq is sliced away / masked out downstream
    lse = jnp.where(jnp.isfinite(lse_ref[0, 0]), lse_ref[0, 0], 0.0)  # (bq, 1)
    delta = delta_ref[0, 0]  # (bq, 1)

    num_kv = pl.cdiv(k_ref.shape[2], block_kv)
    num_kv_eff = jax.lax.min(num_kv, pl.cdiv(q_start + block_q, block_kv)) if causal else num_kv
    iq = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    ikq = ik - iq

    def body(j, dq):
        kv_start = j * block_kv
        k = k_ref[0, 0, pl.ds(kv_start, block_kv), :]
        v = v_ref[0, 0, pl.ds(kv_start, block_kv), :]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = ik < kv_len - kv_start
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())), preferred_element_type=jnp.float32)
        # fold the softmax scale into ds before the bf16 cast (dq = scale·dsᵀk)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1, ), (0, )), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kv_eff, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, block_q,
                    causal, q_len, kv_len):
    """Grid: (B, H, num_kv_blocks). k/v blocks (1, 1, bkv, D) come from the
    (possibly grouped) KV head for query head h; dk/dv are written per
    *query* head (into (B, H, Tkv, D)) and group-summed by the caller."""
    block_kv = k_ref.shape[2]
    d = k_ref.shape[-1]
    ki = pl.program_id(2)
    kv_start = ki * block_kv

    k = k_ref[0, 0]
    v = v_ref[0, 0]

    num_q = pl.cdiv(q_ref.shape[2], block_q)
    start_q = (kv_start // block_q) if causal else 0

    iq = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    ikq = ik - iq

    def body(i, carry):
        dk, dv = carry
        q_start = i * block_q
        q = q_ref[0, 0, pl.ds(q_start, block_q), :]
        do = do_ref[0, 0, pl.ds(q_start, block_q), :]
        lse_raw = lse_ref[0, 0, pl.ds(q_start, block_q), :]  # (bq, 1)
        lse = jnp.where(jnp.isfinite(lse_raw), lse_raw, 0.0)
        delta = delta_ref[0, 0, pl.ds(q_start, block_q), :]  # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (ik < kv_len - kv_start) & (iq < q_len - q_start)
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        pb = p.astype(do.dtype)

        dv = dv + jax.lax.dot_general(pb, do, (((0, ), (0, )), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                  preferred_element_type=jnp.float32)
        # scale folds into ds (dk = scale·dsᵀq), matching the fwd s-scaling
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0, ), (0, )), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    zero = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, (zero, zero))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _pad_seq(x, block):
    t = x.shape[2]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """q: (B, H, T, D); k/v: (B, Hkv, T, D) with H divisible by Hkv (GQA
    native — no pre-expansion). Returns (B, H, T, D)."""
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_kv, scale)
    return out


def _flash_call(q, k, v, causal, block_q, block_kv, scale):
    B, H, T, D = q.shape
    Hkv, T_kv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, f"query heads {H} not a multiple of kv heads {Hkv}"
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D**0.5)
    block_q = min(block_q, T)
    block_kv = min(block_kv, T_kv)

    qp = _pad_seq(q, block_q)
    kp = _pad_seq(k, block_kv)
    vp = _pad_seq(v, block_kv)
    Tq, Tkv = qp.shape[2], kp.shape[2]
    grid = (B, H, Tq // block_q)

    kernel = functools.partial(_fwd_kernel, scale=scale, block_kv=block_kv, causal=causal,
                               q_len=T, kv_len=T_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        interpret=_pallas.interpret(),
    )(qp, kp, vp)
    return out, lse, (qp, kp, vp, Tq, Tkv)


def _flash_fwd(q, k, v, causal, block_q, block_kv, scale):
    from jax.ad_checkpoint import checkpoint_name
    T = q.shape[2]
    out_p, lse, (qp, kp, vp, Tq, Tkv) = _flash_call(q, k, v, causal, block_q, block_kv, scale)
    # name the kernel outputs so a remat policy can pin them: re-running the
    # forward kernel inside backward costs ~6% of step time under plain
    # dots_saveable (the custom-call is not a "dot"). Pair with
    # jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    # (models.transformer exposes it as policy "dots_and_attn_saveable").
    out_p = checkpoint_name(out_p, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # residuals keep the UNPADDED operands: backward re-pads (cheap) and the
    # logical q/kv lengths stay statically derivable from the shapes
    return out_p[:, :, :T], (q, k, v, out_p, lse)


def _flash_bwd(causal, block_q, block_kv, scale, res, g_out):
    return _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out)


def _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out, delta_shift=None):
    q, k, v, out_p, lse = res
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    grp = H // Hkv
    T_kv_logical = k.shape[2]
    scale_v = scale if scale is not None else 1.0 / (D**0.5)
    bq = min(block_q, T)
    bkv = min(block_kv, T_kv_logical)
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bkv)
    vp = _pad_seq(v, bkv)
    Tq, Tkv = qp.shape[2], kp.shape[2]

    dop = jnp.pad(g_out, ((0, 0), (0, 0), (0, Tq - T), (0, 0))) if Tq != T else g_out

    delta = jnp.einsum("bhtd,bhtd->bht", dop.astype(jnp.float32),
                       out_p.astype(jnp.float32))[..., None]  # (B, H, Tq, 1)
    if delta_shift is not None:
        delta = delta - delta_shift.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale_v, block_kv=bkv, causal=causal,
                          kv_len=T_kv_logical),
        grid=(B, H, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // grp, 0, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // grp, 0, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), qp.dtype),
        interpret=_pallas.interpret(),
    )(qp, kp, vp, dop, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale_v, block_q=bq, causal=causal,
                          q_len=T, kv_len=T_kv_logical),
        grid=(B, H, Tkv // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, Tq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h // grp, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h // grp, j, 0)),
            pl.BlockSpec((1, 1, Tq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tq, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tq, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tkv, D), kp.dtype),
            jax.ShapeDtypeStruct((B, H, Tkv, D), vp.dtype),
        ],
        interpret=_pallas.interpret(),
    )(qp, kp, vp, dop, lse, delta)

    if grp > 1:  # group-sum per-query-head dk/dv back onto the shared KV head
        dk = dk.reshape(B, Hkv, grp, Tkv, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, grp, Tkv, D).sum(axis=2)
    return dq[:, :, :T], dk[:, :, :T_kv_logical], dv[:, :, :T_kv_logical]


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """Flash attention that also returns the per-row log-sum-exp —
    the merge currency of ring attention (``ops/pallas/ring_attention.py``):
    two attention results over disjoint KV sets combine exactly from their
    (out, lse) pairs. lse shape (B, H, T); rows that attend nothing are -inf.
    """
    out, lse = _flash_lse_fwd(q, k, v, causal, block_q, block_kv, scale)[0]
    return out, lse


def _flash_lse_fwd(q, k, v, causal, block_q, block_kv, scale):
    T = q.shape[2]
    out_p, lse, (qp, kp, vp, Tq, Tkv) = _flash_call(q, k, v, causal, block_q, block_kv, scale)
    return (out_p[:, :, :T], lse[:, :, :T, 0]), (q, k, v, out_p, lse)


def _flash_lse_bwd(causal, block_q, block_kv, scale, res, g):
    """The lse cotangent folds into the existing dq/dkv kernels: with
    s-gradient ds = p∘(dp − delta), and dlse/ds = p, the combined cotangent
    is ds = p∘(dp − (delta − g_lse)) — so shifting delta by −g_lse reuses
    both kernels unchanged."""
    g_out, g_lse = g
    out_p = res[3]
    T = g_out.shape[2]
    Tq = out_p.shape[2]
    g_lse_p = jnp.pad(g_lse, ((0, 0), (0, 0), (0, Tq - T))) if Tq != T else g_lse
    return _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out,
                           delta_shift=g_lse_p[..., None])


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def sharded_flash_attention(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """Mesh-aware flash attention: q (B, H, T, D), k/v (B, Hkv, T, D) with
    full (or head-gathered) sequence per shard.

    A ``pallas_call`` cannot be split by the automatic SPMD partitioner, so on
    a non-trivial mesh the kernel runs inside ``shard_map``: batch over the
    data axes and heads over (seq, tensor) — the head-parallel placement
    Ulysses-style sequence parallelism hands us (DeepSpeed-Ulysses; the
    v0.9.2 reference's long-sequence surface is block-sparse attention,
    ``deepspeed/ops/sparse_attention/``). Falls back to a direct call on a
    trivial mesh or inside an enclosing manual region. When the KV head count
    doesn't divide the head-axis degree, KV is expanded to full heads first —
    every shard_map input must be sharded (a replicated input's cotangent
    would need a psum that check_vma=False disables).
    """
    from ...comm import comm as dist

    if not dist.has_mesh() or dist.in_manual_region():
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)
    mesh = dist.get_mesh()
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    dp_axes, head_axes = dist.attention_partition_axes(B, H)
    if not dp_axes and not head_axes:
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)

    head_degree = int(np.prod([mesh.shape[a] for a in head_axes])) if head_axes else 1
    qspec = P(dp_axes or None, head_axes or None, None, None)
    if head_degree > 1 and Hkv % head_degree != 0:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    kvspec = qspec

    def fn(q, k, v):  # positional: custom_vjp rejects kwargs
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)

    # Mosaic refuses a kernel under any automatic axis, so the size-1 axes go
    # manual too (trivially: nothing is split over them). An unused axis of
    # size > 1 stays automatic — a manual axis the specs don't mention would
    # have its cotangents psum'd (check_vma=False) — and the chip's compiler
    # then refuses the layout by name.
    manual = (set(dp_axes) | set(head_axes)
              | {a for a in mesh.axis_names if mesh.shape[a] == 1})
    with dist.manual_axes(manual):
        # replication checking off: pallas_call out_shapes carry no vma
        # annotations
        return jax.shard_map(fn, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                             out_specs=qspec, check_vma=False,
                             axis_names=manual)(q, k, v)
