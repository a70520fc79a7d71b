"""Pallas block-sparse flash attention.

TPU-native replacement for the reference's Triton block-sparse matmuls
(``deepspeed/ops/sparse_attention/matmul.py`` sdd/dsd/dds +
``softmax.py``): instead of three sparse matmul kernels with a separate
sparse softmax, one flash-style kernel streams only the *active* KV blocks
of each query block row (online softmax, fp32 accumulators, bf16 MXU
operands), and the backward follows the same two-kernel (dq; dkv) split as
the dense flash kernel in ``ops/pallas/flash_attention.py``.

The layout is a compile-time constant: per (head, q-block) the active
kv-block indices are baked into small int32 index tables; each distinct
layout therefore compiles its own kernel (same trade the reference makes —
its Triton kernels JIT per layout too).

Compute cost scales with the number of active blocks, so a sliding-window
layout turns O(T^2) attention into O(T·w) — the long-context story this
subsystem exists for.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _index_tables(layout):
    """(H, nq, nk) 0/1 -> per-row and per-column active index tables.

    Returns (q_idx (H,nq,K), q_cnt (H,nq), kv_idx (H,nk,Kt), kv_cnt (H,nk));
    padding entries repeat index 0 but are never visited (count-bounded
    loops)."""
    H, nq, nk = layout.shape
    q_cnt = layout.sum(-1).astype(np.int32)
    kv_cnt = layout.sum(-2).astype(np.int32)
    K = max(1, int(q_cnt.max()))
    Kt = max(1, int(kv_cnt.max()))
    q_idx = np.zeros((H, nq, K), np.int32)
    kv_idx = np.zeros((H, nk, Kt), np.int32)
    for h in range(H):
        for i in range(nq):
            act = np.nonzero(layout[h, i])[0]
            q_idx[h, i, :len(act)] = act
        for j in range(nk):
            act = np.nonzero(layout[h, :, j])[0]
            kv_idx[h, j, :len(act)] = act
    return q_idx, q_cnt, kv_idx, kv_cnt


def _fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block,
                causal, seq_len):
    d = q_ref.shape[-1]
    h = pl.program_id(1)
    qi = pl.program_id(2)
    q_start = qi * block
    q = q_ref[0, 0]

    iq = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ikq = ik - iq

    def body(j, carry):
        m, l, acc = carry
        kv_start = pl.multiple_of(idx_ref[h, qi, j] * block, block)
        k = k_ref[0, 0, pl.ds(kv_start, block), :]
        v = v_ref[0, 0, pl.ds(kv_start, block), :]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = ik < seq_len - kv_start
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero under the mask: a row whose every visited entry is
        # masked (causal row with only future blocks) must yield p=0 -> l=0
        # -> zero output, not exp(0)=1 against the mask sentinel
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                                preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((block, 1), -jnp.inf, jnp.float32), jnp.zeros((block, 1), jnp.float32),
            jnp.zeros((block, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, cnt_ref[h, qi], body, init)
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l == 0, -jnp.inf, m + jnp.log(l_safe))


def _bwd_dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, block, causal, seq_len):
    d = q_ref.shape[-1]
    h = pl.program_id(1)
    qi = pl.program_id(2)
    q_start = qi * block
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    iq = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ikq = ik - iq

    def body(j, dq):
        kv_start = pl.multiple_of(idx_ref[h, qi, j] * block, block)
        k = k_ref[0, 0, pl.ds(kv_start, block), :]
        v = v_ref[0, 0, pl.ds(kv_start, block), :]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = ik < seq_len - kv_start
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1, ), (0, )), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, cnt_ref[h, qi], body, jnp.zeros((block, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, block, causal, seq_len):
    d = k_ref.shape[-1]
    h = pl.program_id(1)
    ki = pl.program_id(2)
    kv_start = ki * block
    k = k_ref[0, 0, pl.ds(kv_start, block), :]
    v = v_ref[0, 0, pl.ds(kv_start, block), :]

    iq = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ikq = ik - iq

    def body(n, carry):
        dk, dv = carry
        q_start = pl.multiple_of(idx_ref[h, ki, n] * block, block)
        q = q_ref[0, 0, pl.ds(q_start, block), :]
        do = do_ref[0, 0, pl.ds(q_start, block), :]
        lse = lse_ref[0, 0, pl.ds(q_start, block), :]
        delta = delta_ref[0, 0, pl.ds(q_start, block), :]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (ik < seq_len - kv_start) & (iq < seq_len - q_start)
        if causal:
            mask = mask & (ikq <= q_start - kv_start)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do, (((0, ), (0, )), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0, ), (0, )), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    zero = jnp.zeros((block, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, cnt_ref[h, ki], body, (zero, zero))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def make_block_sparse_attention(layout, block, causal=True, scale=None):
    """Build an attention fn specialized to a static block ``layout``.

    ``layout``: numpy (H, nq_blocks, nkv_blocks) 0/1. Returns
    ``fn(q, k, v) -> out`` for q/k/v of shape (B, H, T, D) with
    T <= nq_blocks*block (the tail is padded internally). Differentiable
    (custom VJP, same two-kernel split as the dense flash kernel)."""
    layout = np.asarray(layout)
    if layout.ndim != 3:
        raise ValueError(f"layout must be (H, nq, nk), got {layout.shape}")
    q_idx_np, q_cnt_np, kv_idx_np, kv_cnt_np = _index_tables(layout)
    H, nq, nk = layout.shape

    q_idx = jnp.asarray(q_idx_np)
    q_cnt = jnp.asarray(q_cnt_np)  # (H, nq)
    kv_idx = jnp.asarray(kv_idx_np)
    kv_cnt = jnp.asarray(kv_cnt_np)

    def _pad(x, n_blocks):
        t = x.shape[2]
        pad = n_blocks * block - t
        if pad < 0:
            raise ValueError(f"sequence {t} exceeds layout capacity {n_blocks * block}")
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x

    @jax.custom_vjp
    def attend(q, k, v):
        out, _ = attend_fwd(q, k, v)
        return out

    def _call_fwd(q, k, v):
        B, Hq, T, D = q.shape
        if Hq != H:
            raise ValueError(f"layout built for {H} heads, got {Hq}")
        sc = scale if scale is not None else 1.0 / (D**0.5)
        qp, kp, vp = _pad(q, nq), _pad(k, nk), _pad(v, nk)
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=sc, block=block, causal=causal, seq_len=T),
            grid=(B, H, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, i: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, nq * block, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, nq * block, 1), jnp.float32),
            ],
            interpret=_pallas.interpret(),
        )(q_idx, q_cnt, qp, kp, vp)
        return out, lse, (qp, kp, vp)

    def attend_fwd(q, k, v):
        T = q.shape[2]
        out_p, lse, (qp, kp, vp) = _call_fwd(q, k, v)
        return out_p[:, :, :T], (qp, kp, vp, out_p, lse, T)

    def attend_bwd(res, g):
        qp, kp, vp, out_p, lse, T = res
        B, _, Tq, D = qp.shape
        sc = scale if scale is not None else 1.0 / (D**0.5)
        dop = jnp.pad(g, ((0, 0), (0, 0), (0, Tq - T), (0, 0))) if Tq != T else g
        delta = jnp.einsum("bhtd,bhtd->bht", dop.astype(jnp.float32),
                           out_p.astype(jnp.float32))[..., None]
        lse_f = jnp.where(jnp.isfinite(lse), lse, 0.0)  # empty rows: p stays 0 via mask

        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=sc, block=block, causal=causal, seq_len=T),
            grid=(B, H, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct(qp.shape, qp.dtype),
            interpret=_pallas.interpret(),
        )(q_idx, q_cnt, qp, kp, vp, dop, lse_f, delta)

        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=sc, block=block, causal=causal, seq_len=T),
            grid=(B, H, nk),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, nq * block, D), lambda b, h, j: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, j: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nk * block, D), lambda b, h, j: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nq * block, D), lambda b, h, j: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nq * block, 1), lambda b, h, j: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, nq * block, 1), lambda b, h, j: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block, D), lambda b, h, j: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block, D), lambda b, h, j: (b, h, j, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                       jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
            interpret=_pallas.interpret(),
        )(kv_idx, kv_cnt, qp, kp, vp, dop, lse_f, delta)
        return dq[:, :, :T], dk[:, :, :T], dv[:, :, :T]

    attend.defvjp(attend_fwd, attend_bwd)
    return attend
