"""Pallas weight-only-quantized matmul (w8a16).

Serving counterpart of the reference's CUDA dequant+GEMM inference kernels
(``csrc/transformer/inference/csrc/pt_binding.cpp`` int8 ``qkv_gemm``/
``mlp_gemm`` variants and the ``ds_quantizer`` ops): activations stay bf16,
weights stream from HBM as int8 and hit the MXU straight after an
int8->bf16 widen — the bf16 weight matrix never exists in HBM, halving
weight bandwidth (the decode-time bottleneck).

Kernel design (its roofline share is not measured yet: ROADMAP S2):
- The int8 block is converted bf16 in ONE VPU pass (no fp32 round-trip)
  and fed to the MXU; the per-group quantization scale is applied to the
  tiny ``(block_m, block_n)`` fp32 partial sum AFTER the dot — K*N scale
  multiplies become M*N (M is the batch, ~8 at decode).
- Scales load once per n-tile as a ``(G, block_n)`` block reused across
  the k grid, not replicated per k-step.
- ``block_k`` = one quantization group so each k-block sees exactly one
  scale row; ``block_n`` as large as divides N (fewer grid steps keep the
  DMA pipeline fed).

Layout: x (M, K) bf16; qw (K, N) int8; scales (G, N) fp32 with group size
K/G along the contraction dim.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, bk, gsize, ng):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one-pass widen to the activation dtype; MXU does the heavy lifting.
    # A k-block spans ng quantization groups (big DMA blocks at group-level
    # quality): one dot per group, scale applied to the (bm, bn) partial.
    x = x_ref[...]
    w = w_ref[...]
    acc = jnp.zeros_like(acc_ref)
    span = min(gsize, bk)
    for t in range(ng):
        part = jax.lax.dot_general(x[:, t * span:(t + 1) * span],
                                   w[t * span:(t + 1) * span, :].astype(x.dtype),
                                   (((1, ), (0, )), ((), ())),
                                   preferred_element_type=jnp.float32)
        acc += part * s_ref[(k * bk) // gsize + t, :][None, :]
    acc_ref[...] += acc

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pick_block(n, cap, mult=128):
    """Largest multiple of ``mult`` <= cap dividing n, else n itself (Mosaic
    tiling: blocks must tile ``mult``x128 unless they span the whole dim).
    Shared by this kernel's defaults and the model-side callers."""
    if n <= cap:
        return n
    d = cap - cap % mult
    while d >= mult:
        if n % d == 0:
            return d
        d -= mult
    return n


def pick_block_k(K, gsize, cap=1024):
    """Largest multiple of ``gsize`` dividing K under ``cap`` (>=1 group per
    block) — the k-block rule shared by this kernel's default and the fused
    decode blocks."""
    for cand in range(min(K, cap) // gsize * gsize, gsize - 1, -gsize):
        if K % cand == 0:
            return cand
    return gsize


def _qmm_vmem(bm, bn, bk, Gp, gsize, xb, ob):
    """VMEM bytes of one :func:`_qmm_kernel` step: double-buffered blocks,
    the f32 accumulator scratch, the step's local f32 partials and one
    group's widened weight slice."""
    io = 2 * (bm * bk * xb + bk * bn + Gp * bn * 4 + bm * bn * ob)
    return io + 3 * bm * bn * 4 + 2 * min(gsize, bk) * bn * xb


def _pick_bn(N, bm, bk, Gp, gsize, xb, ob):
    """Widest n-block (fewer grid steps keep the DMA pipeline fed) whose
    step fits the VMEM budget: 4096 columns at decode row counts, narrower
    as the row block grows."""
    cap = 4096
    while cap >= 128:
        bn = pick_block(N, cap, 128)
        if _pallas.fits_vmem(_qmm_vmem(bm, bn, bk, Gp, gsize, xb, ob)):
            return bn
        cap //= 2
    raise ValueError(
        f"quant_matmul: a ({bm}, {bk}) x ({bk}, {pick_block(N, 128, 128)}) step "
        f"does not fit the {_pallas.VMEM_BLOCK_BUDGET}-byte VMEM budget; "
        f"pass a smaller block_m")


def quant_matmul(x, qw, scales, block_m=256, block_n=None, block_k=None, out_dtype=None):
    """``x @ dequantize(qw, scales)`` without materializing the bf16 weight.

    x: (M, K); qw: (K, N) int8; scales: (G, N) fp32, G | K. Returns (M, N)
    in ``out_dtype`` (defaults to x.dtype)."""
    M, K = x.shape
    K2, N = qw.shape
    scales = jnp.asarray(scales, jnp.float32)
    if scales.ndim == 3 and scales.shape[1] == 1:
        scales = scales[:, 0, :]  # accept quantize()'s (G, 1, N) directly
    if scales.ndim != 2:
        raise ValueError(f"scales must be (G, N), got shape {scales.shape}")
    G = scales.shape[0]
    if K != K2:
        raise ValueError(f"x K={K} != qw K={K2}")
    if scales.shape[1] != N:
        raise ValueError(f"scales N={scales.shape[1]} != weight N={N}")
    if K % G != 0:
        raise ValueError(f"groups {G} must divide K={K}")
    gsize = K // G
    bm = min(block_m, M)
    if block_k is None:
        if gsize <= 1024:
            # largest multiple of the group size dividing K under ~1MB blocks
            bk = pick_block_k(K, gsize)
        else:
            # huge groups (e.g. G==1): sub-group k-blocks — any divisor of
            # gsize works since consecutive blocks just reuse one scale row
            bk = gsize
            for cand in range(1024 - 1024 % 128, 127, -128):
                if gsize % cand == 0:
                    bk = cand
                    break
    else:
        bk = min(block_k, K)
    if bk % gsize and gsize % bk:
        raise ValueError(f"block_k {bk} must divide or be a multiple of group size {gsize}")
    if K % bk:
        raise ValueError(f"block_k {bk} must divide K={K}")
    ng = max(1, bk // gsize)
    out_dtype = out_dtype or x.dtype
    Gpad = -(-G // 8) * 8
    bn = block_n or _pick_bn(N, bm, bk, Gpad, gsize, x.dtype.itemsize,
                             jnp.dtype(out_dtype).itemsize)
    if M % bm or N % bn:
        raise ValueError(f"shape ({M},{K})x({K},{N}) not divisible by blocks ({bm},{bk},{bn})")
    nk = K // bk
    if Gpad != G:  # Mosaic block sublanes must be a multiple of 8
        scales = jnp.pad(scales, ((0, Gpad - G), (0, 0)))

    return pl.pallas_call(
        functools.partial(_qmm_kernel, nk=nk, bk=bk, gsize=gsize, ng=ng),
        name="dstpu_quant_matmul",
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((Gpad, bn), lambda i, j, k: (0, j)),  # revisited, one DMA per j
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
    )(x, qw, scales)
