"""Microbench: w8a16 matmul variants at decode shapes on the real chip.

Decode is weight-streaming bound; this sweeps implementations of
``x(8,1280) @ W(1280,5120)`` over 36 stacked layers (one full "model pass"
of 236 MB bf16 / 118 MB int8) so HBM must stream every rep. Timing
amortizes the fixed per-call cost (dispatch + one value fetch): R in-jit
reps per call, one value fetch at the end.

Run: python benchmarks/qmm_microbench.py [variant ...]
"""
import functools
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

L, M, K, N = 36, 8, 1280, 5120
GSIZE = 128
G = K // GSIZE
R = 64  # in-jit reps


def make_data(rng):
    w = rng.standard_normal((L, K, N), np.float32).astype(np.float32) * 0.02
    x = rng.standard_normal((M, K), np.float32) * 0.1
    # group quantize along K
    wg = w.reshape(L, G, GSIZE, N)
    scale = np.abs(wg).max(axis=2) / 127.0 + 1e-8  # (L, G, N)
    qw = np.clip(np.round(wg / scale[:, :, None, :]), -127, 127).astype(np.int8)
    qw = qw.reshape(L, K, N)
    return (jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16),
            jnp.asarray(qw), jnp.asarray(scale, jnp.float32))


# ---------------------------------------------------------------- variants
def run_scan(per_layer, ws, x):
    """acc over layers; R reps via fori_loop."""
    def one_pass(acc):
        def body(acc, w):
            # feed the carry back into x so no rep/layer can be hoisted/CSE'd
            x_eff = x + 1e-20 * acc[:, :K].astype(x.dtype)
            return acc + per_layer(x_eff, w), None
        acc, _ = jax.lax.scan(body, acc, ws)
        return acc
    def rep(i, acc):
        return one_pass(acc * 0.5)
    return jax.lax.fori_loop(0, R, rep, jnp.zeros((M, N), jnp.float32))


def v_bf16(x, w, qw, scale):
    return run_scan(lambda x, w: jnp.matmul(x, w, preferred_element_type=jnp.float32), w, x)


def v_xla_int8(x, w, qw, scale):
    def per_layer(x, wq_s):
        qw, s = wq_s
        wd = (qw.astype(jnp.bfloat16).reshape(G, GSIZE, N)
              * s[:, None, :].astype(jnp.bfloat16)).reshape(K, N)
        return jnp.matmul(x, wd, preferred_element_type=jnp.float32)
    return run_scan(per_layer, (qw, scale), x)


def v_pallas_old(x, w, qw, scale):
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul
    def per_layer(x, wq_s):
        qw, s = wq_s
        return quant_matmul(x, qw, s, block_m=8, block_n=256, block_k=128,
                            out_dtype=jnp.float32)
    return run_scan(per_layer, (qw, scale), x)


# ---- new kernel: bf16 convert only; scale applied to (M, N) partial sums
def _qmm2_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, bk, gsize):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jax.lax.dot_general(x_ref[...], w_ref[...].astype(x_ref.dtype),
                               (((1, ), (0, )), ((), ())),
                               preferred_element_type=jnp.float32)
    g = (k * bk) // gsize
    acc_ref[...] += part * s_ref[g, :][None, :]

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmm2(x, qw, scales, block_n=512, block_k=None, out_dtype=jnp.float32):
    M, K = x.shape
    _, N = qw.shape
    G = scales.shape[0]
    gsize = K // G
    bk = block_k or min(512, gsize)
    Gpad = -(-G // 8) * 8
    if Gpad != G:
        scales = jnp.pad(scales, ((0, Gpad - G), (0, 0)))
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_qmm2_kernel, nk=nk, bk=bk, gsize=gsize),
        grid=(1, N // block_n, nk),
        in_specs=[
            pl.BlockSpec((M, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((Gpad, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((M, block_n), jnp.float32)],
    )(x, qw, scales)


# ---- mixed-dtype dot: hand Mosaic the s8 operand directly
def _qmm3_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, bk, gsize):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jax.lax.dot_general(x_ref[...], w_ref[...],
                               (((1, ), (0, )), ((), ())),
                               preferred_element_type=jnp.float32)
    g = (k * bk) // gsize
    acc_ref[...] += part * s_ref[g, :][None, :]

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmm3(x, qw, scales, block_n=2560, block_k=None, out_dtype=jnp.float32):
    M, K = x.shape
    _, N = qw.shape
    G = scales.shape[0]
    gsize = K // G
    bk = block_k or min(512, gsize)
    Gpad = -(-G // 8) * 8
    if Gpad != G:
        scales = jnp.pad(scales, ((0, Gpad - G), (0, 0)))
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_qmm3_kernel, nk=nk, bk=bk, gsize=gsize),
        grid=(1, N // block_n, nk),
        in_specs=[
            pl.BlockSpec((M, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((Gpad, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((M, block_n), jnp.float32)],
    )(x, qw, scales)


def v_mixed(x, w, qw, scale):
    def per_layer(x, wq_s):
        qw, s = wq_s
        return qmm3(x, qw, s)
    return run_scan(per_layer, (qw, scale), x)


# ---- dynamic w8a8: per-row int8 activations, native int8 MXU dot
def _qmm4_kernel(x_ref, sx_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, bk, gsize):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jax.lax.dot_general(x_ref[...], w_ref[...],
                               (((1, ), (0, )), ((), ())),
                               preferred_element_type=jnp.int32)
    g = (k * bk) // gsize
    sx = sx_ref[0, :]  # (M,)
    acc_ref[...] += part.astype(jnp.float32) * (sx[:, None] * s_ref[g, :][None, :])

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmm4(x, qw, scales, block_n=2560, block_k=None, out_dtype=jnp.float32):
    M, K = x.shape
    _, N = qw.shape
    G = scales.shape[0]
    gsize = K // G
    bk = block_k or min(512, gsize)
    Gpad = -(-G // 8) * 8
    if Gpad != G:
        scales = jnp.pad(scales, ((0, Gpad - G), (0, 0)))
    nk = K // bk
    # dynamic per-row activation quant (tiny: M x K)
    sx = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1) / 127.0 + 1e-12
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx[:, None]), -127, 127).astype(jnp.int8)
    sx8 = jnp.tile(sx[None, :], (8, 1))  # (8, M) sublane-tiled
    return pl.pallas_call(
        functools.partial(_qmm4_kernel, nk=nk, bk=bk, gsize=gsize),
        grid=(1, N // block_n, nk),
        in_specs=[
            pl.BlockSpec((M, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((8, M), lambda i, j, k: (0, 0)),
            pl.BlockSpec((bk, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((Gpad, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((M, block_n), jnp.float32)],
    )(xq, sx8, qw, scales)


def v_w8a8(x, w, qw, scale):
    def per_layer(x, wq_s):
        qw, s = wq_s
        return qmm4(x, qw, s)
    return run_scan(per_layer, (qw, scale), x)


def make_v_new(block_n, block_k):
    def v(x, w, qw, scale):
        def per_layer(x, wq_s):
            qw, s = wq_s
            return qmm2(x, qw, s, block_n=block_n, block_k=block_k)
        return run_scan(per_layer, (qw, scale), x)
    return v


VARIANTS = {
    "bf16": (v_bf16, 2 * L * K * N),
    "xla_int8": (v_xla_int8, 1 * L * K * N),
    "pallas_old": (v_pallas_old, 1 * L * K * N),
    "new_n512_k128": (make_v_new(512, 128), 1 * L * K * N),
    "new_n1024_k128": (make_v_new(1024, 128), 1 * L * K * N),
    "new_n2560_k128": (make_v_new(2560, 128), 1 * L * K * N),
    "mixed_n2560": (v_mixed, 1 * L * K * N),
    "w8a8_n2560": (v_w8a8, 1 * L * K * N),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    rng = np.random.default_rng(0)
    x, w, qw, scale = make_data(rng)
    ref = None
    for name in names:
        fn, wbytes = VARIANTS[name]
        f = jax.jit(lambda x, w, qw, scale, fn=fn: fn(x, w, qw, scale))
        y = f(x, w, qw, scale)
        got = np.asarray(jax.device_get(y), np.float32)
        if ref is None and name == "bf16":
            ref = got
        err = (np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)) if ref is not None else -1
        # marginal timing: (t[many] - t[few]) cancels the fixed fetch +
        # dispatch cost of a call
        def timed(ncalls):
            trials = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(ncalls):
                    y = f(x, w, qw, scale)
                float(jnp.sum(y))
                trials.append(time.perf_counter() - t0)
            return min(trials)
        dt = (timed(9) - timed(1)) / (8 * R)
        gbs = wbytes / dt / 1e9
        print(f"{name:16s} {dt*1e3:7.3f} ms/pass  {gbs:7.1f} GB/s (weight bytes)  relerr={err:.4f}",
              flush=True)


if __name__ == "__main__":
    main()
