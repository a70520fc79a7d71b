"""Fused single-token decode layer (TPU Pallas).

The TPU equivalent of the reference's fused inference pass — ``qkv_gemm ->
softmax_context -> vector_matmul -> mlp_gemm`` (``csrc/transformer/
inference/csrc/pt_binding.cpp:1745-1805`` + ``inference_context.h``'s
workspace): a decode layer runs in THREE resident kernels, with int8
weights streamed block-by-block through the MXU and the layer's
norms/biases/activations/rotary folded in (no XLA glue between
projections).

Why: at decode the step is HBM-bound and the op count is the enemy — the
per-projection path costs ~190 kernel launches + ~340 XLA glue fusions per
token step, whose fixed costs roughly double the ideal weight-streaming
time. This brings a layer to 3 launches + 2 cache-commit
dynamic-update-slices:

    kernel A  norm1(x) folded into the fused [q;k;v] int8 matmul (+bias),
              with RoPE rotation of the q/k head segments on the final step
    kernel B  ``decode_attention`` over the committed KV cache (GQA-native:
              kv_heads may divide num_heads)
    kernel C  o-projection (+bias) -> residual -> norm2 -> up [and gate]
              (+bias, act) -> down (+bias) -> residual -> x_out

Everything inside the kernels stays 2-D (lane dim = feature dim): Mosaic
cannot lane-split ``(B, nh*hd) -> (B, nh, hd)`` in-kernel, so the head
reshape + cache commit happen in XLA where they are free (the HLO audit
shows zero copies in the decode loop body). RoPE needs no head reshape:
the rotation acts on static per-head column segments of the fused
[q;k;v] row, so it folds into kernel A's flush step.

Supported model shape (the engine gates on this): fused int8 qkv weights,
layernorm or rmsnorm norms, sequential residual, gelu/gelu_exact/
quick_gelu/relu MLP or a gated swiglu/geglu MLP (gate and up share the
norm2(x) tiles in kernel C), rope (full rotary only, ``rotary_dim in (0,
head_size)``) / learned / no positional embedding, and grouped KV heads
(``kv_heads`` dividing ``num_heads``). Still gated out: alibi, partial
rotary, local-attention layers, act-quant, MoE — see
``InferenceEngine._fused_decode_eligible`` for the reason strings.
Models without bias params (rmsnorm shapes) pass zero biases; the kernels
are uniform. Quantization groups follow ``CausalLMModel.quantize_params``.
Weight-block scales are applied to the (B, n-block) fp32 partial sums
after each dot — see ``quant_matmul.py`` for the design rationale and
microbenchmarks.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas


def _norm(x32, norms_ref, row, kind, eps):
    """Row ``row`` of the (4, H) norms block is the scale, ``row + 1`` the
    bias (a zero row for rmsnorm models, which have no bias param)."""
    scale = norms_ref[row, :][None, :]
    if kind == "rmsnorm":
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return x32 * jax.lax.rsqrt(ms + eps) * scale
    bias = norms_ref[row + 1, :][None, :]
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _act(h, kind):
    if kind == "gelu":
        return jax.nn.gelu(h, approximate=True)
    if kind == "gelu_exact":
        return jax.nn.gelu(h, approximate=False)
    if kind == "quick_gelu":
        return h * jax.nn.sigmoid(1.702 * h)
    if kind == "silu":
        return h * jax.nn.sigmoid(h)
    return jnp.maximum(h, 0.0)


def _rope_rotate(y, sin, cos, col0, rot_cols, hd):
    """Rotate the head segments of ``y`` (f32 (B, bn), a column block of the
    fused [q;k;v] row starting at global column ``col0``) that lie below
    ``rot_cols`` — the q and k heads; the v tail passes through. ``bn`` is a
    whole number of heads. ``sin``/``cos``: (B, hd // 2) f32 gathered at
    each row's position. Same half-split convention as ``apply_rope``."""
    half = hd // 2
    parts = []
    for i in range(y.shape[1] // hd):
        off = i * hd
        a = y[:, off:off + half]
        b = y[:, off + half:off + hd]
        parts.append(a * cos - b * sin)
        parts.append(b * cos + a * sin)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    return jnp.where(col < rot_cols, jnp.concatenate(parts, axis=-1), y)


def _qdot(x_bf16, w_ref, s_ref, k_idx, bk, gsize, col_off=None):
    """One k-block of an int8 matmul: widen to bf16, dot, scale partials.
    ``k_idx``: which k-block this grid step computes (python int or traced).
    ``col_off``: column offset into the (full-width) scales block when the
    weight block covers only a slice of N. Returns fp32 (B, bn)."""
    w = w_ref[...]
    bn = w.shape[1]
    ng = max(1, bk // gsize)
    span = min(gsize, bk)
    acc = None
    for t in range(ng):
        part = jax.lax.dot_general(
            x_bf16[:, t * span:(t + 1) * span],
            w[t * span:(t + 1) * span, :].astype(x_bf16.dtype),
            (((1, ), (0, )), ((), ())), preferred_element_type=jnp.float32)
        row = (k_idx * bk) // gsize + t
        if col_off is None:
            sl = s_ref[row, :]
        else:
            sl = s_ref[row, pl.ds(col_off, bn)]
        part = part * sl[None, :]
        acc = part if acc is None else acc + part
    return acc


from .quant_matmul import pick_block, pick_block_k as _pick_bk


def _prep_scales(sc):
    sc = jnp.asarray(sc, jnp.float32)
    G = sc.shape[0]
    Gp = -(-G // 8) * 8
    return (jnp.pad(sc, ((0, Gp - G), (0, 0))) if Gp != G else sc), G


def _row_blocks(B):
    """Row-block candidates, largest first: all rows when there are few (the
    decode step), else the multiples of 8 dividing ``B`` up to 256 (the
    chunked-prefill step runs slots x chunk rows through these kernels)."""
    if B <= 256:
        return [B]
    return [d for d in range(256, 7, -8) if B % d == 0] or [B]


# --------------------------------------------------------------- kernel A
def _qkv_ln_kernel(x_ref, norms_ref, w_ref, s_ref, b_ref, *rest,
                   nk1, bk1, g1, eps, norm_kind, rot_cols, hd):
    if rot_cols:
        sin_ref, cos_ref, o_ref, xln_s, acc_s = rest
    else:
        o_ref, xln_s, acc_s = rest
    col0 = pl.program_id(1) * o_ref.shape[1]
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _ln1():
        x32 = x_ref[...].astype(jnp.float32)
        xln_s[...] = _norm(x32, norms_ref, 0, norm_kind, eps).astype(x_ref.dtype)

    part = _qdot(xln_s[:, pl.ds(s * bk1, bk1)], w_ref, s_ref, s, bk1, g1)

    @pl.when(s == 0)
    def _init():
        acc_s[...] = part

    @pl.when(s > 0)
    def _acc():
        acc_s[...] += part

    @pl.when(s == nk1 - 1)
    def _done():
        y = acc_s[...] + b_ref[0, :][None, :]
        if rot_cols:
            y = _rope_rotate(y, sin_ref[...], cos_ref[...], col0, rot_cols, hd)
        o_ref[...] = y.astype(o_ref.dtype)


def _qkv_vmem(bm, bn, bk, H, Gp, g1, xb, rope):
    """VMEM bytes of one :func:`_qkv_ln_kernel` step: double-buffered
    operand blocks, the scratch, one group's widened weight slice and the
    f32 values of the norm and flush steps. Ran 1.3x-1.6x above the least
    ``vmem_limit_bytes`` the v5e compiler accepts at six shapes (gpt2-large,
    llama2-7b, llama3-8b x 8 and 512 rows), never below."""
    io = 2 * (bm * H * xb + 8 * H * 4 + bk * bn + Gp * bn * 4 + 8 * bn * 4
              + bm * bn * xb)
    if rope:
        io += 2 * 2 * bm * 128 * 4
    scratch = bm * H * xb + bm * bn * 4
    temps = min(g1, bk) * bn * xb + 2 * bm * H * 4 + 3 * bm * bn * 4
    return io + scratch + temps


def _qkv_blocks(B, H, Nq, Gp, g1, xb, hd):
    """(row, column, contraction) blocks of kernel A under the VMEM budget.
    The decode step keeps every row and every column resident and only
    walks k; wider models halve the k block, and the chunked-prefill step's
    hundreds of rows tile rows and columns (a column block is a whole
    number of heads so the rotary rotation stays block-local)."""
    col_unit = math.lcm(128, hd) if hd else 128
    cols = [Nq] + [d for d in range(Nq - Nq % col_unit, 0, -col_unit)
                   if d < Nq and Nq % d == 0]
    for bm in _row_blocks(B):
        for bn in cols:
            cap = 1024
            while cap >= min(g1, 128):
                bk = _pick_bk(H, g1, cap)
                if _pallas.fits_vmem(_qkv_vmem(bm, bn, bk, H, Gp, g1, xb, bool(hd))):
                    return bm, bn, bk
                cap //= 2
    raise ValueError(
        f"fused_qkv_ln: no (rows, columns, k) blocking of ({B}, {H}) x "
        f"({H}, {Nq}) with int8 group {g1} fits the "
        f"{_pallas.VMEM_BLOCK_BUDGET}-byte VMEM budget")


def fused_qkv_ln(x, norms, qkv, *, eps=1e-5, norm="layernorm", rope=None):
    """norm1(x) @ dequant(Wqkv) + bias (+ rope) in one kernel. x: (B, H)
    bf16; norms: (4, H) f32 (rows 0/1 used; bias row is zeros for
    rmsnorm); qkv: (W int8 (H, Nqkv), scales, bias). ``rope``: optional
    ``(sin2d, cos2d, rot_heads, head_dim)`` — (B, head_dim // 2) f32
    tables gathered at each row's position; the first ``rot_heads`` head
    segments (the q and k heads of the fused layout) are rotated on the
    flush step, the v tail passes through. Returns (B, Nqkv) bf16.

    Jitted (the arrays of ``rope`` apart from its two integers), as
    ``kv_commit.commit_kv_rows`` is: the layers of a step program, its first
    forwards and its loop body share one trace and one lowering of the
    kernel a row count."""
    sin2d, cos2d, rot_heads, hd = rope if rope is not None else (None, None, 0, 0)
    return _qkv_ln(x, norms, tuple(qkv), sin2d, cos2d, eps=eps, norm=norm,
                   rot_heads=rot_heads, hd=hd, interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames=("eps", "norm", "rot_heads", "hd", "interpret"))
def _qkv_ln(x, norms, qkv, sin2d, cos2d, *, eps, norm, rot_heads, hd, interpret):
    B, H = x.shape
    w, sc, b = qkv
    Nq = w.shape[1]
    sc, G = _prep_scales(sc)
    g1 = H // G
    bm, bn, bk1 = _qkv_blocks(B, H, Nq, sc.shape[0], g1, x.dtype.itemsize, hd)
    nk1 = H // bk1
    kernel = functools.partial(_qkv_ln_kernel, nk1=nk1, bk1=bk1, g1=g1, eps=eps,
                               norm_kind=norm, rot_cols=rot_heads * hd, hd=hd)
    in_specs = [
        pl.BlockSpec((bm, H), lambda i, j, s: (i, 0)),
        pl.BlockSpec(norms.shape, lambda i, j, s: (0, 0)),
        pl.BlockSpec((bk1, bn), lambda i, j, s: (s, j)),
        pl.BlockSpec((sc.shape[0], bn), lambda i, j, s: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
    ]
    operands = [x, norms, w, sc, b.reshape(1, -1)]
    if rot_heads:
        half = hd // 2
        in_specs += [pl.BlockSpec((bm, half), lambda i, j, s: (i, 0))] * 2
        operands += [jnp.asarray(sin2d, jnp.float32),
                     jnp.asarray(cos2d, jnp.float32)]
    return pl.pallas_call(
        kernel,
        name="dstpu_fused_qkv_ln",
        grid=(B // bm, Nq // bn, nk1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Nq), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, H), x.dtype), pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)


# --------------------------------------------------------------- kernel C
def _out_mlp_kernel(attn_ref, x_ref, norms_ref,
                    o_w, o_s, o_b, up_w, up_s, up_b, *rest,
                    nko, nju, nku, nkd, bko, bk1, bnu, bkd, go, gu, gd,
                    eps, act, norm_kind, gated):
    if gated:
        (gt_w, gt_s, gt_b, dn_w, dn_s, dn_b,
         xo_ref, res2, ln2_s, up_h, g_h, acc_s) = rest
    else:
        dn_w, dn_s, dn_b, xo_ref, res2, ln2_s, up_h, acc_s = rest
        gt_w = gt_s = gt_b = g_h = None
    s = pl.program_id(1)
    A1 = nko
    A2 = A1 + nju * nku

    # ---- o projection + residual ----
    @pl.when(s < A1)
    def _o():
        part = _qdot(attn_ref[:, pl.ds(s * bko, bko)], o_w, o_s, s, bko, go)

        @pl.when(s == 0)
        def _():
            acc_s[...] = part

        @pl.when(s > 0)
        def _():
            acc_s[...] += part

    @pl.when(s == A1 - 1)
    def _o_done():
        r = acc_s[...] + o_b[0, :][None, :] + x_ref[...].astype(jnp.float32)
        res2[...] = r
        ln2_s[...] = _norm(r, norms_ref, 2, norm_kind, eps).astype(ln2_s.dtype)

    # ---- up (and gate) projection + activation ----
    @pl.when((s >= A1) & (s < A2))
    def _up():
        p_ = s - A1
        j, k = p_ // nku, p_ % nku
        xt = ln2_s[:, pl.ds(k * bk1, bk1)]
        part = _qdot(xt, up_w, up_s, k, bk1, gu, col_off=j * bnu)
        gpart = _qdot(xt, gt_w, gt_s, k, bk1, gu, col_off=j * bnu) if gated \
            else None

        def _combine(u, g):
            ub = u + up_b[0, pl.ds(j * bnu, bnu)][None, :]
            if gated:  # gated MLP: act(gate) * up (swiglu / geglu)
                return _act(g + gt_b[0, pl.ds(j * bnu, bnu)][None, :], act) * ub
            return _act(ub, act)

        @pl.when(k == 0)
        def _():
            upd = part
            if nku == 1:  # single k-block: this step completes the column
                upd = _combine(part, gpart)
            elif gated:
                g_h[:, pl.ds(j * bnu, bnu)] = gpart.astype(g_h.dtype)
            up_h[:, pl.ds(j * bnu, bnu)] = upd.astype(up_h.dtype)

        @pl.when(k > 0)
        def _():
            upd = up_h[:, pl.ds(j * bnu, bnu)].astype(jnp.float32) + part
            if nku > 1:  # tracing reaches here only when nku > 1
                gacc = None
                if gated:
                    gacc = g_h[:, pl.ds(j * bnu, bnu)].astype(jnp.float32) + gpart
                    g_h[:, pl.ds(j * bnu, bnu)] = gacc.astype(g_h.dtype)
                upd2 = _combine(upd, gacc)
                upd = jnp.where(k == nku - 1, upd2, upd)
            up_h[:, pl.ds(j * bnu, bnu)] = upd.astype(up_h.dtype)

    # ---- down projection + residual ----
    @pl.when(s >= A2)
    def _down():
        k = s - A2
        part = _qdot(up_h[:, pl.ds(k * bkd, bkd)], dn_w, dn_s, k, bkd, gd)

        @pl.when(k == 0)
        def _():
            acc_s[...] = part

        @pl.when(k > 0)
        def _():
            acc_s[...] += part

    @pl.when(s == pl.num_programs(1) - 1)
    def _finish():
        xo_ref[...] = (res2[...] + acc_s[...] + dn_b[0, :][None, :]).astype(xo_ref.dtype)


def _out_mlp_vmem(bm, Ko, H, F, bko, bk1, bnu, bkd, groups, gsizes, xb, gated):
    """VMEM bytes of one :func:`_out_mlp_kernel` step. Every operand block
    is resident for the whole o -> up -> down walk, so the weight blocks of
    all three phases add up; the walked ones are double-buffered, the
    whole-array scale and bias blocks (constant block index) are counted
    once, which is how the v5e compiler's own accounting came out at the
    six shapes of :func:`_qkv_vmem` (this estimate 1.1x-1.5x above it)."""
    Gop, Gup, Gdp = groups
    ng = 2 if gated else 1
    rows = 2 * bm * (Ko + 2 * H) * xb                       # attn, x, out
    weights = 2 * (bko * H + ng * bk1 * bnu + bkd * H)
    scales = 4 * (Gop * H + ng * Gup * F + Gdp * H)
    small = 8 * 4 * (H + 2 * H + ng * F + H)                # norms + biases
    scratch = bm * (4 * H + xb * H + ng * xb * F + 4 * H)
    wide = max(min(gsizes[0], bko) * H, min(gsizes[1], bk1) * bnu,
               min(gsizes[2], bkd) * H)
    temps = wide * xb + 4 * bm * max(H, bnu) * 4
    return rows + weights + scales + small + scratch + temps


def fused_out_mlp(attn2d, x, norms, o, up, down, *, activation="gelu",
                  eps=1e-5, norm="layernorm", gate=None):
    """x + o_proj(attn) -> norm2 -> up [* act(gate)] -> down -> + residual,
    one kernel. attn2d: (B, nh*hd) bf16 flattened attention output; x:
    (B, H) residual stream; norms (4, H) f32 rows 2/3 used; o/up/down (and
    ``gate`` when the MLP is gated): (W int8, scales, bias). For
    ``activation`` in ("swiglu", "geglu") pass ``gate``; the gate
    contraction shares norm2(x)'s k-tiles with up and the activation
    applies to the gate (silu for swiglu, tanh-gelu for geglu), matching
    ``MLP``. Rows are independent: when they do not all fit the VMEM
    budget (the chunked-prefill step) an outer grid axis walks row blocks
    and each re-streams the weights. Returns x_out (B, H) bf16. Jitted, as
    :func:`fused_qkv_ln` is and for the same reason."""
    return _out_mlp(attn2d, x, norms, tuple(o), tuple(up), tuple(down),
                    None if gate is None else tuple(gate), activation=activation,
                    eps=eps, norm=norm, interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames=("activation", "eps", "norm", "interpret"))
def _out_mlp(attn2d, x, norms, o, up, down, gate, *, activation, eps, norm, interpret):
    B, H = x.shape
    o_w, o_s, o_b = o
    up_w, up_s, up_b = up
    dn_w, dn_s, dn_b = down
    Ko = o_w.shape[0]
    F = up_w.shape[1]
    o_s, Go = _prep_scales(o_s)
    up_s, Gu = _prep_scales(up_s)
    dn_s, Gd = _prep_scales(dn_s)
    go, gu, gd = Ko // Go, H // Gu, F // Gd
    gated = gate is not None
    xb = x.dtype.itemsize
    groups = (o_s.shape[0], up_s.shape[0], dn_s.shape[0])
    for cap in (1024, 512, 256, 128):
        bko = _pick_bk(Ko, go, cap)
        bk1 = _pick_bk(H, gu, cap)
        bkd = _pick_bk(F, gd, cap)
        bnu = pick_block(F, 2560 * cap // 1024, 128)
        bm = next((m for m in _row_blocks(B) if _pallas.fits_vmem(_out_mlp_vmem(
            m, Ko, H, F, bko, bk1, bnu, bkd, groups, (go, gu, gd), xb, gated))),
            None)
        if bm is not None:
            break
    else:
        raise ValueError(
            f"fused_out_mlp: no blocking of {B} rows with hidden {H}, ffn {F} "
            f"fits the {_pallas.VMEM_BLOCK_BUDGET}-byte VMEM budget")
    nko, nkd = Ko // bko, F // bkd
    nju, nku = F // bnu, H // bk1
    nsteps = nko + nju * nku + nkd
    A1 = nko

    act = activation
    if gated:
        act = "silu" if activation == "swiglu" else "gelu"
        gt_w, gt_s, gt_b = gate
        gt_s, Gg = _prep_scales(gt_s)
        assert gt_w.shape == up_w.shape and Gg == Gu, \
            "gate/up projections must share shape and quant grouping"

    kernel = functools.partial(
        _out_mlp_kernel, nko=nko, nju=nju, nku=nku, nkd=nkd,
        bko=bko, bk1=bk1, bnu=bnu, bkd=bkd, go=go, gu=gu, gd=gd,
        eps=eps, act=act, norm_kind=norm, gated=gated)
    f32 = jnp.float32
    rows = lambda n: pl.BlockSpec((bm, n), lambda i, s: (i, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i, s: (0, 0))
    bias = lambda n: pl.BlockSpec((1, n), lambda i, s: (0, 0))
    up_spec = pl.BlockSpec((bk1, bnu), lambda i, s: (
        jnp.clip(s - A1, 0, nju * nku - 1) % nku,
        jnp.clip(s - A1, 0, nju * nku - 1) // nku))
    in_specs = [
        rows(Ko), rows(H), whole(norms),
        pl.BlockSpec((bko, H), lambda i, s: (jnp.clip(s, 0, nko - 1), 0)),
        whole(o_s), bias(H),
        up_spec, whole(up_s), bias(F),
    ]
    operands = [attn2d, x, norms, o_w, o_s, o_b.reshape(1, -1),
                up_w, up_s, up_b.reshape(1, -1)]
    if gated:
        in_specs += [up_spec, whole(gt_s), bias(F)]  # gate walks up's tiles
        operands += [gt_w, gt_s, gt_b.reshape(1, -1)]
    in_specs += [
        pl.BlockSpec((bkd, H),
                     lambda i, s: (jnp.clip(s - A1 - nju * nku, 0, nkd - 1), 0)),
        whole(dn_s), bias(H),
    ]
    operands += [dn_w, dn_s, dn_b.reshape(1, -1)]
    scratch = [
        pltpu.VMEM((bm, H), f32),       # res2
        pltpu.VMEM((bm, H), x.dtype),   # ln2 out
        pltpu.VMEM((bm, F), x.dtype),   # up_h
    ]
    if gated:
        scratch.append(pltpu.VMEM((bm, F), x.dtype))  # gate partials
    scratch.append(pltpu.VMEM((bm, H), f32))          # shared o/down accumulator
    return pl.pallas_call(
        kernel,
        name="dstpu_fused_out_mlp",
        grid=(B // bm, nsteps),
        in_specs=in_specs,
        out_specs=rows(H),
        out_shape=jax.ShapeDtypeStruct((B, H), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)


def fused_decode_block(x, norms, kv, qkv, o, up, down,
                       start, pos, *, activation="gelu", eps=1e-5, block_kv=256,
                       norm="layernorm", rope=None, gate=None):
    """One fused transformer decode layer for a single token per row.

    x: (B, H) bf16 residual stream. norms: (4, H) f32 rows
    [norm1_scale, norm1_bias, norm2_scale, norm2_bias] (zero bias rows for
    rmsnorm). kv: the layer's cache leaves as ``init_cache`` makes them,
    ``(k_cache, v_cache)`` of (B, kv_heads, S, hd) each or the packed
    ``(kv_cache, )`` of (B, kv_heads, S, 2 * hd), keys in lanes ``[0, hd)``
    — ``kv_heads`` may be smaller than ``num_heads`` (GQA; attention groups
    q heads over the KV heads). qkv/o/up/down (and ``gate`` for
    swiglu/geglu): (weight_q int8, scales f32 (G, N), bias f32 (N,)) tuples
    in matmul layout (qkv fused [q;k;v]). start: (B,) int32 first
    attendable slot; pos: scalar int32 cache write position. ``rope``:
    optional (sin2d, cos2d) — (B, hd // 2) f32 rotary tables gathered at
    each row's position, rotated in-kernel over the q and k head segments.

    Returns (x_out (B, H) bf16, new cache leaves) — the rows are committed
    (dynamic_update_slice at ``pos``; a packed row is its key and value
    joined) before attention, exactly like the unfused model path.
    """
    from .decode_attention import decode_attention
    B, H = x.shape
    _, nkv, S, lanes = kv[0].shape
    hd = lanes // 2 if len(kv) == 1 else lanes
    Nq = qkv[0].shape[1]
    nh = Nq // hd - 2 * nkv
    rope_op = None
    if rope is not None:
        sin2d, cos2d = rope
        rope_op = (sin2d, cos2d, nh + nkv, hd)
    qkv2d = fused_qkv_ln(x, norms, qkv, eps=eps, norm=norm, rope=rope_op)
    qf, kf, vf = jnp.split(qkv2d, [nh * hd, (nh + nkv) * hd], axis=-1)
    fresh = (kf.reshape(B, nkv, 1, hd), vf.reshape(B, nkv, 1, hd))
    if len(kv) == 1:
        fresh = (jnp.concatenate(fresh, axis=-1), )
    kv = tuple(jax.lax.dynamic_update_slice_in_dim(c, r.astype(c.dtype), pos, axis=2)
               for c, r in zip(kv, fresh))
    attn = decode_attention(qf.reshape(B, nh, hd), kv[0], kv[1] if len(kv) == 2 else None,
                            start, pos + 1, block_kv=min(block_kv, S))
    x_out = fused_out_mlp(attn.reshape(B, nh * hd), x, norms, o, up, down,
                          activation=activation, eps=eps, norm=norm, gate=gate)
    return x_out, kv
