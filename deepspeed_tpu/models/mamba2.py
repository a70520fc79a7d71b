"""Mamba-2 (state-space duality, arXiv:2405.21060), the mixer two kinds of
layer share: a ``mamba2`` layer's only sublayer (``nemotron_h``: 64 heads of
64, a state of 128 a channel, 8 groups, nothing scaled) and a
``parallel_hybrid`` layer's second mixer beside attention (``falcon_h1``: 32
heads of 128, a state of 256, 2 groups, its input and its in-projection's
output scaled by published constants). Every size is the configuration's:
``nh`` heads of ``hd`` channels, a scalar decay a head, a state of ``hd x N``
a head, ``B`` and ``C`` shared by ``G`` groups of heads, ONE causal depthwise
convolution over ``x``, ``B`` and ``C`` together, a group-wise gated RMSNorm:

    [z ; xBC ; dt] = (m_in u) W_in * mu             z: nh hd, xBC: nh hd + 2 G N, dt: nh
                                                    (m_in: ssm_in_multiplier; mu: ssm_multipliers'
                                                    five constants over z, x, B, C, dt; 1 unless set)
    xBC_t = SiLU(sum_j w[:, j] xBC_(t-W+1+j) + b_c) = [x_t (nh, hd) ; B_t (G, N) ; C_t (G, N)]
    Delta_t = softplus(dt_t + dt_bias) ; a = -exp(A_log)               a scalar a head
    S_t = exp(Delta_t a) S_(t-1) + (Delta_t x_t) (x) B_t               head h reads group h // (nh / G)
    y_t = S_t C_t + D x_t
    out = [w_n * RMS_group(y * SiLU(z))] W_out      the mean square over each group's nh hd / G channels

What a slot holds for it (``CausalLMModel.cache_spec``): the state ``(B, nh,
hd, N)`` (``N`` in the lanes) and the convolution's last ``W - 1`` inputs
``(B, 1, W - 1, nh hd + 2 G N)``, both at rest in the serving dtype, loaded
to float32 and rounded once on the store. The rules of a span program are
:class:`~deepspeed_tpu.models.transformer.GatedDeltaNet`'s: a row advances
over exactly its ``q_spans`` live columns (later columns get Delta 0: no
decay, no input), a span-0 row's leaves come out bit for bit, a span at
position 0 starts from zero whatever the slot held.

One column is the one-token update: :func:`ssd_step`, the definition, or
where the state leaf tiles (``ops/pallas/ssd_step.py: tiles``: both published
shapes; no tiny preset) the kernel that does the same on the leaf in place,
``dstpu_ssd_step``. Anything wider (a prefill chunk, the full forward) is
the chunked matrix form of the same recurrence (:func:`ssd_chunked`): the
part inside a chunk of ``cfg.ssm_chunk_size`` positions as masked products,
the state carried from chunk to chunk.
"""

from functools import partial

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.pallas import ssd_step as ssd_kernel
from .transformer import (TransformerConfig, _serves_by_spans, _tp_mesh_size, gdn_conv_init,
                          gdn_dt_bias_init, last_live_inputs, scaled)


def mamba2_a_log_init(key, shape, dtype=jnp.float32):
    """``A_log`` as the layer is published to start: the log of A ~ U[1, 16]
    a head, so heads forget at different rates."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def ssd_step(S, x, dt, a, Bm, Cm, D):
    """The recurrence for ONE token, float32: ``S`` (B, nh, hd, N), ``x`` (B,
    nh, hd), ``dt`` (B, nh), ``a``/``D`` (nh,), ``Bm``/``Cm`` (B, nh, N)
    (each head's group's). ``S' = exp(dt a) S + (dt x) (x) B``; returns ``(S' C
    + D x, S')``. Elementwise products and a sum over ``N``: a slot's state is
    read and written once. A token with ``dt`` 0 leaves the state as it is."""
    S = (jnp.exp(dt * a)[..., None, None] * S
         + (dt[..., None] * x)[..., None] * Bm[:, :, None, :])
    return jnp.sum(S * Cm[:, :, None, :], axis=-1) + D[:, None] * x, S


def ssd_chunked(S, x, dt, a, Bm, Cm, D, chunk):
    """The same recurrence over ``T`` tokens, chunk by chunk, float32: ``x``
    (B, T, nh, hd), ``dt`` (B, T, nh), ``Bm``/``Cm`` (B, T, G, N), ``S`` the
    incoming state. With ``c_t`` the running sum of ``dt a`` inside a chunk
    (inclusive) and head ``h`` in group ``g``:

        Y = tril(C_g B_g^T * e^(c_t - c_s)) (dt x) + e^(c_t) C_g S_0^T + D x
        S_L = e^(c_L) S_0 + ((dt x) * e^(c_L - c_s))^T B_g

    A token with ``dt`` 0 (padding up to a whole chunk, a column past a
    row's span) leaves the state as it is. Returns ``(Y (B, T, nh, hd),
    S_T)``. The products run at ``highest`` precision: their operands are
    float32 that bfloat16 passes would round."""
    B, T, nh, hd = x.shape
    G = Bm.shape[2]
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0), ) * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
    nc = (T + pad) // chunk
    # (nc, B, chunk, ...): the scan walks the chunks
    split = lambda v: jnp.moveaxis(v.reshape((B, nc, chunk) + v.shape[2:]), 1, 0)
    mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(S, xs):
        xc, dc, bc, cc = xs  # (B, L, nh, hd), (B, L, nh), (B, L, G, N) x 2
        c = jnp.cumsum(dc * a, axis=1)  # (B, L, nh)
        ch = jnp.moveaxis(c, 1, 2)  # (B, nh, L)
        decay = jnp.exp(jnp.where(lower, ch[..., :, None] - ch[..., None, :], -jnp.inf))
        cb = mm("btgn,bsgn->bgts", cc, bc)  # (B, G, L, L)
        m = decay.reshape(B, G, nh // G, chunk, chunk) * cb[:, :, None]
        dx = dc[..., None] * xc  # (B, L, nh, hd)
        xg = lambda v: v.reshape(B, chunk, G, nh // G, hd)
        Sg = S.reshape(B, G, nh // G, hd, -1)
        y = (mm("bgrts,bsgrp->btgrp", m, xg(dx))
             + jnp.exp(c).reshape(B, chunk, G, nh // G)[..., None]
             * mm("btgn,bgrpn->btgrp", cc, Sg))
        to_end = jnp.exp(c[:, -1:] - c)[..., None]  # (B, L, nh, 1)
        S = (jnp.exp(c[:, -1])[..., None, None] * S
             + mm("bsgrp,bsgn->bgrpn", xg(dx * to_end), bc).reshape(S.shape))
        return S, y.reshape(B, chunk, nh, hd) + D[:, None] * xc

    S, y = jax.lax.scan(body, S, tuple(split(v) for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(B, T + pad, nh, hd)[:, :T], S


class GroupGatedNorm(nn.Module):
    """``w * g / rms_group(g)`` with ``g = y * SiLU(z)``: the mean square over
    each of ``groups`` runs of channels, float32, one learned scale a channel."""
    groups: int
    epsilon: float

    @nn.compact
    def __call__(self, y, z):
        g = y * jax.nn.silu(z)
        grouped = g.reshape(g.shape[:-1] + (self.groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + self.epsilon)
        scale = self.param("scale", nn.initializers.ones, (g.shape[-1], ), jnp.float32)
        return grouped.reshape(g.shape) * scale


def in_projection_multipliers(cfg):
    """``mu``: ``cfg.ssm_multipliers``' five constants spread over the
    in-projection's outputs ``[z (nh hd) ; x (nh hd) ; B (G N) ; C (G N) ; dt
    (nh)]``, float32; None where the configuration publishes none. A constant
    of the configuration, not a parameter."""
    if not cfg.ssm_multipliers:
        return None
    gn = cfg.ssm_groups * cfg.ssm_state_size
    widths = (cfg.mamba2_inner, cfg.mamba2_inner, gn, gn, cfg.ssm_num_heads)
    return jnp.concatenate([jnp.full((w, ), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


class Mamba2(nn.Module):
    """The mixer of a ``mamba2`` layer and of a ``parallel_hybrid`` layer's
    state-space branch (module docstring). The call is the narrow one of the
    newer mixers; ``carry`` passes through untouched."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, kv_cache=None, write_index=None, q_spans=None, carry=None):
        cfg = self.cfg
        B, T, H = x.shape
        nh, hd, N, G = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups
        di, cc, W = cfg.mamba2_inner, cfg.mamba2_conv_channels, cfg.ssm_conv_kernel
        _serves_by_spans("mamba2", kv_cache, write_index, q_spans)
        f32 = jnp.float32
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=f32,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("ssd_proj"):
            zxd = dense(di + cc + nh, name="in_proj")(scaled(x, cfg.ssm_in_multiplier))
            mu = in_projection_multipliers(cfg)
            if mu is not None:
                zxd = (zxd.astype(f32) * mu).astype(cfg.dtype)
            z, xbc, dt = zxd[..., :di], zxd[..., di:di + cc], zxd[..., di + cc:]
            conv_w = self.param("conv", gdn_conv_init, (cc, W), f32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (cc, ), f32)
            dt_bias = self.param("dt_bias", gdn_dt_bias_init, (nh, ), f32)
            a = -jnp.exp(self.param("A_log", mamba2_a_log_init, (nh, ), f32))
            D = self.param("D", nn.initializers.ones, (nh, ), f32)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)  # (B, T, nh)
            in_place = False
            if kv_cache is None:
                state = jnp.zeros((B, nh, hd, N), f32)
                window = jnp.zeros((B, W - 1, cc), cfg.dtype)
            else:
                state_rest, window_rest = kv_cache
                live_row = q_spans > 0
                fresh = live_row & (write_index == 0)
                # GatedDeltaNet's rule for its in-place kernel
                in_place = (T == 1 and cfg.attention_impl == "flash" and _tp_mesh_size() == 1
                            and ssd_kernel.tiles(state_rest, nh, hd, N, G))
                if T == 1:
                    ssd_kernel.tally(in_place)
                if not in_place:
                    state = jnp.where(fresh[:, None, None, None], 0.0, state_rest.astype(f32))
                window = jnp.where(fresh[:, None, None], 0, window_rest[:, 0]).astype(cfg.dtype)
                if in_place:
                    # ONE reading of the window leaf: sixteen layers deep the compiler
                    # recomputes this select for the convolution AFTER the step's new
                    # window was written into the donated leaf (PERF.md, PR 57)
                    window = jax.lax.optimization_barrier(window)
                dt = jnp.where((jnp.arange(T)[None, :] < q_spans[:, None])[..., None], dt, 0.0)
            # causal depthwise convolution over [window ; this call's inputs]
            seq = jnp.concatenate([window, xbc.astype(cfg.dtype)], axis=1)
            conv = sum(seq[:, j:j + T].astype(f32) * conv_w[:, j] for j in range(W)) + conv_b
            u = jax.nn.silu(conv).astype(cfg.dtype).astype(f32)
            xs = u[..., :di].reshape(B, T, nh, hd)
            Bm = u[..., di:di + G * N].reshape(B, T, G, N)
            Cm = u[..., di + G * N:].reshape(B, T, G, N)
        with jax.named_scope("ssd_state"):
            if in_place:
                y, new_state = ssd_kernel.ssd_update(state_rest, xs[:, 0], dt[:, 0], a, Bm[:, 0],
                                                     Cm[:, 0], D, live_row, fresh)
                y = y[:, None]
            elif T == 1:
                per_head = lambda v: jnp.repeat(v[:, 0], nh // G, axis=1)  # (B, nh, N)
                y, state = ssd_step(state, xs[:, 0], dt[:, 0], a, per_head(Bm), per_head(Cm), D)
                y = y[:, None]
            else:
                y, state = ssd_chunked(state, xs, dt, a, Bm, Cm, D, cfg.ssm_chunk_size)
            if kv_cache is None:
                new_cache = None
            else:
                # the last W - 1 LIVE inputs: rows [span, span + W - 1) of seq
                if T == 1:
                    tail = jnp.where(live_row[:, None, None], seq[:, 1:], seq[:, :-1])
                else:
                    tail = last_live_inputs(seq, q_spans, W - 1)
                keep = live_row[:, None, None, None]
                if not in_place:
                    new_state = jnp.where(keep, state.astype(state_rest.dtype), state_rest)
                new_cache = (
                    new_state,
                    jnp.where(keep, tail[:, None].astype(window_rest.dtype), window_rest))
        with jax.named_scope("ssd_out"):
            g = GroupGatedNorm(G, cfg.layernorm_epsilon, name="norm")(
                y.reshape(B, T, di), z.astype(f32))
            out = dense(H, name="out_proj")(g.astype(cfg.dtype))
        return out, new_cache, carry
