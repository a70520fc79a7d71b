"""The plain reference of ``falcon_h1`` (TII Falcon-H1-34B-Instruct) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: ONE full
causal forward, no cache, no kernels, the state-space model as the plain
recurrence over positions (a ``lax.scan`` of the one-step equations, no
chunking), every published multiplier where the published forward applies it.

Every block runs a Mamba-2 mixer AND grouped-query attention side by side on
ONE normed input (no bias but the convolution's; RMSNorm eps 1e-5):

    e   = E[token] * m_e                                      embedding_multiplier
    a   = RMSNorm_in(x)                                        ONE norm for both branches
    h   = x + m_so SSM(m_si a) + m_ao Attn(m_ai a)             ssm_out, ssm_in, attention_out, attention_in
    y   = h + MLP(RMSNorm_ff(h))
    logits = (RMSNorm_f(y_L) W_head) * m_l                     lm_head_multiplier; untied

- ``Attn`` (``nq`` query and ``nkv`` key/value heads of ``d``): ``q = u W_q``,
  ``k = (u W_k) * m_k`` (key_multiplier, BEFORE rotation), ``v = u W_v``;
  ``q, k = RoPE(q), RoPE(k)`` over the whole head, halves rotated
  (``(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)`` with ``x1`` the head's
  first half), frequencies ``theta^(-2j/d)``, no scaling; causal ``softmax(q
  k^T / sqrt(d)) v``, query head ``i`` reads key/value head ``i // (nq /
  nkv)``; ``W_o``.
- ``SSM`` (``nh`` heads of ``hd``, state ``N``, ``G`` groups, ``W`` 4):
  ``[z ; xBC ; dt] = (u W_in) * mu``, ``mu`` the five ssm_multipliers spread
  over ``[z (nh hd) ; x (nh hd) ; B (G N) ; C (G N) ; dt (nh)]``; ``xBC_t <-
  SiLU(sum_j w[:, j] xBC_(t-W+1+j) + b_c)`` (depthwise, causal, zeros before
  position 0) ``= [x_t (nh, hd) ; B_t (G, N) ; C_t (G, N)]``, head ``h`` reads
  group ``h // (nh / G)``; ``Delta_t = softplus(dt_t + dt_bias)``, ``a =
  -exp(A_log)`` (a scalar a head); ``S_t = exp(Delta_t a) S_(t-1) + (Delta_t
  x_t) (x) B_t``; ``y_t = S_t C_t + D x_t``; ``y_t <- w_n * g / rms_group(g)``,
  ``g = y_t * SiLU(z_t)`` (the gate BEFORE the norm), the mean square over
  each of the ``G`` groups of ``nh hd / G`` channels; ``SSM(u)_t = y_t W_out``.
  No clamp on Delta.
- ``MLP``: ``(SiLU((r W_gate) * m_g) * (r W_up)) W_down * m_d``
  (mlp_multipliers: gate, down).

**Departures**, each also under ``assumed`` in the configuration file: the
constants are applied in float32 at their published digits (the family's
code multiplies a bf16 activation by a Python float, or by a ``mup_vector``
buffer that a bf16 deployment rounds to bf16: one more rounding of a constant,
under this comparison's limit). The builder had no network: where
``modeling_falcon_h1.py`` differs from the above, the code wins and this file
is to be corrected.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
mixer at a time, the MLP a block of its intermediate width at a time and the
head a block of the vocabulary at a time, on the positions asked for: beside
the engine's 10.5 GB of weights and 4.0 GB of pool the chip has room for a few
hundred MB of float32 copies, not for a layer's 1.7 GB.
"""

import functools

import jax
import jax.numpy as jnp

# A position's error is |got - ref|_2 / |ref|_2 over its 261,120 logits (the
# logits are scaled by 1/128; the measure is relative to the row's own norm),
# of prefill + 16 decode steps of two requests (prompts 300 and 1,100) through
# the scheduler's pool (bf16 weights, activations, rows and state at rest;
# float32 state update, softmax and norms) against this reference's full
# forward on the same bf16 weights. The weights are the benchmark's draw
# (``jobs/serve_falcon_h1.py: falcon_params``).
#
# LOGITS_TOL, EVERY compared position's limit, between its two readings (my
# chip runs, PR 56, 14 runs of 34 positions, each with a seed of its own): the
# program reads 0.0239-0.0290 at its WORST position (a run's median
# 0.0208-0.0235: six blocks of three bf16 terms each, the MLP's 3.8 against a
# stream of 4-9), this reference with its weight matrices at int8
# 0.0518-0.0595 at its BEST position: a factor of 1.34 above the one and 1.33
# under the other. The PROGRAM without its attention branch reads 0.44-0.51 at
# its best position, without ``mu`` 0.90-1.14, with its state leaves zeroed
# between syncs over the limit in 30 of 34 positions (median 1.27-1.35; the
# four under it are the first request's first sync, whose state was whole).
LOGITS_TOL = 3.9e-2

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals): the served path reads 1e-6 at worst; a wrong state,
# span, weight or constant gives 1e-3 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); ``x`` itself where ``levels`` (a
    Python number: the plain forward holds no rounded copy) is 0."""
    if not levels:
        return x
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / levels
    return jnp.round(x / jnp.where(step == 0, 1.0, step)) * step


def _rope(x, theta):
    """x (B, n, T, d): the whole head rotated by position, halves paired."""
    d, T = x.shape[-1], x.shape[2]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]  # (T, d/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, lp, hp):
    """u (B, T, H) -> (B, T, H): causal grouped-query attention, keys scaled
    before rotation."""
    T = u.shape[1]
    q = jnp.einsum("bth,hnd->bntd", u, lp["wq"])
    k = jnp.einsum("bth,hnd->bntd", u, lp["wk"]) * hp["key_multiplier"]
    v = jnp.einsum("bth,hnd->bntd", u, lp["wv"])
    q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head i reads i // rep
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def mamba2(u, lp, hp):
    """u (B, T, H) -> (B, T, H): the in-projection's output scaled by ``mu``,
    then the recurrence one position at a time from a zero state."""
    B, T, _ = u.shape
    nh, hd, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], hp["ssm_groups"]
    di = nh * hd
    cc, W = lp["conv"].shape
    widths = (di, di, G * N, G * N, nh)
    mu = jnp.concatenate([jnp.full((w, ), m, jnp.float32)
                          for w, m in zip(widths, hp["ssm_multipliers"])])
    zxd = (u @ lp["w_in"]) * mu
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cc], zxd[..., di + cc:]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))  # zeros before position 0
    xbc = jax.nn.silu(sum(padded[:, j:j + T] * lp["conv"][:, j] for j in range(W))
                      + lp["conv_b"])
    x = xbc[..., :di].reshape(B, T, nh, hd)
    per_head = lambda m: jnp.repeat(m.reshape(B, T, G, N), nh // G, axis=2)  # (B, T, nh, N)
    Bm, Cm = per_head(xbc[..., di:di + G * N]), per_head(xbc[..., di + G * N:])
    delta = jax.nn.softplus(dt + lp["dt_bias"])  # (B, T, nh)
    a = -jnp.exp(lp["a_log"])  # (nh,)

    def token(S, xs):
        d_t, x_t, b_t, c_t = xs  # (B, nh), (B, nh, hd), (B, nh, N) x 2
        S = (jnp.exp(d_t * a)[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t) + lp["d"][:, None] * x_t

    lead = lambda y: jnp.moveaxis(y, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((B, nh, hd, N), jnp.float32),
                        (lead(delta), lead(x), lead(Bm), lead(Cm)))
    g = jnp.moveaxis(y, 0, 1).reshape(B, T, di) * jax.nn.silu(z)
    g = g.reshape(B, T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + hp["eps"])
    return (g.reshape(B, T, di) * lp["norm_w"]) @ lp["w_out"]


def mlp_block(r, w_gate, w_up, w_down, hp):
    """A block of the intermediate width: r (B, T, H), w_gate / w_up (H, Fb),
    w_down (Fb, H) -> its part of ``(SiLU(gate * m_g) * up) W_down``, before
    the down multiplier."""
    gate = jax.nn.silu((r @ w_gate) * hp["mlp_gate_multiplier"])
    return (gate * (r @ w_up)) @ w_down


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction; the MLP's a block of FFN_BLOCK of its intermediate width at a
# time, so W_down's scale is a block of its contraction's: group-wise int8);
# norms, the convolution, A, D, dt_bias and the embedding stay
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "w_in": 0, "w_out": 0}
FFN_BLOCK = 5376  # columns of the intermediate width widened to float32 at a time


def branch(kind, a, lp, hp, levels=0.0):
    """One mixer's term over the normed input ``a``: ``m_s SSM(m_si a)``
    (``kind`` "ssm") or ``m_a Attn(m_ai a)`` ("attn"), ``lp`` that mixer's
    leaves alone. ``levels`` > 0, the lower-precision probe, rounds the weight
    matrices to that many integer levels (127 is int8, the nearest precision
    below bf16)."""
    with jax.default_matmul_precision("highest"):
        lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
        lp.update({k: _rounded(lp[k], levels, _ROUNDED[k]) for k in lp if k in _ROUNDED})
        if kind == "ssm":
            return hp["ssm_out_multiplier"] * mamba2(hp["ssm_in_multiplier"] * a, lp, hp)
        return hp["attention_out_multiplier"] * attention(hp["attention_in_multiplier"] * a, lp, hp)


_ATTN = ("wq", "wk", "wv", "wo")
_SSM = ("w_in", "conv", "conv_b", "dt_bias", "a_log", "d", "norm_w", "w_out")


def _size(v):
    return jnp.sqrt(jnp.mean(jnp.square(v)))


def ffn_part(x, ln, w_gate, w_up, w_down, hp, levels=0.0):
    """One block of the MLP's intermediate width over ``RMSNorm_ff(x)``."""
    with jax.default_matmul_precision("highest"):
        wide = lambda w: _rounded(jnp.asarray(w, jnp.float32), levels, 0)
        return mlp_block(_rms(x, jnp.asarray(ln, jnp.float32), hp["eps"]),
                         wide(w_gate), wide(w_up), wide(w_down), hp)


def head(h, g, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (H, Vb) -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return (_rms(h, f32(g), hp["eps"]) @ _rounded(f32(w), levels, 0)) * hp[
            "lm_head_multiplier"]


@functools.lru_cache(maxsize=None)
def _jitted(hp_key, levels):
    hp = dict(hp_key)
    return (jax.jit(lambda a, lp, kind: branch(kind, a, lp, hp, levels), static_argnums=2),
            jax.jit(lambda x, ln, wg, wu, wd: ffn_part(x, ln, wg, wu, wd, hp, levels)),
            jax.jit(lambda h, g, w: head(h, g, w, hp, levels)))


VOCAB_BLOCK = 8192  # columns of the head widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0, branches=False):
    """``ids`` (B, T) int32 -> logits (B, T - first, V) float32 of positions
    ``first ..``. ``p``: :func:`from_tree`'s layout. A layer at a time: each
    of its two mixers a compiled program of its own, its MLP a block of the
    intermediate width at a time; the head a block of the vocabulary at a
    time. ``levels`` 127: the same forward with its weight matrices rounded
    to int8, the nearest precision below the configuration's bf16.
    ``branches``: return also ``(layers, 4)``, each layer's RMS of ``m_s
    SSM``, ``m_a Attn``, the MLP's term and the stream the mixers add to:
    what says that no branch vanishes beside the other."""
    branch_fn, ffn_fn, head_fn = _jitted(tuple(sorted(hp.items())), float(levels))
    h = jnp.asarray(p["embed"][ids], jnp.float32) * hp["embedding_multiplier"]
    sizes = []
    for lp in p["layers"]:
        a = _rms(h, jnp.asarray(lp["ln_in"], jnp.float32), hp["eps"])
        s = branch_fn(a, {k: lp[k] for k in _SSM}, "ssm")
        t = branch_fn(a, {k: lp[k] for k in _ATTN}, "attn")
        x = h + s + t
        F = lp["w_gate"].shape[1]
        f = sum(ffn_fn(x, lp["ln_ff"], lp["w_gate"][:, f0:f0 + FFN_BLOCK],
                       lp["w_up"][:, f0:f0 + FFN_BLOCK], lp["w_down"][f0:f0 + FFN_BLOCK])
                for f0 in range(0, F, FFN_BLOCK)) * hp["mlp_down_multiplier"]
        sizes.append(jnp.stack([_size(s), _size(t), _size(f), _size(h)]))
        # a layer's blocks are done before the next layer's are asked for: the
        # dispatch runs ahead of the device, and every slice asked for is held
        h = jax.block_until_ready(x + f)
    h = h[:, first:]
    V = p["head"].shape[1]
    logits = jnp.concatenate([head_fn(h, p["final_norm"], p["head"][:, v0:v0 + VOCAB_BLOCK])
                              for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)
    return (logits, jnp.stack(sizes)) if branches else logits


def kwargs_for(config):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys alone (the program's configuration is not
    consulted: a constant the program drops must not drop here with it)."""
    pub = config["published"]
    gate, down = pub["mlp_multipliers"]
    return {"eps": float(pub["rms_norm_eps"]), "theta": float(pub["rope_theta"]),
            "ssm_heads": int(pub["mamba_n_heads"]), "ssm_head_dim": int(pub["mamba_d_head"]),
            "ssm_state": int(pub["mamba_d_state"]), "ssm_groups": int(pub["mamba_n_groups"]),
            "embedding_multiplier": float(pub["embedding_multiplier"]),
            "lm_head_multiplier": float(pub["lm_head_multiplier"]),
            "attention_in_multiplier": float(pub["attention_in_multiplier"]),
            "attention_out_multiplier": float(pub["attention_out_multiplier"]),
            "key_multiplier": float(pub["key_multiplier"]),
            "ssm_in_multiplier": float(pub["ssm_in_multiplier"]),
            "ssm_out_multiplier": float(pub["ssm_out_multiplier"]),
            "ssm_multipliers": tuple(float(m) for m in pub["ssm_multipliers"]),
            "mlp_gate_multiplier": float(gate), "mlp_down_multiplier": float(down)}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, num_layers):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer at a time."""
    def one(lt):
        a, m, f = lt["attn"], lt["mamba2"], lt["mlp"]
        return dict(ln_in=lt["attn_norm"]["scale"], ln_ff=lt["mlp_norm"]["scale"],
                    wq=a["q_proj"]["kernel"], wk=a["k_proj"]["kernel"],
                    wv=a["v_proj"]["kernel"], wo=a["o_proj"]["kernel"],
                    w_in=m["in_proj"]["kernel"], conv=m["conv"], conv_b=m["conv_bias"],
                    dt_bias=m["dt_bias"], a_log=m["A_log"], d=m["D"],
                    norm_w=m["norm"]["scale"], w_out=m["out_proj"]["kernel"],
                    w_gate=f["gate_proj"]["kernel"], w_up=f["up_proj"]["kernel"],
                    w_down=f["down_proj"]["kernel"])

    return dict(embed=tree["embed"]["embedding"],
                layers=[one(tree[f"layer_{i}"]) for i in range(num_layers)],
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"])


# ---- the comparison --------------------------------------------------------
def position_errors(got, ref):
    """Per position: |got - ref|_2 / |ref|_2 over the position's logits."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def compare(got, ref, tol=LOGITS_TOL):
    """``got``/``ref``: (P, V) logits of the compared positions. ``ok``: every
    position's error finite and at most ``tol``. Returns also the largest,
    the smallest and the median error and every position's error for whoever
    sets the limit."""
    err = position_errors(got, ref)
    finite = jnp.nan_to_num(err, nan=jnp.inf)
    return {"ok": bool(jnp.all(err <= tol)),  # NaN is over
            "error": float(jnp.max(finite)), "min_error": float(jnp.min(finite)),
            "median_error": float(jnp.median(err)), "rows": int(err.shape[0]),
            "errors": [round(float(e), 5) for e in err]}
