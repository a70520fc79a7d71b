"""The plain reference of ``phi4flash`` (Phi-4-mini-flash-reasoning; SambaY,
arXiv:2507.06607, with Differential Attention, arXiv:2410.05258) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: ONE full
causal forward, no cache, no kernels, no ring (a mask), the state-space model
as the plain recurrence over positions (a ``lax.scan`` of the one-step
equations, no chunking).

Every layer, ``u = LN(x)`` (LayerNorm with bias): ``h = x + mixer(u)``,
``y = h + fc2(SiLU(gate) * up)`` with ``[gate ; up] = fc1(LN(h))`` (no
bias); a final LayerNorm; the head is the embedding. No positional encoding
of any kind. The mixer of layer ``i`` (0-based, ``L`` layers, ``L/2 = 16``):

- ``mamba`` (even ``i <= L/2``): ``[x~ ; z] = u W_in``; ``x = SiLU(conv4(x~)
  + b_c)`` (causal, depthwise, zeros before position 0); ``[d ; B ; C] = x
  W_x``; ``Delta = softplus(d W_dt + b_dt)``; ``h_t = exp(Delta_t A) h_(t-1)
  + (Delta_t x_t) (x) B_t``, ``A = -exp(A_log)``; ``y_t = h_t C_t + D x_t``;
  ``out = (y * SiLU(z)) W_out``. The LAST Mamba layer (``i = L/2``) also
  hands on ``m_t = y_t`` (before the gate).
- ``diff_attention`` (odd ``i <= L/2 + 1``): ``[q ; k ; v] = u W_qkv + b``;
  query pair ``p`` is heads ``(2p, 2p+1)``, its key/value pair ``g = p //
  (pairs a group)``: ``K1 = K_2g``, ``K2 = K_2g+1``, ``V = [V_2g ; V_2g+1]``;
  ``A1 = softmax(q_2p K1^T / sqrt(d))``, ``A2 = softmax(q_2p+1 K2^T /
  sqrt(d))`` under the layer's mask (causal; for ``i < L/2`` also only the
  query's last ``window`` keys, itself included); ``o_p = RMSNorm_2d((A1 -
  lambda A2) V) (1 - lambda_init)``, ``lambda = exp(l_q1 . l_k1) - exp(l_q2
  . l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``out =
  concat_p(o_p) W_o + b_o``. Layer ``L/2 + 1`` is the one full layer.
- ``gmu`` (even ``i >= L/2 + 2``): ``out = (SiLU(u W_1) * m) W_2``.
- ``cross_attention`` (odd ``i >= L/2 + 3``): the same differential
  attention with its own ``W_q``, ``W_o`` and lambdas over the full layer's
  ``K`` and ``V``, causal.

**Departures from the published code**, each also under ``assumed`` in the
configuration file: ``config.json`` carries neither the Mamba sizes
(``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` ceil(H/16)) nor the
differential form; they are the family's published description. ``W_qkv`` is
kept as three matrices (the same numbers). The sub-norm has a learned scale
and no bias, eps 1e-5. The window counts the query's own key (512 keys in
all). ``m`` is the SSM output with the ``D`` term, before the gate. The
builder had no network: where ``modeling_phi4flash.py`` differs, the code
wins and this file is to be corrected.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time and the head a block of the vocabulary at a time, on the
positions asked for, so that float32 copies of the chip's 7.7 GB of bf16
weights never exist at once beside the engine.
"""

import functools
import math

import jax
import jax.numpy as jnp

# Logits of prefill + 16 decode steps of two requests through the scheduler's
# pool (bf16 weights, activations, rows, rings and state at rest; float32
# scan, softmax and norms) against this reference's full forward on the same
# bf16 weights. A position's error is |got - ref|_2 / |ref|_2 over its
# 200,064 logits. LOGITS_TOL is EVERY compared position's limit, set between
# two readings (my chip runs, PR 32: 14 runs of 34 positions, prompts 300 and
# 1,100; PERF.md section 4): the program reads 0.0486-0.0552 at its WORST
# position (0.043-0.045 in the median: 64 bf16 sublayers, each rewriting the
# stream), this reference with its weight matrices at int8 0.0763-0.0918 at
# its BEST position: a factor of 1.17 either side.
LOGITS_TOL = 6.5e-2

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals): the served path reads 1e-6 at worst; a wrong state,
# ring row, window or span gives 1e-3 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def int8_rows(x):
    """A pool leaf as an int8 tier would hold it: each vector along the last
    axis (a pair's K or V row; the ``d_inner`` values of one of a state's
    ``d_state`` planes; a window input) rounded to 127 symmetric levels of
    its largest magnitude, in ``x``'s dtype."""
    return _rounded(x.astype(jnp.float32), 127.0, -1).astype(x.dtype)


def lambda_init(layer_idx):
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


def mamba(u, lp):
    """u (B, T, H) -> (out (B, T, H), m (B, T, d_inner)): the recurrence one
    position at a time from a zero state."""
    B, T, _ = u.shape
    di, W = lp["conv"].shape
    ds = lp["a_log"].shape[1]
    r = lp["w_dt"].shape[0]
    xz = u @ lp["w_in"]
    xt, z = xz[..., :di], xz[..., di:]
    padded = jnp.pad(xt, ((0, 0), (W - 1, 0), (0, 0)))  # zeros before position 0
    x = jax.nn.silu(sum(padded[:, j:j + T] * lp["conv"][:, j] for j in range(W)) + lp["conv_b"])
    dbc = x @ lp["w_x"]
    delta = jax.nn.softplus(dbc[..., :r] @ lp["w_dt"] + lp["dt_bias"])  # (B, T, di)
    Bm, Cm = dbc[..., r:r + ds], dbc[..., r + ds:]
    A = -jnp.exp(lp["a_log"])  # (di, ds)

    def token(h, xs):
        d_t, x_t, b_t, c_t = xs  # (B, di) x2, (B, ds) x2
        h = jnp.exp(d_t[..., None] * A) * h + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + lp["d"] * x_t

    lead = lambda y: jnp.moveaxis(y, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((B, di, ds), jnp.float32),
                        (lead(delta), lead(x), lead(Bm), lead(Cm)))
    y = jnp.moveaxis(y, 0, 1)
    return (y * jax.nn.silu(z)) @ lp["w_out"], y


def gmu(u, lp, m):
    return (jax.nn.silu(u @ lp["w_1"]) * m) @ lp["w_2"]


def diff_attention(u, lp, hp, window, k=None, v=None):
    """u (B, T, H) -> (out, k, v). ``k``/``v`` (B, T, kv heads, d) given: the
    cross form (queries only). ``window`` 0: every earlier key."""
    B, T, H = u.shape
    flat = lambda w: w.reshape(w.shape[0], -1)
    d = hp["head_dim"]
    q = (u @ flat(lp["wq"]) + lp["bq"].reshape(-1)).reshape(B, T, -1, 2, d)
    if k is None:
        k = (u @ flat(lp["wk"]) + lp["bk"].reshape(-1)).reshape(B, T, -1, d)
        v = (u @ flat(lp["wv"]) + lp["bv"].reshape(-1)).reshape(B, T, -1, d)
    pairs, kv_pairs = q.shape[2], k.shape[2] // 2
    rep = pairs // kv_pairs
    kp = jnp.repeat(k.reshape(B, T, kv_pairs, 2, d), rep, axis=2)  # pair p reads g = p // rep
    vp = jnp.repeat(v.reshape(B, T, kv_pairs, 2 * d), rep, axis=2)
    rel = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    keep = (rel >= 0) & ((rel < window) if window else True)

    def attn_map(member):
        s = jnp.einsum("bqpd,bkpd->bpqk", q[:, :, :, member], kp[:, :, :, member]) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)

    lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"]))
           + lp["lam_init"])
    o = jnp.einsum("bpqk,bkpe->bqpe", attn_map(0) - lam * attn_map(1), vp)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + hp["eps"])
    o = o * lp["sub_norm"] * (1.0 - lp["lam_init"])
    return o.reshape(B, T, -1) @ lp["wo"].reshape(-1, H) + lp["bo"], k, v


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, biases, the convolution, A, D, lambdas and the
# embedding stay
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "w_in": 0, "w_x": 0, "w_dt": 0,
            "w_out": 0, "w_1": 0, "w_2": 0, "w_gate": 0, "w_up": 0, "w_down": 0}


def layer(h, lp, hp, kind, window, carry, levels=0.0):
    """One block. ``carry``: ``(m, k, v)`` from the layers below (zeros-sized
    placeholders until they exist); returns ``(h, carry)``. ``levels`` > 0,
    the lower-precision probe, rounds the weight matrices to that many
    integer levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
        for name, axis in _ROUNDED.items():
            if name in lp:
                lp[name] = _rounded(lp[name], levels, axis)
        m, k, v = carry
        u = _ln(h, lp["ln1"], lp["ln1_b"], hp["eps"])
        if kind == "mamba":
            out, m = mamba(u, lp)
        elif kind == "gmu":
            out = gmu(u, lp, m)
        elif kind == "cross_attention":
            out, _, _ = diff_attention(u, lp, hp, 0, k, v)
        else:
            out, k_new, v_new = diff_attention(u, lp, hp, window)
            if not window:
                k, v = k_new, v_new
        h = h + out
        u = _ln(h, lp["ln2"], lp["ln2_b"], hp["eps"])
        return h + (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"], (m, k, v)


def head(h, g, b, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (Vb, H) -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _ln(h, f32(g), f32(b), hp["eps"]) @ _rounded(f32(w), levels, 1).T


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda h, lp, lv, kind, window, carry: layer(h, lp, hp, kind, window, carry,
                                                                   lv), static_argnums=(3, 4)),
            jax.jit(lambda h, g, b, w, lv: head(h, g, b, w, hp, lv)))


VOCAB_BLOCK = 12504  # rows of the tied head widened to float32 at a time (200064 / 16)


def forward(p, ids, hp, levels=0.0, first=0):
    """``ids`` (B, T) int32 -> logits (B, T - first, V) float32 of positions
    ``first ..``. ``p``: :func:`from_tree`'s layout. One compiled program a
    (kind, window), run a layer at a time; the head a block of the vocabulary
    at a time. ``levels`` 127: the same forward with its weight matrices
    rounded to int8, the nearest precision below the configuration's bf16."""
    layer_fn, head_fn = _jitted(tuple(sorted(hp.items())))
    h = jnp.asarray(p["embed"][ids], jnp.float32)
    B, T = ids.shape
    empty = jnp.zeros((B, T, 0), jnp.float32)
    carry = (empty, empty, empty)
    for kind, window, lp in zip(p["layer_types"], p["layer_windows"], p["layers"]):
        h, carry = layer_fn(h, lp, jnp.float32(levels), kind, window, carry)
    h = h[:, first:]
    V = p["embed"].shape[0]
    g, b = p["final_norm"]
    return jnp.concatenate([head_fn(h, g, b, p["embed"][v0:v0 + VOCAB_BLOCK], jnp.float32(levels))
                            for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys."""
    pub = config["published"]
    return {"eps": float(pub["layer_norm_eps"]),
            "head_dim": int(pub["hidden_size"]) // int(pub["num_attention_heads"])}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, layer_types, layer_windows):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer at a time. The program holds a pair's two key (and value) heads as
    one head of twice the size: the same matrix, reshaped here."""
    def one(i, lt, kind):
        mlp = lt["mlp"]
        out = dict(ln1=lt["attn_norm"]["scale"], ln1_b=lt["attn_norm"]["bias"],
                   ln2=lt["mlp_norm"]["scale"], ln2_b=lt["mlp_norm"]["bias"],
                   w_gate=mlp["gate_proj"]["kernel"], w_up=mlp["up_proj"]["kernel"],
                   w_down=mlp["down_proj"]["kernel"])
        if kind == "mamba":
            m = lt["mamba"]
            out.update(w_in=m["in_proj"]["kernel"], conv=m["conv"], conv_b=m["conv_bias"],
                       w_x=m["x_proj"]["kernel"], w_dt=m["dt_proj"]["kernel"],
                       dt_bias=m["dt_bias"], a_log=m["A_log"], d=m["D"],
                       w_out=m["out_proj"]["kernel"])
        elif kind == "gmu":
            out.update(w_1=lt["gmu"]["in_proj"]["kernel"], w_2=lt["gmu"]["out_proj"]["kernel"])
        else:
            m = lt["attn"]
            out.update(wq=m["q_proj"]["kernel"], bq=m["q_proj"]["bias"],
                       wo=m["o_proj"]["kernel"], bo=m["o_proj"]["bias"],
                       lq1=m["lambda_q1"], lk1=m["lambda_k1"], lq2=m["lambda_q2"],
                       lk2=m["lambda_k2"], sub_norm=m["sub_norm"]["scale"],
                       lam_init=jnp.float32(lambda_init(i)))
            if kind == "diff_attention":
                out.update(wk=m["k_proj"]["kernel"], bk=m["k_proj"]["bias"],
                           wv=m["v_proj"]["kernel"], bv=m["v_proj"]["bias"])
        return out

    return dict(embed=tree["embed"]["embedding"], layer_types=tuple(layer_types),
                layer_windows=tuple(layer_windows),
                layers=[one(i, tree[f"layer_{i}"], kind) for i, kind in enumerate(layer_types)],
                final_norm=(tree["final_norm"]["scale"], tree["final_norm"]["bias"]))


# ---- the comparison --------------------------------------------------------
def position_errors(got, ref):
    """Per position: |got - ref|_2 / |ref|_2 over the position's logits."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def compare(got, ref, tol=LOGITS_TOL):
    """``got``/``ref``: (P, V) logits of the compared positions. ``ok``:
    every position's error finite and at most ``tol``. Returns also the
    largest, the smallest and the median error, and every position's error
    for whoever sets the limit."""
    err = position_errors(got, ref)
    finite = jnp.nan_to_num(err, nan=jnp.inf)
    return {"ok": bool(jnp.all(err <= tol)),  # NaN is over
            "error": float(jnp.max(finite)), "min_error": float(jnp.min(finite)),
            "median_error": float(jnp.median(err)), "rows": int(err.shape[0]),
            "errors": [round(float(e), 5) for e in err]}
