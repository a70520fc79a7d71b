"""The plain reference of ``olmo_hybrid`` (Olmo-Hybrid-7B): three
``linear_attention`` layers (the gated delta rule) to each ``full_attention``
layer, in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``.
The recurrence runs token by token: no chunking, no cache, no batching of
requests, no kernels.

Every projection is bias-free, ``H`` 3840, ``x_t`` a layer's input.

Block, both kinds: ``h = x + RMSNorm(mixer(x))``, ``y = h + RMSNorm(MLP(h))``
(the mixer and the MLP read the residual stream itself), ``MLP(h) =
W_down(SiLU(W_gate h) * W_up h)``; a final RMSNorm before the untied head.

``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the
whole projection before the split into heads; causal softmax attention at
scale ``head_dim^-1/2``; no rotary rotation; ``W_o``.

``linear_attention``, per head ``i``, key size ``dk``, value size ``dv``:

    [q~ ; k~ ; v~] = x [W_q ; W_k ; W_v]
    u_t[c] = SiLU(sum_{j=0..W-1} w[c, j] u~_{t-W+1+j}[c]),  zeros before position 0
    q_i <- q_i / |q_i|_2 dk^-1/2 ;  k_i <- k_i / |k_i|_2
    beta_t = 2 sigmoid(W_b x_t)_i ;  g_t = -exp(A_log_i) softplus((W_a x_t)_i + dt_bias_i)
    S_t = e^g_t S_{t-1} + beta_t k_t (v_t - e^g_t S_{t-1}^T k_t)^T ,  S_0 = 0 ;  o_t = S_t^T q_t
    y_t = W_o [ RMSNorm_dv(o_t) * SiLU((W_g x_t)_i) ]

**Departures from the published description**, each also under ``assumed``
in the configuration file: ``config.json`` gives the sizes and
``layer_types`` only. Norm placement, the QK norm and the absence of a
rotation (``rope_theta: null``) are the Olmo family's convention read
literally; the convolution has no bias; ``|.|_2`` is ``sqrt(sum x^2 +
1e-6)`` (``L2_EPS``: a zero vector stays zero, as the public Gated DeltaNet
layer code does it). The builder had no network: where the published
modeling code differs, the code wins and this file is to be corrected.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time and the head a block of the vocabulary at a time, on the
positions asked for, so that float32 copies of the chip's 8.2 GB of bf16
weights never exist at once beside the engine.
"""

import functools

import jax
import jax.numpy as jnp

L2_EPS = 1e-6

# Logits of prefill + 16 decode steps of two requests through the scheduler's
# pool (bf16 weights, activations, K/V rows and state at rest; float32 scan,
# update, softmax and norms) against this reference's full forward on the
# same bf16 weights. A position's error is |got - ref|_2 / |ref|_2 over its
# 100,352 logits. LOGITS_TOL is EVERY compared position's limit; the readings
# it lies between are in PERF.md section 4 (PR 30), with their seeds.
LOGITS_TOL = 2.55e-2

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals, on the benchmark's draw of the weights): the served
# path reads 4e-7 in the median and 1e-6 at worst; a wrong state, window or
# span gives 0.01 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def int8_rows(x):
    """A pool leaf as an int8 tier would hold it: each vector along the last
    axis (a K or V row of one head; a state's row of ``dv`` values; a window
    input) rounded to 127 symmetric levels of its largest magnitude, in
    ``x``'s dtype."""
    return _rounded(x.astype(jnp.float32), 127.0, -1).astype(x.dtype)


def full_attention(x, lp, hp):
    """x (B, T, H) -> (B, T, H): QK-normed causal softmax attention."""
    B, T, _ = x.shape
    n, d = lp["wq"].shape[1:]
    flat = lambda w: w.reshape(w.shape[0], -1)
    q = _rms(x @ flat(lp["wq"]), lp["q_norm"], hp["eps"]).reshape(B, T, n, d)
    k = _rms(x @ flat(lp["wk"]), lp["k_norm"], hp["eps"]).reshape(B, T, -1, d)
    v = (x @ flat(lp["wv"])).reshape(B, T, -1, d)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def linear_attention(x, lp, hp):
    """x (B, T, H) -> (B, T, H): the gated delta rule, one token at a time."""
    B, T, _ = x.shape
    n, dv = lp["wo"].shape[:2]
    dk = lp["wq"].shape[1] // n
    W = lp["conv"].shape[1]
    mixed = jnp.concatenate([x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]], axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (W - 1, 0), (0, 0)))  # zeros before position 0
    u = jax.nn.silu(sum(padded[:, j:j + T] * lp["conv"][:, j] for j in range(W)))
    q = u[..., :n * dk].reshape(B, T, n, dk)
    k = u[..., n * dk:2 * n * dk].reshape(B, T, n, dk)
    v = u[..., 2 * n * dk:].reshape(B, T, n, dv)
    norm = lambda y: jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
    q, k = q / norm(q) * dk ** -0.5, k / norm(k)
    beta = jax.nn.sigmoid(x @ lp["wb"]) * (2.0 if hp["neg_eigval"] else 1.0)  # (B, T, n)
    alpha = jnp.exp(-jnp.exp(lp["a_log"]) * jax.nn.softplus(x @ lp["wa"] + lp["dt_bias"]))

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs  # (B, n, dk) x2, (B, n, dv), (B, n) x2
        S = a_t[..., None, None] * S
        S = S + jnp.einsum("bnd,bnv->bndv", k_t,
                           b_t[..., None] * (v_t - jnp.einsum("bndv,bnd->bnv", S, k_t)))
        return S, jnp.einsum("bndv,bnd->bnv", S, q_t)

    lead = lambda y: jnp.moveaxis(y, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((B, n, dk, dv), jnp.float32),
                        (lead(q), lead(k), lead(v), lead(alpha), lead(beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), lp["o_norm"], hp["eps"])  # (B, T, n, dv)
    o = o * jax.nn.silu((x @ lp["wg"]).reshape(B, T, n, dv))
    return jnp.einsum("btnv,nvh->bth", o, lp["wo"])


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, gates' biases, the convolution and the embedding stay
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wg": 0, "wb": 0, "wa": 0, "wo": (0, 1),
            "w_gate": 0, "w_up": 0, "w_down": 0}


def layer(h, lp, hp, kind, levels=0.0):
    """One block of the given kind (``full_attention`` / ``linear_attention``).
    ``levels`` > 0, the lower-precision probe, rounds the weight matrices to
    that many integer levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
        for name, axis in _ROUNDED.items():
            if name in lp:
                # attention's q/k/v are (H, heads, d): columns are (head, d) pairs
                lp[name] = _rounded(lp[name], levels, axis)
        mixer = linear_attention if kind == "linear_attention" else full_attention
        h = h + _rms(mixer(h, lp, hp), lp["ln1"], hp["eps"])
        ff = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        return h + _rms(ff, lp["ln2"], hp["eps"])


def head(h, final_norm, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (H, Vb) -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(final_norm), hp["eps"]) @ _rounded(f32(w), levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda h, lp, lv, kind: layer(h, lp, hp, kind, lv), static_argnums=3),
            jax.jit(lambda h, g, w, lv: head(h, g, w, hp, lv)))


VOCAB_BLOCK = 12544  # columns of the head widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0):
    """``ids`` (B, T) int32 -> logits (B, T - first, V) float32 of positions
    ``first ..``. ``p``: :func:`from_tree`'s layout. One compiled program a
    layer kind, run a layer at a time; the head a block of the vocabulary at
    a time. ``levels`` 127: the same forward with its weight matrices rounded
    to int8, the nearest precision below the configuration's bf16."""
    layer_fn, head_fn = _jitted(tuple(sorted(hp.items())))
    h = jnp.asarray(p["embed"][ids], jnp.float32)
    for kind, lp in zip(p["layer_types"], p["layers"]):
        h = layer_fn(h, lp, jnp.float32(levels), kind)
    h = h[:, first:]
    V = p["head"].shape[1]
    return jnp.concatenate([head_fn(h, p["final_norm"], p["head"][:, v0:v0 + VOCAB_BLOCK],
                                    jnp.float32(levels))
                            for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys."""
    pub = config["published"]
    return {"eps": float(pub["rms_norm_eps"]), "neg_eigval": bool(pub["linear_allow_neg_eigval"])}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, layer_types):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer at a time."""
    def one(lt, kind):
        mlp = lt["mlp"]
        out = dict(ln1=lt["attn_norm"]["scale"], ln2=lt["mlp_norm"]["scale"],
                   w_gate=mlp["gate_proj"]["kernel"], w_up=mlp["up_proj"]["kernel"],
                   w_down=mlp["down_proj"]["kernel"])
        if kind == "linear_attention":
            m = lt["gdn"]
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], wg=m["g_proj"]["kernel"],
                       wb=m["b_proj"]["kernel"], wa=m["a_proj"]["kernel"],
                       a_log=m["A_log"], dt_bias=m["dt_bias"], conv=m["conv"],
                       o_norm=m["o_norm"]["scale"], wo=m["o_proj"]["kernel"])
        else:
            m = lt["attn"]
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], q_norm=m["q_norm"]["scale"],
                       k_norm=m["k_norm"]["scale"], wo=m["o_proj"]["kernel"])
        return out

    return dict(embed=tree["embed"]["embedding"], layer_types=tuple(layer_types),
                layers=[one(tree[f"layer_{i}"], kind) for i, kind in enumerate(layer_types)],
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"])


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
