"""The plain reference: one pre-LayerNorm decoder in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It serves both configurations of the benchmark (GPT-2 and
OPT differ here only in the activation).

    h = wte[ids] + wpe[positions + position_offset]
    per layer:  a = LN1(h);  q, k, v = a Wq + bq, a Wk + bk, a Wv + bv
                h = h + softmax(causal(q k^T / sqrt(d))) v  Wo + bo
                m = LN2(h);  h = h + act(m W1 + b1) W2 + b2
    logits = LNf(h) head          (head = wte^T when tied)

Departures from the published descriptions, all noted in the configuration
files under ``assumed``: GPT-2's ``gelu_new`` is the tanh approximation
(``activation: gelu_tanh``, as published); OPT looks its positions up at
index + 2 in a 2050-row table, the program holds 2048 rows at offset 0 and
so does this reference (``position_offset`` is there for a program that
follows the published layout).

The reference takes its own parameter layout (``Params`` below). The two
``from_*_tree`` functions translate the program's trees into it; they are the
only place that knows the program's parameter names.

Tolerances, with their reasons, are TRAIN_LOSS_TOL and SERVE_LOGITS_TOL.
"""

import math

import jax
import jax.numpy as jnp

# Train: the engine's first loss (bf16 activations and matmuls, fp32 master
# weights, fp32 softmax and cross-entropy) against this reference's float32
# loss on the same parameters and batch. At random initialisation the loss
# is ln(vocab) ~ 10.9 and nearly flat in the logits, so bf16 rounding of the
# activations (2^-9 relative) moves it little: measured 1e-6, 1.3e-4, 3.4e-4
# and 6.6e-4 on the chip (gpt2-large, four seeds, my chip runs c1a/c1b, PR
# 23). A shifted label, another batch or a dropped layer moves it by 1e-2 to
# 1 (PR 21: a 4x batch showed as 6e-3 at once). 4e-3 is six times the worst
# measured.
TRAIN_LOSS_TOL = 4e-3

# Serve: logits of prefill and 16 decode steps through the scheduler's paged
# cache and fused kernels (int8 weights, bf16 activations, bf16 KV) against
# this reference's full forward on the DEQUANTISED weights, so int8 rounding
# of the weights is in both and what is compared is the arithmetic. The
# error is the largest absolute difference over the largest absolute
# reference logit of that position, worst position. bf16 activations through
# 36 residual layers measured 0.0102, 0.0121, 0.0124 and 0.0136 on the chip
# (four seeded requests, my chip run c2b, PR 23; chip_smoke's single kernels:
# 0.003 to 0.006). A wrong position, mask, slot or scale is O(1); an int8 KV
# pool in place of the configuration's bf16 adds about 0.003 a kernel over 36
# layers on top. 0.025 is 1.8 times the worst measured and nine standard
# deviations above their mean.
SERVE_LOGITS_TOL = 2.5e-2


def _layernorm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _act(x, kind):
    if kind == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(f"reference has no activation {kind!r}")


def forward(p, ids, *, num_heads, eps, activation, position_offset=0):
    """``ids`` (B, T) int32 -> logits (B, T, V) float32. ``p``: wte (V, H),
    wpe (P, H), layers [dict(ln1_g, ln1_b, wq, bq, wk, bk, wv, bv (H, H) /
    (H,), wo, bo, ln2_g, ln2_b, w1 (H, F), b1, w2 (F, H), b2)], lnf_g, lnf_b,
    head (H, V) or None for the tied head."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        B, T = ids.shape
        H = p["wte"].shape[1]
        d = H // num_heads
        h = f32(p["wte"])[ids] + f32(p["wpe"])[jnp.arange(T) + position_offset][None]
        causal = jnp.tril(jnp.ones((T, T), bool))
        for lp in p["layers"]:
            a = _layernorm(h, f32(lp["ln1_g"]), f32(lp["ln1_b"]), eps)
            heads = lambda w, b: (a @ f32(w) + f32(b)).reshape(B, T, num_heads, d)
            q, k, v = heads(lp["wq"], lp["bq"]), heads(lp["wk"], lp["bk"]), heads(lp["wv"], lp["bv"])
            s = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
            h = h + o.reshape(B, T, H) @ f32(lp["wo"]) + f32(lp["bo"])
            m = _layernorm(h, f32(lp["ln2_g"]), f32(lp["ln2_b"]), eps)
            h = h + _act(m @ f32(lp["w1"]) + f32(lp["b1"]), activation) @ f32(lp["w2"]) + f32(lp["b2"])
        h = _layernorm(h, f32(p["lnf_g"]), f32(p["lnf_b"]), eps)
        head = f32(p["wte"]).T if p.get("head") is None else f32(p["head"])
        return h @ head


def loss(p, ids, **kw):
    """Mean next-token cross-entropy over every position but the last."""
    logits = forward(p, ids, **kw)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def kwargs_for(config, model_cfg):
    ref = config["reference"]
    return dict(num_heads=model_cfg.num_heads, eps=model_cfg.layernorm_epsilon,
                activation=ref["activation"], position_offset=ref["position_offset"])


# ---- the program's parameter trees -> Params ------------------------------
def _layer_names(tree):
    names = sorted((k for k in tree if k.startswith("layer_")), key=lambda k: int(k[6:]))
    if not names:
        raise ValueError("the reference reads unrolled layers (layer_<i>); the cells "
                         "run scan_layers=False")
    return names


def from_train_tree(tree):
    """The training engine's float32 master parameters (flax names)."""
    layers = []
    for name in _layer_names(tree):
        lt = tree[name]
        at, ml = lt["attn"], lt["mlp"]
        H = at["q_proj"]["kernel"].shape[0]
        flat = lambda node: (node["kernel"].reshape(H, -1), node["bias"].reshape(-1))
        (wq, bq), (wk, bk), (wv, bv) = flat(at["q_proj"]), flat(at["k_proj"]), flat(at["v_proj"])
        layers.append(dict(
            ln1_g=lt["attn_norm"]["scale"], ln1_b=lt["attn_norm"]["bias"],
            wq=wq, bq=bq, wk=wk, bk=bk, wv=wv, bv=bv,
            wo=at["o_proj"]["kernel"].reshape(-1, H), bo=at["o_proj"]["bias"],
            ln2_g=lt["mlp_norm"]["scale"], ln2_b=lt["mlp_norm"]["bias"],
            w1=ml["up_proj"]["kernel"], b1=ml["up_proj"]["bias"],
            w2=ml["down_proj"]["kernel"], b2=ml["down_proj"]["bias"]))
    return dict(wte=tree["embed"]["embedding"], wpe=tree["pos_embed"], layers=layers,
                lnf_g=tree["final_norm"]["scale"], lnf_b=tree["final_norm"]["bias"],
                head=None)


def _dequant(q, scale):
    """(K, N) int8 with (G, N) float32 group scales along K -> float32."""
    G = scale.shape[0]
    w = q.astype(jnp.float32).reshape(G, -1, q.shape[1]) * scale[:, None, :]
    return w.reshape(q.shape)


def from_int8_tree(tree, vocab_size):
    """The serving engine's int8 tree (``quantize_params``: fused ``qkv_q``,
    ``kernel_q``/``kernel_scale`` projections, a separate padded ``logits_q``
    head), dequantised: the reference multiplies the same weights."""
    layers = []
    for name in _layer_names(tree):
        lt = tree[name]
        at, ml = lt["attn"], lt["mlp"]
        qkv = _dequant(at["qkv_q"], at["qkv_scale"])
        H = qkv.shape[0]
        wq, wk, wv = jnp.split(qkv, 3, axis=1)
        bq, bk, bv = jnp.split(at["qkv_bias"].reshape(-1), 3)
        deq = lambda node: _dequant(node["kernel_q"], node["kernel_scale"])
        layers.append(dict(
            ln1_g=lt["attn_norm"]["scale"], ln1_b=lt["attn_norm"]["bias"],
            wq=wq, bq=bq, wk=wk, bk=bk, wv=wv, bv=bv,
            wo=deq(at["o_proj"]).reshape(-1, H), bo=at["o_proj"]["bias"],
            ln2_g=lt["mlp_norm"]["scale"], ln2_b=lt["mlp_norm"]["bias"],
            w1=deq(ml["up_proj"]), b1=ml["up_proj"]["bias"],
            w2=deq(ml["down_proj"]), b2=ml["down_proj"]["bias"]))
    head = _dequant(tree["logits_q"], tree["logits_scale"])[:, :vocab_size]
    return dict(wte=tree["embed"]["embedding"], wpe=tree["pos_embed"], layers=layers,
                lnf_g=tree["final_norm"]["scale"], lnf_b=tree["final_norm"]["bias"],
                head=head)


def logits_error(got, ref):
    """Largest absolute difference over the largest absolute reference
    logit, per position; returns the worst position's."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.max(jnp.max(jnp.abs(got - ref), axis=-1) / jnp.max(jnp.abs(ref), axis=-1))
