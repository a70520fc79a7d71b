"""The plain reference of ``lfm2_moe`` (LiquidAI LFM2-8B-A1B) in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``: ONE full causal forward,
no cache, no kernels, no batching of experts (a loop), the short convolution
as a sum over its taps of the whole sequence shifted.

Every block is ``h = x + Op_l(RMSNorm_op(x))``, ``y = h + FFN_l(RMSNorm_ffn(h))``
(RMSNorm ``x / rms(x) * g``, eps 1e-5); ``logits = RMSNorm_f(x) E^T``, the head
the embedding transposed (tied). ``Op``:

- ``short_conv`` (``conv_L_cache`` L = 3, no bias): ``[B ; C ; X] = u W_in``
  (thirds in that order); ``z_t = B_t * X_t``; ``c_t = sum_k w[:, k]
  z_(t-(L-1)+k)`` a channel (depthwise, causal, zeros before position 0, no
  activation); ``Op = (C_t * c_t) W_out``.
- ``full_attention``: ``q = u W_q`` (32 heads of 64), ``k, v = u W_k, u W_v`` (8
  heads), no bias; RMSNorm over the 64 values of each head of q and of k (one
  weight vector a projection) BEFORE rotation; rotary positions on all 64
  dimensions, pairs ``(j, j + 32)``, theta 1e6, on EVERY attention layer;
  causal ``softmax(q k^T / sqrt(64)) v``, query head n reads key head ``n //
  4``; ``W_o``.

``FFN`` of the first ``num_dense_layers`` layers: ``(silu(x W_1) * x W_3) W_2``.
Above them: ``s = sigmoid(x W_r)`` over ALL experts; the k experts are the top
k of ``s + b`` (``b``: the stored selection bias; ties to the lowest id); ``w_e
= scale * s_e / (sum_chosen s + 1e-6)`` WITHOUT the bias; ``sum_e w_e
Expert_e(x)``, each expert the same gated form at its own width; NO shared
expert.

**The share.** The cell holds every expert (``first`` 0, ``held`` = all): the
layer is whole. ``first``/``held`` are kept for a share of the experts, as
``references/exaone_moe.py`` has them.

**Departures**, each also under ``assumed`` in the configuration file: the
builder had no network; where ``modeling_lfm2_moe.py`` differs (the split's
order B, C, X; the rotation's pairs; the per-head norm before rotation; the
final norm's place; the tied head), the code wins and this file is to be
corrected.

**Routing is discontinuous** (``references/mistral_small_4.py``'s argument):
:func:`forward` takes the experts the program chose (``choice``) and follows a
set that differs from its own top-k where the program's lowest choice lies
less than ``ROUTING_MARGIN`` (in standard deviations of the row's selection
scores) under the reference's own k-th score; the weights stay the
reference's own ``s``.

**The carried rows.** ``call_starts`` (T,) names, for each position, the first
position of the program call that would compute it (:func:`serving_calls`: a
prompt's chunks, then a call a token). A tap that reaches behind its
position's call reads ZERO: the forward of a program that DROPS a slot's
carried rows at every call boundary. It is the control that says the
comparison would see such a program; ``None`` is the model itself.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time, an expert at a time and the head a block of the vocabulary
at a time, on the positions asked for, so that float32 copies of the chip's
7.9 GB of bf16 weights never exist at once beside the engine.
"""

import functools

import jax
import jax.numpy as jnp

# A position's error is |got - ref|_2 / |ref|_2 over its 65,536 logits, of
# prefill + 16 decode steps of two requests (prompts 300 and 1,100) through the
# scheduler's pool (bf16 weights, activations, rows and carried inputs at rest;
# float32 tap sum, softmax, router and norms) against this reference's full
# forward on the same bf16 weights, following the program's routing where it
# is a near tie. The weights are the benchmark's draw (``jobs/
# serve_lfm2_moe.py``: taps uniform in (-1, 1), every FFN's last matrix
# centred).
#
# LOGITS_TOL, EVERY compared position's limit, between its two readings (my
# chip runs, PR 50, 7 runs of 34 positions, each with a seed of its own, 128
# slots): the program reads 0.0293-0.0310 at its WORST position (a run's
# median 0.0269-0.0280: 24 bf16 sublayers at hidden 2,048), this reference
# with its weight matrices at int8 0.0591-0.0616 at its BEST position (0.110-
# 0.165 at its worst): a factor of 1.39 above the one and 1.37 under the
# other. The reference that drops the carried rows at every call boundary
# reads 1.37-1.39 at its worst position (0 at the one compared position that
# sits inside a prompt's last chunk, whose own taps are whole).
LOGITS_TOL = 4.3e-2

# A program's set of experts is followed where its lowest choice lies less
# than this under the reference's own k-th selection score, in standard
# deviations of the row's scores. The farthest choice the bf16 program made
# read 0.097-0.130 in those runs (24 sublayers of bf16 move a score further
# than cell 7's 16 do: 0.032-0.052 there); at cell 7's 0.10 one to five of a
# run's 14,320 pairs were refused, and one of them at a compared position read
# 0.066. A router that scores or selects wrongly differs by whole standard
# deviations in most rows and is caught by MAX_FOLLOWED_SHARE.
ROUTING_MARGIN = 0.20

# The (layer, position) pairs followed, as a share of all pairs of the
# forward: 944 to 1,018 of 14,320 (6.6 to 7.1%) in those runs.
MAX_FOLLOWED_SHARE = 0.15

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals): the served path reads 1e-6 at worst; a wrong carried
# row, span, weight or choice gives 1e-3 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def _rotated(x, theta):
    """x (B, n, T, d) rotated by position: pairs (j, j + d/2), all of d."""
    d, T = x.shape[-1], x.shape[-2]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def short_conv(u, lp, call_starts=None):
    """u (B, T, H) -> (B, T, H): the gated short convolution over the whole
    sequence. ``call_starts`` (T,) int32: a tap that reaches behind
    ``call_starts[t]`` reads zero (module docstring); None: behind position 0."""
    T, H = u.shape[1], u.shape[2]
    W = lp["taps"].shape[1]
    bcx = u @ lp["w_in"]
    b, c, x = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    z = b * x
    starts = jnp.zeros((T, ), jnp.int32) if call_starts is None else call_starts
    t = jnp.arange(T)
    conv = jnp.zeros_like(z)
    for k in range(W):
        back = W - 1 - k  # tap k reads position t - back
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :T]
        seen = (t - back >= starts)[None, :, None]
        conv = conv + jnp.where(seen, shifted, 0.0) * lp["taps"][:, k]
    return (c * conv) @ lp["w_out"]


def serving_calls(prompt_len, T, chunk):
    """``call_starts`` of a request served in prefill chunks of ``chunk`` and
    then a token a call: position t of the prompt belongs to the call that
    starts at ``t // chunk * chunk``, a later one to its own."""
    t = jnp.arange(T)
    return jnp.where(t < prompt_len, t // chunk * chunk, t).astype(jnp.int32)


def attention(u, lp, hp):
    """u (B, T, H) -> (B, T, H): causal grouped-query attention, per-head QK
    norm, then rotary positions."""
    T = u.shape[1]
    q = jnp.einsum("bth,hnd->bntd", u, lp["wq"])
    k = jnp.einsum("bth,hnd->bntd", u, lp["wk"])
    v = jnp.einsum("bth,hnd->bntd", u, lp["wv"])
    q, k = _rms(q, lp["qn"], hp["eps"]), _rms(k, lp["kn"], hp["eps"])
    q, k = _rotated(q, hp["theta"]), _rotated(k, hp["theta"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head n reads n // rep
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def _gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(u, lp, hp, follow=None):
    """Router over ALL experts: (weights (B, T, E) zero outside the chosen k,
    info). ``follow`` (B, T, k) int32: the program's choice (-1: none given);
    a set that differs from the reference's own top-k is taken where it is a
    near tie (``ROUTING_MARGIN``), with the reference's own ``s``. ``info``:
    ``gap`` (B, T) between the own k-th and (k+1)-th selection score,
    ``followed`` / ``refused`` (B, T) bool, ``reach`` (B, T): how far under the
    own k-th score its lowest choice lay (0 where the sets agree). ``gap`` and
    ``reach`` in standard deviations of the row's selection scores."""
    s = jax.nn.sigmoid(u @ lp["gate"])
    c = s + lp["bias"]  # the selection scores
    k, E = hp["top_k"], s.shape[-1]
    top_c, top_i = jax.lax.top_k(c, k + 1)  # stable: ties to the lowest id
    std = jnp.std(c, axis=-1)
    chosen = jnp.sum(jax.nn.one_hot(top_i[..., :k], E, dtype=s.dtype), axis=-2)
    followed = refused = jnp.zeros(s.shape[:-1], bool)
    reach = jnp.zeros(s.shape[:-1], s.dtype)
    if follow is not None:
        theirs = jnp.sum(jax.nn.one_hot(follow, E, dtype=s.dtype), axis=-2)  # -1: no expert
        differs = jnp.any(theirs != chosen, axis=-1) & (follow[..., 0] >= 0)
        lowest = jnp.min(jnp.take_along_axis(c, jnp.maximum(follow, 0), axis=-1), axis=-1)
        reach = jnp.where(differs, (top_c[..., k - 1] - lowest) / std, 0.0)
        followed = differs & (reach < ROUTING_MARGIN)
        refused = differs & ~followed
        chosen = jnp.where(followed[..., None], theirs, chosen)
    w = s * chosen
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + hp["renorm_eps"])
    return w * hp["routed_scale"], {"gap": (top_c[..., k - 1] - top_c[..., k]) / std,
                                    "followed": followed, "refused": refused, "reach": reach}


def routed(u, lp, hp, first=None, held=None, levels=0.0, follow=None):
    """The routed experts' part for the experts ``first .. first + held`` that
    ``lp`` holds (all of ``lp``'s by default), one expert at a time; and
    :func:`route`'s info."""
    first = hp["first"] if first is None else first
    held = lp["w_up"].shape[0] if held is None else held
    w, info = route(u, lp, hp, follow)
    wide = lambda x: _rounded(x.astype(jnp.float32), levels, 0)

    def one(acc, e):
        y = _gated_ffn(u, wide(lp["w_gate"][e]), wide(lp["w_up"][e]), wide(lp["w_down"][e]))
        return acc + jnp.take(w, first + e, axis=-1)[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    return out, info


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, the taps, the router and its bias and the embedding's
# lookup stay as they are
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "w_in": 0, "w_out": 0,
            "m_gate": 0, "m_up": 0, "m_down": 0}
_EXPERTS = ("w_gate", "w_up", "w_down")  # widened (and rounded) an expert at a time


def layer(x, lp, hp, kind, levels=0.0, follow=None, call_starts=None):
    """One block: ``(y (B, T, H), route's info or None)``. ``levels`` > 0, the
    lower-precision probe, rounds the weight matrices to that many integer
    levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        experts = {k: lp[k] for k in _EXPERTS if k in lp}
        lp = dict({k: jnp.asarray(v, jnp.float32) for k, v in lp.items() if k not in experts},
                  **experts)
        lp.update({k: _rounded(lp[k], levels, axis) for k, axis in _ROUNDED.items() if k in lp})
        u = _rms(x, lp["op_ln"], hp["eps"])
        h = x + (short_conv(u, lp, call_starts) if kind == "short_conv"
                 else attention(u, lp, hp))
        g = _rms(h, lp["ffn_ln"], hp["eps"])
        if "gate" not in lp:
            return h + _gated_ffn(g, lp["m_gate"], lp["m_up"], lp["m_down"]), None
        r, info = routed(g, lp, hp, levels=levels, follow=follow)
        return h + r, info


def head(h, g, e, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), e (Vb, H) rows of the embedding
    -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(g), hp["eps"]) @ _rounded(f32(e).T, levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda x, lp, lv, follow, starts, kind: layer(x, lp, hp, kind, lv, follow,
                                                                   starts),
                    static_argnums=5),
            jax.jit(lambda h, g, e, lv: head(h, g, e, hp, lv)))


VOCAB_BLOCK = 8192  # rows of the embedding widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0, choice=None, call_starts=None):
    """``ids`` (B, T) int32 -> (logits (B, T - first, V) float32 of positions
    ``first ..``, routing). ``p``: :func:`from_tree`'s layout. One compiled
    program a kind of block, run a layer at a time; the head a block of the
    vocabulary at a time. ``choice`` (expert layers, B, T, k): the experts the
    program chose in each expert layer, followed where they are a near tie
    (module docstring). ``routing``: ``followed`` / ``refused`` (expert layers,
    B, T) bool, ``reach`` and ``gap`` alike. ``levels`` 127: the same forward
    with its weight matrices rounded to int8, the nearest precision below the
    configuration's bf16. ``call_starts`` (T,): the forward of a program that
    drops its carried rows at every call boundary (module docstring)."""
    layer_fn, head_fn = _jitted(tuple(sorted(hp.items())))
    x = jnp.asarray(p["embed"][ids], jnp.float32)
    none = jnp.full(ids.shape + (hp["top_k"], ), -1, jnp.int32)
    starts = (jnp.zeros((ids.shape[1], ), jnp.int32) if call_starts is None
              else jnp.asarray(call_starts, jnp.int32))
    infos = []
    for kind, lp in zip(p["layer_types"], p["layers"]):
        follow = None
        if "gate" in lp:
            follow = none if choice is None else jnp.asarray(choice[len(infos)], jnp.int32)
        x, info = layer_fn(x, lp, jnp.float32(levels), follow, starts, kind)
        if info is not None:
            infos.append(info)
    x = x[:, first:]
    V = p["embed"].shape[0]
    logits = jnp.concatenate([head_fn(x, p["final_norm"], p["embed"][v0:v0 + VOCAB_BLOCK],
                                      jnp.float32(levels))
                              for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)
    empty = jnp.zeros((0, ) + ids.shape)
    return logits, {key: (jnp.stack([i[key] for i in infos]) if infos else empty)
                    for key in ("followed", "refused", "reach", "gap")}


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys and the sizes the program built (``first``)."""
    pub = config["published"]
    return {"eps": float(pub["norm_eps"]), "top_k": int(pub["num_experts_per_tok"]),
            "routed_scale": float(pub["routed_scaling_factor"]),
            "renorm_eps": float(config["reference"]["renorm_eps"]),
            "theta": float(pub["rope_theta"]), "first": int(model_cfg.moe_first_expert)}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, layer_types):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer and an expert at a time."""
    def one(lt, kind):
        out = dict(op_ln=lt["attn_norm"]["scale"], ffn_ln=lt["mlp_norm"]["scale"])
        if kind == "short_conv":
            m = lt["conv"]
            out.update(w_in=m["in_proj"]["kernel"], taps=m["conv"], w_out=m["out_proj"]["kernel"])
        else:
            m = lt["attn"]
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], wo=m["o_proj"]["kernel"],
                       qn=m["q_norm"]["scale"], kn=m["k_norm"]["scale"])
        if "mlp" in lt:
            f = lt["mlp"]
            out.update(m_gate=f["gate_proj"]["kernel"], m_up=f["up_proj"]["kernel"],
                       m_down=f["down_proj"]["kernel"])
        else:
            f = lt["moe"]
            out.update(gate=f["gate"], bias=f["e_score_correction_bias"],
                       w_gate=f["experts"]["gate_proj"], w_up=f["experts"]["up_proj"],
                       w_down=f["experts"]["down_proj"])
        return out

    return dict(embed=tree["embed"]["embedding"], layer_types=tuple(layer_types),
                layers=[one(tree[f"layer_{i}"], kind) for i, kind in enumerate(layer_types)],
                final_norm=tree["final_norm"]["scale"])


# ---- the comparison --------------------------------------------------------
def position_errors(got, ref):
    """Per position: |got - ref|_2 / |ref|_2 over the position's logits."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def compare(got, ref, followed=None, refused=None, tol=LOGITS_TOL):
    """``got``/``ref``: (P, V) logits of the compared positions; ``followed``
    / ``refused``: the forward's (expert layer, position) pairs, any shape,
    where the program's routing differed and was / was not taken (None:
    nothing was given to follow). ``ok``: every position's error finite and
    at most ``tol``, and at most ``MAX_FOLLOWED_SHARE`` of the pairs
    followed. Returns also the largest, the smallest and the median error,
    ``routing_margin_rows`` (pairs followed) and ``routing_refused_rows`` of
    ``routing_rows``, and every position's error for whoever sets the
    limits."""
    err = position_errors(got, ref)
    finite = jnp.nan_to_num(err, nan=jnp.inf)
    n_followed = 0 if followed is None else int(jnp.sum(followed))
    n_pairs = 0 if followed is None else int(jnp.size(followed))
    ok = bool(jnp.all(err <= tol)) and n_followed <= MAX_FOLLOWED_SHARE * n_pairs  # NaN is over
    return {"ok": ok, "error": float(jnp.max(finite)), "min_error": float(jnp.min(finite)),
            "median_error": float(jnp.median(err)), "rows": int(err.shape[0]),
            "routing_margin_rows": n_followed, "routing_rows": n_pairs,
            "routing_refused_rows": 0 if refused is None else int(jnp.sum(refused)),
            "errors": [round(float(e), 5) for e in err]}
