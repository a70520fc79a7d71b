"""The plain reference of ``exaone_moe`` (K-EXAONE-236B-A23B) in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``: ONE full causal forward
with the multi-token-prediction module's logits beside the stack's, no cache,
no ring, no kernels, no batching of experts (a loop over them).

Layer ``l`` over ``x`` (B, T, H), ``eps`` 1e-5, RMSNorm with a learned weight:

- block: ``h = x + RMSNorm(Attn_l(x))``, ``y = h + RMSNorm(FFN_l(h))``: both
  halves read the residual stream itself and their OUTPUTS are normalised;
  ``logits = RMSNorm_f(y_last) W_head``, embedding and head untied.
- ``Attn_l``: ``q = x W_q`` (``nh`` heads of ``d``), ``k, v = x W_k, x W_v``
  (``nkv`` heads), no bias; ``q`` and ``k`` each through an RMSNorm over the
  ``d`` values of ONE head, one weight vector for all heads; on a layer with a
  window ``w`` ONLY, rotary positions (``theta``, all ``d`` dimensions, pairs
  ``(j, j + d/2)``) on ``q`` and ``k``; scores ``x d^-0.5``, causal, and on a
  windowed layer query ``i`` sees keys ``i - w < j <= i``; query head ``n`` reads
  key/value head ``n // (nh / nkv)``; ``W_o``. A layer without a window takes
  NO positional term.
- ``FFN_l``, ``l`` under ``first_dense``: ``(silu(h W_g) * h W_u) W_d``. Above:
  ``s = sigmoid(h W_r)`` over ALL experts; the k chosen are the top k of ``s +
  b`` (``b``: the stored selection bias; ties to the lowest id); ``w_e = scale
  s_e / (sum_chosen s + 1e-20)`` (without ``b``); ``sum_e w_e E_e(h) +
  E_shared(h)``, every ``E`` the gated form at the experts' width.
- the module: with ``g_i = RMSNorm_f(y_last)_i`` and ``t_(i+1)`` the next
  token, ``u_i = W_eh [RMSNorm_e(Emb(t_(i+1))) ; RMSNorm_h(g_i)]``, ``z =
  Block_m(u)`` (a block of the form above: attention over its OWN keys with no
  window and no rotation, the sparse FFN with its own router and experts),
  ``p_i = RMSNorm_m(z_i) W_head``: the draft of token ``i + 2`` is ``argmax
  p_i``.

**The share.** ``first``/``held`` name the experts this chip holds. The
router scores all of them and keeps its top-k; pairs routed to experts held
elsewhere, and what those would add, are left out, here as in the program.

**Assumed** (each also under ``assumed`` in the configuration file): the
residual form, the per-head norm and the rotation by kind are Exaone 4's
(``transformers`` ``models/exaone4``); the router with its selection bias and
the module's form are ``deepseek_v3``'s, whose key names the published config
carries; the module's FFN is sparse. No ``exaone_moe`` modelling code was at
hand: where it differs, the code wins and this file is to be corrected.

**Routing is discontinuous** (``references/nemotron_h.py``'s argument): the
program's bfloat16 moves a selection score by about a hundredth of a row's
spread, so :func:`forward` takes the experts the program chose (``choice``)
where they are a near tie by ``ROUTING_MARGIN``, with the reference's own
``s`` renormalised over that set. A choice further off is not followed.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time, an expert at a time and the head a block of the vocabulary
at a time, on the positions asked for, so that float32 copies of the chip's
9 GB of bf16 weights never exist at once beside the engine.
"""

import functools

import jax
import jax.numpy as jnp

# A position's error is |got - ref|_2 / |ref|_2 over its logits (the stack's,
# and the module's draft logits as rows of their own), of prefill + 16 tokens
# decoded THROUGH THE VERIFY PATH (two columns a step, every draft rejected,
# every step a roll-back) of two requests (prompts 300 and 1,100: both wrap the
# 128-row rings) against this reference's full forward on the same bf16
# weights, following the program's routing where it is a near tie.
#
# LOGITS_TOL, EVERY compared row's limit, between its readings (chip runs of
# this PR, PERF.md section 4 gives them with their runs): the program's worst
# row under it, this reference with its weight matrices at int8 over it at its
# BEST row, the PROGRAM with roll-back off (void rows left visible) far over.
LOGITS_TOL = 1.11e-2

# A program's set of experts is followed where its lowest choice lies less
# than this under the reference's own k-th selection score, in standard
# deviations of the row's scores.
ROUTING_MARGIN = 0.10

# the (layer, position) pairs followed, as a share of all pairs of the forward
MAX_FOLLOWED_SHARE = 0.15

# a row's limit by the dtype the program is served in. float32 (the CPU tests
# and rehearsals): the served path reads 1e-6 at worst; a wrong ring, span,
# weight or choice gives 1e-3 and up
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


def attention(x, lp, hp, window):
    """x (B, T, H) -> (B, T, H). ``window`` > 0: the last ``window`` keys, and
    rotary positions; 0: every key, no positional term."""
    T = x.shape[1]
    q = jnp.einsum("bth,hnd->bntd", x, lp["wq"])
    k = jnp.einsum("bth,hnd->bntd", x, lp["wk"])
    v = jnp.einsum("bth,hnd->bntd", x, lp["wv"])
    q, k = _rms(q, lp["qn"], hp["eps"]), _rms(k, lp["kn"], hp["eps"])
    if window:
        q, k = _rotated(q, hp["theta"]), _rotated(k, hp["theta"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head n reads n // rep
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * q.shape[-1] ** -0.5
    rel = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    keep = (rel >= 0) & (rel < window) if window else rel >= 0
    s = jnp.where(keep[None, None], s, -jnp.inf)
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
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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
# contraction); norms, the router and its bias and the embedding stay
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "s_gate": 0, "s_up": 0, "s_down": 0,
            "w_eh": 0}
# widened (and rounded) an expert at a time; the dense layer's FFN a block of
# DENSE_BLOCK of its width at a time, which is the same sum
_EXPERTS = ("w_gate", "w_up", "w_down", "m_gate", "m_up", "m_down")
DENSE_BLOCK = 2048


def dense_ffn(h, lp, levels=0.0):
    """``(silu(h W_g) * h W_u) W_d`` a block of the width at a time: ``lp``'s
    ``m_*`` are (blocks, H, b) and (blocks, b, H)."""
    wide = lambda x: _rounded(x.astype(jnp.float32), levels, 0)

    def one(acc, b):
        return acc + _gated_ffn(h, wide(lp["m_gate"][b]), wide(lp["m_up"][b]),
                                wide(lp["m_down"][b])), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(lp["m_up"].shape[0]))[0]


def _widened(lp, levels):
    experts = {k: lp[k] for k in _EXPERTS if k in lp}
    lp = dict({k: jnp.asarray(v, jnp.float32) for k, v in lp.items() if k not in experts},
              **experts)
    lp.update({k: _rounded(lp[k], levels, axis) for k, axis in _ROUNDED.items() if k in lp})
    return lp


def layer(x, lp, hp, window, levels=0.0, follow=None):
    """One block: ``(y (B, T, H), route's info or None)``. ``levels`` > 0, the
    lower-precision probe, rounds the weight matrices to that many integer
    levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        lp = _widened(lp, levels)
        h = x + _rms(attention(x, lp, hp, window), lp["attn_ln"], hp["eps"])
        if "gate" not in lp:
            f, info = dense_ffn(h, lp, levels), None
        else:
            r, info = routed(h, lp, hp, levels=levels, follow=follow)
            f = r + _gated_ffn(h, lp["s_gate"], lp["s_up"], lp["s_down"])
        return h + _rms(f, lp["ffn_ln"], hp["eps"]), info


def module_input(g, next_emb, mp, hp, levels=0.0):
    """``u = W_eh [RMSNorm_e(next_emb) ; RMSNorm_h(g)]``."""
    with jax.default_matmul_precision("highest"):
        mp = _widened(mp, levels)
        return jnp.concatenate([_rms(next_emb, mp["enorm"], hp["eps"]),
                                _rms(g, mp["hnorm"], hp["eps"])], axis=-1) @ mp["w_eh"]


def head(h, g, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (H, Vb) -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(g), hp["eps"]) @ _rounded(f32(w), levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda x, lp, lv, follow, window: layer(x, lp, hp, window, lv, follow),
                    static_argnums=4),
            jax.jit(lambda h, g, w, lv: head(h, g, w, hp, lv)),
            jax.jit(lambda g, e, mp, lv: module_input(g, e, mp, hp, lv)),
            jax.jit(lambda h, g: _rms(h, jnp.asarray(g, jnp.float32), hp["eps"])))


VOCAB_BLOCK = 8192  # columns of the head widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0, choice=None):
    """``ids`` (B, T) int32 -> (logits, draft logits, routing), the two (B, T
    - 1 - first, V) float32 of positions ``first .. T - 2``: the last position
    has no next token for the module to read, so it closes the one before it
    and is not returned. ``p``: :func:`from_tree`'s layout. ``choice`` (expert
    layers + 1, B, T', k), T' >= T - 1: the experts the program chose in each
    sparse layer and, LAST, in the module's, followed where they are a near
    tie. ``routing``: ``followed`` / ``refused`` (expert layers + 1, B, T)
    bool, ``reach`` and ``gap`` alike. ``levels`` 127: the same forward with
    its weight matrices rounded to int8."""
    layer_fn, head_fn, input_fn, norm_fn = _jitted(tuple(sorted(hp.items())))
    B, T = ids.shape
    emb = jnp.asarray(p["embed"][ids], jnp.float32)
    none = jnp.full((B, T, hp["top_k"]), -1, jnp.int32)

    def theirs(n):
        if choice is None:
            return none
        c = jnp.asarray(choice[n], jnp.int32)[:, :T]
        return jnp.concatenate([c, none[:, c.shape[1]:]], axis=1)

    infos = []
    x = emb
    for lp, window in zip(p["layers"], p["windows"]):
        x, info = layer_fn(x, lp, jnp.float32(levels),
                           theirs(len(infos)) if "gate" in lp else None, window)
        if info is not None:
            infos.append(info)
    # the module: position i reads the stack's normed output at i and token i + 1
    g = norm_fn(x, p["final_norm"])
    mp = p["module"]
    u = input_fn(g, jnp.roll(emb, -1, axis=1), {k: mp[k] for k in ("enorm", "hnorm", "w_eh")},
                 jnp.float32(levels))
    z, info = layer_fn(u, mp["block"], jnp.float32(levels), theirs(len(infos)), 0)
    infos.append(info)
    keep = slice(first, T - 1)
    V = p["head"].shape[1]
    both = []
    for h, norm in ((x[:, keep], p["final_norm"]), (z[:, keep], mp["final_norm"])):
        both.append(jnp.concatenate(
            [head_fn(h, norm, p["head"][:, v0:v0 + VOCAB_BLOCK], jnp.float32(levels))
             for v0 in range(0, V, VOCAB_BLOCK)], axis=-1))
    return both[0], both[1], {key: jnp.stack([i[key][:, :T - 1] for i in infos])
                              for key in ("followed", "refused", "reach", "gap")}


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys and the sizes the program built (``first``)."""
    pub = config["published"]
    return {"eps": float(pub["rms_norm_eps"]), "top_k": int(pub["num_experts_per_tok"]),
            "routed_scale": float(pub["routed_scaling_factor"]),
            "theta": float(pub["rope_parameters"]["rope_theta"]),
            "first": int(model_cfg.moe_first_expert)}


# ---- the program's parameter tree -> Params -------------------------------
def _block(lt):
    m = lt["attn"]
    out = dict(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"], wv=m["v_proj"]["kernel"],
               wo=m["o_proj"]["kernel"], qn=m["q_norm"]["scale"], kn=m["k_norm"]["scale"],
               attn_ln=lt["attn_norm"]["scale"], ffn_ln=lt["mlp_norm"]["scale"])
    if "mlp" in lt:
        f = lt["mlp"]
        H, F = f["up_proj"]["kernel"].shape
        b = DENSE_BLOCK if F % DENSE_BLOCK == 0 else F
        cols = lambda w: jnp.moveaxis(w.reshape(H, F // b, b), 1, 0)  # (blocks, H, b)
        out.update(m_gate=cols(f["gate_proj"]["kernel"]), m_up=cols(f["up_proj"]["kernel"]),
                   m_down=f["down_proj"]["kernel"].reshape(F // b, b, H))
    else:
        f = lt["moe"]
        out.update(gate=f["gate"], bias=f["e_score_correction_bias"],
                   w_gate=f["experts"]["gate_proj"], w_up=f["experts"]["up_proj"],
                   w_down=f["experts"]["down_proj"],
                   s_gate=f["shared_expert"]["gate_proj"]["kernel"],
                   s_up=f["shared_expert"]["up_proj"]["kernel"],
                   s_down=f["shared_expert"]["down_proj"]["kernel"])
    return out


def from_tree(tree, windows):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``, the
    module under ``mtp``), leaves as they are (bf16 on the chip): the
    reference widens them to float32 a layer and an expert at a time.
    ``windows``: each layer's window (0: every key)."""
    m = tree["mtp"]
    module = dict(enorm=m["enorm"]["scale"], hnorm=m["hnorm"]["scale"],
                  w_eh=m["eh_proj"]["kernel"], block=_block(m["block"]),
                  final_norm=m["final_norm"]["scale"])
    return dict(embed=tree["embed"]["embedding"], windows=tuple(int(w) for w in windows),
                layers=[_block(tree[f"layer_{i}"]) for i in range(len(windows))],
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"],
                module=module)


# ---- the comparison --------------------------------------------------------
def position_errors(got, ref):
    """Per row: |got - ref|_2 / |ref|_2 over the row's logits."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def compare(got, ref, followed=None, refused=None, tol=LOGITS_TOL):
    """``got``/``ref``: (P, V) logits of the compared rows (the stack's and the
    module's alike); ``followed`` / ``refused``: the forward's (expert layer,
    position) pairs, any shape, where the program's routing differed and was
    / was not taken (None: nothing was given to follow). ``ok``: every row's
    error finite and at most ``tol``, and at most ``MAX_FOLLOWED_SHARE`` of
    the pairs followed. Returns also the largest, the smallest and the median
    error, ``routing_margin_rows`` (pairs followed) and
    ``routing_refused_rows`` of ``routing_rows``, and every row's error for
    whoever sets the limits."""
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
