"""The plain reference of ``nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: ONE full
causal forward, no cache, no kernels, no batching of experts (a loop), the
state-space model as the plain recurrence over positions (a ``lax.scan`` of
the one-step equations, no chunking).

Every layer is ONE sublayer, ``u = RMSNorm_l(x)`` (eps 1e-5), ``x <- x +
f_l(u)``; ``logits = RMSNorm_f(x) W_head``, embedding and head untied. ``f``:

- ``mamba2`` (``M``; ``nh`` heads of ``hd``, state ``N``, ``G`` groups, ``W`` 4):
  ``[z ; xBC ; dt] = u W_in`` (no bias); ``xBC_t <- SiLU(sum_j w[:, j]
  xBC_(t-W+1+j) + b_c)`` (depthwise, causal, zeros before position 0) ``= [x_t
  (nh, hd) ; B_t (G, N) ; C_t (G, N)]``, head ``h`` reads group ``h // (nh /
  G)``; ``Delta_t = softplus(dt_t + dt_bias)`` (a head), ``a = -exp(A_log)``
  (a scalar a head); ``S_t = exp(Delta_t a) S_(t-1) + (Delta_t x_t) (x) B_t``;
  ``y_t = S_t C_t + D x_t``; ``y_t <- w_n * g / rms_group(g)``, ``g = y_t *
  SiLU(z_t)``, the mean square over each of the ``G`` groups of ``nh hd / G``
  channels; ``f(u)_t = y_t W_out``. No clamp on Delta.
- ``attention`` (``*``): ``q = u W_q``, ``k, v = u W_k, u W_v``, no bias, NO
  rotary or other positional term, causal ``softmax(q k^T / sqrt(d)) v``,
  grouped queries, ``W_o``.
- ``moe`` (``E``): ``s = sigmoid(u W_r)`` over ALL experts; the k experts are
  the top k of ``s + b`` (``b``: the stored selection bias; ties to the lowest
  id); ``w_e = s_e`` (without ``b``), ``w <- scale * w / (sum w + 1e-20)``;
  expert ``e``: ``(relu(u W_up^e))^2 W_down^e``, no gate matrix, no bias; the
  shared expert the same form at its own width; ``f(u) = sum_e w_e
  expert_e(u) + shared(u)``.
- ``mlp`` (``-``): ``(relu(u W_up))^2 W_down``.

**The share.** ``first``/``held`` name the experts this chip holds. The
router scores all of them and keeps its top-k; pairs routed to experts held
elsewhere, and what those would add, are left out, here as in the program.

**Departures**, each also under ``assumed`` in the configuration file:
``config.json`` carries ``rope_theta`` and ``partial_rotary_factor``, which
the family's modelling code does not read (its attention applies no
positional term): none is applied here. ``time_step_min/max/floor`` shape the
published draw of ``dt_bias`` only. ``n_group`` = ``topk_group`` = 1: no
grouping of experts. The builder had no network: where
``modeling_nemotron_h.py`` differs, the code wins and this file is to be
corrected.

**Routing is discontinuous** (``references/mistral_small_4.py``'s argument):
the program's bfloat16 moves a selection score by about a hundredth of a
row's spread, and with 128 experts a row's k-th and (k+1)-th scores lie that
close in a few rows of a hundred. So :func:`forward` takes the experts the
program chose (``choice``): where the program's set differs from the
reference's own top-k, the reference takes the program's set IF the lowest
of the program's choices lies less than ``ROUTING_MARGIN`` (in standard
deviations of the row's selection scores ``s + b``) under the reference's own
k-th score; its weights stay the reference's own ``s``, renormalised over
that set. A choice further off is not followed: the position then differs by
an expert's output and fails.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time, an expert at a time and the head a block of the vocabulary
at a time, on the positions asked for, so that float32 copies of the chip's
10.6 GB of bf16 weights never exist at once beside the engine.
"""

import functools

import jax
import jax.numpy as jnp

# A position's error is |got - ref|_2 / |ref|_2 over its 65,536 logits, of
# prefill + 16 decode steps of two requests (prompts 300 and 1,100) through the
# scheduler's pool (bf16 weights, activations, rows and state at rest; float32
# state update, softmax, router and norms) against this reference's full
# forward on the same bf16 weights, following the program's routing where it
# is a near tie. The weights are the benchmark's draw (``jobs/
# serve_nemotron_h.py: nemotron_params``: every sublayer's last matrix
# centred; uncentred, one shared vector is most of every logit row and every
# reading is a third of what it is here).
#
# LOGITS_TOL, EVERY compared position's limit, between its two readings (my
# chip runs, PR 39, 13 runs of 34 positions, each with a seed of its own):
# the program reads 0.0173-0.0187 at its WORST position (a run's median 0.0159-0.0163:
# 16 bf16 sublayers), this reference with its weight matrices at int8 0.0579-0.0636
# at its BEST position: a factor of 1.50 above the one and 2.07 under the
# other. The PROGRAM with its Mamba-2 state rounded to int8 between syncs (one
# scale a head's 64 x 128) reads 0.0356-0.0481 at its worst, 13 to 22 of 34
# positions over the limit; the PROGRAM without its selection bias 0.50-0.73.
LOGITS_TOL = 2.8e-2

# A program's set of experts is followed where its lowest choice lies less
# than this under the reference's own k-th selection score, in standard
# deviations of the row's scores. The farthest choice the bf16 program made
# read 0.032-0.052 in those runs (0.042-0.072 with the int8 state).
ROUTING_MARGIN = 0.10

# The (layer, position) pairs followed, as a share of all pairs of the
# forward: 679 to 786 of 10,024 (6.8 to 7.8%) in those runs. It guards the
# margin, not the precision: a router that scores or selects wrongly differs
# in most pairs (the selection bias dropped: 4,024 to 7,664 pairs REFUSED).
MAX_FOLLOWED_SHARE = 0.15

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals): the served path reads 1e-6 at worst; a wrong state,
# span, weight or choice gives 1e-3 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def int8_state(x):
    """A state leaf as a plain int8 tier would hold it: ONE scale for the
    leaf's last two axes (a head's ``hd x N`` state of a slot; a slot's ``W -
    1`` window inputs), 127 symmetric levels of the block's largest
    magnitude, in ``x``'s dtype."""
    return _rounded(x.astype(jnp.float32), 127.0, (-2, -1)).astype(x.dtype)


def mamba2(u, lp, hp):
    """u (B, T, H) -> (B, T, H): the recurrence one position at a time from a
    zero state."""
    B, T, _ = u.shape
    nh, hd, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], hp["ssm_groups"]
    di = nh * hd
    cc, W = lp["conv"].shape
    zxd = u @ lp["w_in"]
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


def attention(u, lp):
    """u (B, T, H) -> (B, T, H): causal grouped-query attention, no positions."""
    B, T, _ = u.shape
    q = jnp.einsum("bth,hnd->bntd", u, lp["wq"])
    k = jnp.einsum("bth,hnd->bntd", u, lp["wk"])
    v = jnp.einsum("bth,hnd->bntd", u, lp["wv"])
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head i reads i // rep
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def _relu2_ffn(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def route(u, lp, hp, follow=None):
    """Router over ALL experts: (weights (B, T, E) zero outside the chosen k,
    info). ``follow`` (B, T, k) int32: the program's choice (-1: none given);
    a set that differs from the reference's own top-k is taken where it is a
    near tie (``ROUTING_MARGIN``, module docstring), with the reference's own
    ``s``. ``info``: ``gap`` (B, T) between the own k-th and (k+1)-th
    selection score, ``followed`` / ``refused`` (B, T) bool, ``reach`` (B,
    T): how far under the own k-th score its lowest choice lay (0 where the
    sets agree). ``gap`` and ``reach`` in standard deviations of the row's
    selection scores."""
    s = jax.nn.sigmoid(u @ lp["gate"])
    c = s + lp["bias"]  # the selection scores
    k, E = hp["top_k"], s.shape[-1]
    # ties to the lowest id: top_k is stable
    top_c, top_i = jax.lax.top_k(c, k + 1)
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
    """The routed experts' part for the experts ``first .. first + held``
    that ``lp`` holds (all of ``lp``'s by default), one expert at a time;
    and :func:`route`'s info."""
    first = hp["first"] if first is None else first
    held = lp["w_up"].shape[0] if held is None else held
    w, info = route(u, lp, hp, follow)
    wide = lambda x: _rounded(x.astype(jnp.float32), levels, 0)

    def one(acc, e):
        y = _relu2_ffn(u, wide(lp["w_up"][e]), wide(lp["w_down"][e]))
        return acc + jnp.take(w, first + e, axis=-1)[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    return out, info


def shared(u, lp):
    return _relu2_ffn(u, lp["s_up"], lp["s_down"])


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, the router and its bias, the convolution, A, D, dt_bias
# and the embedding stay as they are
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "w_in": 0, "w_out": 0,
            "s_up": 0, "s_down": 0, "m_up": 0, "m_down": 0}
_EXPERTS = ("w_up", "w_down")  # widened (and rounded) an expert at a time


def layer(h, lp, hp, kind, levels=0.0, follow=None):
    """One block: ``(h (B, T, H), route's info or None)``. ``levels`` > 0, the
    lower-precision probe, rounds the weight matrices to that many integer
    levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        experts = {k: lp[k] for k in _EXPERTS if k in lp}
        lp = dict({k: jnp.asarray(v, jnp.float32) for k, v in lp.items() if k not in experts},
                  **experts)
        lp.update({k: _rounded(lp[k], levels, axis) for k, axis in _ROUNDED.items() if k in lp})
        u = _rms(h, lp["ln"], hp["eps"])
        if kind == "mamba2":
            return h + mamba2(u, lp, hp), None
        if kind == "attention":
            return h + attention(u, lp), None
        if kind == "mlp":
            return h + _relu2_ffn(u, lp["m_up"], lp["m_down"]), None
        r, info = routed(u, lp, hp, levels=levels, follow=follow)
        return h + r + shared(u, lp), info


def head(h, g, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (H, Vb) -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(g), hp["eps"]) @ _rounded(f32(w), levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda h, lp, lv, follow, kind: layer(h, lp, hp, kind, lv, follow),
                    static_argnums=4),
            jax.jit(lambda h, g, w, lv: head(h, g, w, hp, lv)))


VOCAB_BLOCK = 8192  # columns of the head widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0, choice=None):
    """``ids`` (B, T) int32 -> (logits (B, T - first, V) float32 of positions
    ``first ..``, routing). ``p``: :func:`from_tree`'s layout. One compiled
    program a kind, run a layer at a time; the head a block of the vocabulary
    at a time. ``choice`` (expert layers, B, T, k): the experts the program
    chose in each ``moe`` layer, followed where they are a near tie (module
    docstring). ``routing``: ``followed`` / ``refused`` (expert layers, B, T)
    bool, ``reach`` and ``gap`` (expert layers, B, T). ``levels`` 127: the
    same forward with its weight matrices rounded to int8, the nearest
    precision below the configuration's bf16."""
    layer_fn, head_fn = _jitted(tuple(sorted(hp.items())))
    h = jnp.asarray(p["embed"][ids], jnp.float32)
    none = jnp.full(ids.shape + (hp["top_k"], ), -1, jnp.int32)
    infos = []
    for kind, lp in zip(p["layer_types"], p["layers"]):
        follow = None
        if kind == "moe":
            follow = none if choice is None else jnp.asarray(choice[len(infos)], jnp.int32)
        h, info = layer_fn(h, lp, jnp.float32(levels), follow, kind)
        if info is not None:
            infos.append(info)
    h = h[:, first:]
    V = p["head"].shape[1]
    logits = jnp.concatenate([head_fn(h, p["final_norm"], p["head"][:, v0:v0 + VOCAB_BLOCK],
                                      jnp.float32(levels))
                              for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)
    empty = jnp.zeros((0, ) + ids.shape)
    return logits, {key: (jnp.stack([i[key] for i in infos]) if infos else empty)
                    for key in ("followed", "refused", "reach", "gap")}


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys and the sizes the program built (``first``)."""
    pub = config["published"]
    return {"eps": float(pub["layer_norm_epsilon"]), "top_k": int(pub["num_experts_per_tok"]),
            "routed_scale": float(pub["routed_scaling_factor"]),
            "ssm_heads": int(pub["mamba_num_heads"]), "ssm_head_dim": int(pub["mamba_head_dim"]),
            "ssm_state": int(pub["ssm_state_size"]), "ssm_groups": int(pub["n_groups"]),
            "first": int(model_cfg.moe_first_expert)}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, layer_types):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer and an expert at a time."""
    def one(lt, kind):
        out = dict(ln=lt["norm"]["scale"])
        if kind == "mamba2":
            m = lt["mamba2"]
            out.update(w_in=m["in_proj"]["kernel"], conv=m["conv"], conv_b=m["conv_bias"],
                       dt_bias=m["dt_bias"], a_log=m["A_log"], d=m["D"],
                       norm_w=m["norm"]["scale"], w_out=m["out_proj"]["kernel"])
        elif kind == "attention":
            m = lt["attn"]
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], wo=m["o_proj"]["kernel"])
        elif kind == "mlp":
            out.update(m_up=lt["mlp"]["up_proj"]["kernel"],
                       m_down=lt["mlp"]["down_proj"]["kernel"])
        else:
            m = lt["moe"]
            out.update(gate=m["gate"], bias=m["e_score_correction_bias"],
                       w_up=m["experts"]["up_proj"], w_down=m["experts"]["down_proj"],
                       s_up=m["shared_expert"]["up_proj"]["kernel"],
                       s_down=m["shared_expert"]["down_proj"]["kernel"])
        return out

    return dict(embed=tree["embed"]["embedding"], layer_types=tuple(layer_types),
                layers=[one(tree[f"layer_{i}"], kind) for i, kind in enumerate(layer_types)],
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"])


def without_selection_bias(p):
    """``p`` with every expert layer's selection bias at zero: a router that
    chooses by ``s`` alone."""
    return dict(p, layers=[dict(lp, bias=jnp.zeros_like(lp["bias"])) if "bias" in lp else lp
                           for lp in p["layers"]])


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
