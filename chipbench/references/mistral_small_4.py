"""The plain reference of ``mistral4`` (Mistral-Small-4-119B-2603's language
model): latent attention and routed experts with a shared one, in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``. Full
(non-absorbed) attention, no cache, a loop over experts, no kernels.

Per layer, pre-norm, RMSNorm, no biases:

    a    = RMSNorm(h)
    c_q  = RMSNorm(a W_qa)
    q_i  = c_q W_qb,i -> [q_nope_i ; q_rope_i]                      i = 1..heads
    [c_kv ; k_r] = a W_kva ; c_kv = RMSNorm(c_kv) ; k_r = RoPE(k_r)   (ONE k_r for all heads)
    [k_nope_i ; v_i] = c_kv W_kvb,i
    s_ts,i = (q_nope_i . k_nope_i + RoPE(q_rope_i) . k_r) * scale * g(t) ; causal softmax
    h    = h + concat_i(sum_s p_ts,i v_s,i) W_o
    m    = RMSNorm(h)
    p    = softmax(m W_g) over ALL experts ; T = top-k(p) ; w_e = p_e / sum_{e in T} p_e
    h    = h + routed_scale * sum_{e in T, e held} w_e E_e(m) + S(m)
    logits = RMSNorm(h_L) W_head              E, S: x -> (silu(x W1) * (x W3)) W2

RoPE: YaRN frequencies (``rope_frequencies``), dimensions ``2j`` and
``2j + 1`` rotating together; cos and sin times m(mscale) / m(mscale_all_dim)
with m(x) = 0.1 x ln(factor) + 1; ``scale`` = qk_head_dim^-1/2 m(mscale_all_dim)^2;
g(t) = 1 + beta ln(1 + floor(t / original_max_position_embeddings)).

**The share.** ``first``/``held`` name the experts this chip holds, ids
``first .. first + held``. The router scores all of them and keeps its
top-k; pairs routed to experts held elsewhere, and what those experts would
add, are left out, here as in the program. Nothing stands in for them.

**Departures**, each also under ``assumed`` in the configuration file: the
scoring function (softmax, then top-k renormalised), the m^2 rule of
``scale``, g(t) and the pairing are taken from the published family code
(``deepseek_v3``/``mistral4`` in transformers), not from ``config.json``.

**Routing is discontinuous.** The program computes in bfloat16, which at
these widths and this initialisation moves the final hidden state by 2 to 3%
(every layer's output is as large as the residual stream, so each layer's
rounding stays) and a router logit by about a hundredth of the spread of a
row's router logits. With 128 experts a row's k-th and (k+1)-th router
logits lie that close in about one row in fourteen (6 to 8% of the (layer,
position) pairs flipped on the chip), and such a row picks another expert in
the program than here. The two results then differ by one expert's weighted
output, ten times what rounding does; later layers route differently after
it, and later positions attend to it. That is not an error of arithmetic. So the program returns, beside the logits, the experts every
row chose in every layer (``handle.result_choice()``), and :func:`forward`
takes them as ``choice``: where the program's set differs from the
reference's own top-k, the reference takes the program's set IF the lowest
of the program's choices lies less than ``ROUTING_MARGIN`` (in standard
deviations of the row's router logits) under the reference's own k-th
logit, a near tie; its weights stay the reference's own probabilities,
renormalised over that set. A choice further off is not followed, the
position then differs by an expert's output and fails. The comparison
(:func:`compare`) holds EVERY compared position to ``LOGITS_TOL``, reports
the (layer, position) pairs followed (``routing_margin_rows``) and fails if
they are more than ``MAX_FOLLOWED_SHARE`` of all pairs of the forward.
Nothing else of the mathematics is left out.

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time and an expert at a time (:func:`forward`), so that float32
copies of the chip's 10.85 GB of bf16 weights never exist at once.
"""

import functools
import math

import jax
import jax.numpy as jnp

# Logits of prefill + 16 decode steps of two requests through the scheduler's
# latent pool (bf16 weights, activations and latent rows, fp32 softmax and
# combine) against this reference's full forward on the same bf16 weights,
# the reference following the program's routing where it is a near tie.
# A position's error is |got - ref|_2 / |ref|_2 over its logits (the largest
# difference over the largest logit, which PR 26's first limits used, is the
# same quantity with the noise of two extreme values on top; it is reported
# as ``max_errors``).
#
# LOGITS_TOL, EVERY compared position's limit, between its two readings (my
# chip runs, PR 26, 18 runs of 34 positions, each with a seed of its own).
# The program in bf16: 0.0174 to 0.0316, a run's median 0.0227 to 0.0238. The
# reference at the nearest precision below the configuration's bf16 (weight
# matrices and latent rows rounded to int8), compared with itself: 0.0485 at
# its best position, a run's median 0.0642 to 0.0698: not correct at every
# position of every run. 0.04 leaves a factor of 1.27 below and 1.21 above.
# Reported beside it: the reference with its latent rows ALONE at int8 reads
# 0.0221 to 0.0438, as much as bf16 arithmetic itself brings (it passes in 11
# of the 18 runs), so the reference alone cannot tell an int8 pool; the
# PROGRAM reading a pool whose rows are rounded to int8 between syncs can
# (``jobs/serve_ref.py``): its positions past the first sync read a median of
# 0.038 to 0.041 and a largest of 0.044 to 0.149, at least 7 of 34 positions
# over the limit in each of the 18 runs.
LOGITS_TOL = 4.0e-2

# A program's set of experts is followed where its lowest choice lies less
# than this under the reference's own k-th router logit, in standard
# deviations of the row's router logits. The farthest choice the bf16 program
# made read 0.058 to 0.101 in those runs (0.133 with the int8 pool); at 0.05,
# the first value tried, 4 of 187 differing pairs of one run were refused.
ROUTING_MARGIN = 0.15

# The (layer, position) pairs followed, as a share of all pairs of the
# forward: 151 to 207 of 2,568 (5.9 to 8.1%) in those runs, 182 to 229 with
# the int8 pool. It guards the margin, not the precision: a router that
# scores wrongly differs in most pairs, near ties or not.
MAX_FOLLOWED_SHARE = 0.15

# a position's limit by the dtype the program is served in; float32 (the
# CPU tests and rehearsals) agrees with the reference to 2e-7
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-4}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(dim, rope):
    """YaRN (arXiv:2309.00071): a pair that turns more than ``beta_fast``
    times within the original context keeps its frequency, one that turns
    fewer than ``beta_slow`` times is divided by ``factor``, linear between."""
    theta, factor = rope["rope_theta"], rope["factor"]
    freq = [theta ** (-2.0 * j / dim) for j in range(dim // 2)]
    if factor <= 1:
        return jnp.asarray(freq, jnp.float32)
    orig = rope["original_max_position_embeddings"]
    corr = lambda turns: dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    span = max(high - low, 1e-3)
    ramp = [min(max((j - low) / span, 0.0), 1.0) for j in range(dim // 2)]
    return jnp.asarray([f / factor * r + f * (1 - r) for f, r in zip(freq, ramp)], jnp.float32)


def _rotate(x, pos, hp):
    """x (..., T, d) at positions ``pos`` (T,). Interleaved pairing: dims 2j
    and 2j+1 form pair j. ``hp["interleave"]`` False pairs j with j + d/2."""
    rope = hp["rope"]
    d = x.shape[-1]
    mag = _mscale(rope["factor"], rope["mscale"]) / _mscale(rope["factor"], rope["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * rope_frequencies(d, rope)[None, :]  # (T, d/2)
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    if hp.get("interleave", True):
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def int8_rows(x):
    """Latent rows (..., width) as an int8 pool would hold them: each row
    rounded to 127 symmetric levels of its largest magnitude, in ``x``'s dtype."""
    return _rounded(x.astype(jnp.float32), 127.0, -1).astype(x.dtype)


def attention(a, lp, hp, levels=0.0):
    """a (B, T, H) normalised input -> (B, T, H), full causal attention over
    the expanded per-head keys and values. ``levels`` > 0 (the lower-precision
    probe) rounds each position's [c_kv ; k_r] row to that many symmetric
    integer levels (127: an int8 pool) before it is used."""
    B, T, _ = a.shape
    rope = hp["rope"]
    nope = hp["qk_nope_head_dim"]
    rank = lp["kva_norm"].shape[0]
    pos = jnp.arange(T)
    c_q = _rms(a @ lp["wqa"], lp["qa_norm"], hp["eps"])
    q = jnp.einsum("btr,rnd->bntd", c_q, lp["wqb"])
    kva = a @ lp["wkva"]
    c_kv = _rms(kva[..., :rank], lp["kva_norm"], hp["eps"])
    k_r = _rotate(kva[..., rank:], pos, hp)  # (B, T, rope): one for all heads
    row = _rounded(jnp.concatenate([c_kv, k_r], axis=-1), levels, -1)
    c_kv, k_r = row[..., :rank], row[..., rank:]
    kv = jnp.einsum("btr,rnd->bntd", c_kv, lp["wkvb"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], pos, hp)
    scale = q.shape[-1] ** -0.5 * _mscale(rope["factor"], rope["mscale_all_dim"]) ** 2
    g = 1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(
        jnp.floor(pos.astype(jnp.float32) / rope["original_max_position_embeddings"]))
    s = (jnp.einsum("bnqd,bnkd->bnqk", q_nope, k_nope)
         + jnp.einsum("bnqd,bkd->bnqk", q_rope, k_r)) * scale * g[None, None, :, None]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def _ffn(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(m, lp, hp, follow=None):
    """Router over ALL experts: (weights (B, T, E) zero outside the chosen k,
    info). ``follow`` (B, T, k) int32: the program's choice (-1: none given);
    a set that differs from the reference's own top-k is taken where it is a
    near tie (``ROUTING_MARGIN``, module docstring), with the reference's
    own probabilities. ``info``: ``gap`` (B, T) between the own k-th and
    (k+1)-th router logit, ``followed`` / ``refused`` (B, T) bool: the
    program's set differed and was / was not taken, ``reach`` (B, T): how far
    under the own k-th logit its lowest choice lay (0 where the sets agree).
    ``gap`` and ``reach`` in standard deviations of the row's router logits."""
    z = m @ lp["gate"]
    p = jax.nn.softmax(z, axis=-1)
    k, E = hp["top_k"], z.shape[-1]
    top_z, top_i = jax.lax.top_k(z, k + 1)
    std = jnp.std(z, axis=-1)
    chosen = jnp.sum(jax.nn.one_hot(top_i[..., :k], E, dtype=p.dtype), axis=-2)
    followed = refused = jnp.zeros(z.shape[:-1], bool)
    reach = jnp.zeros(z.shape[:-1], z.dtype)
    if follow is not None:
        theirs = jnp.sum(jax.nn.one_hot(follow, E, dtype=p.dtype), axis=-2)  # -1: no expert
        differs = jnp.any(theirs != chosen, axis=-1) & (follow[..., 0] >= 0)
        lowest = jnp.min(jnp.take_along_axis(z, jnp.maximum(follow, 0), axis=-1), axis=-1)
        reach = jnp.where(differs, (top_z[..., k - 1] - lowest) / std, 0.0)
        followed = differs & (reach < ROUTING_MARGIN)
        refused = differs & ~followed
        chosen = jnp.where(followed[..., None], theirs, chosen)
    w = p * chosen
    if hp.get("renormalise", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * hp["routed_scale"], {"gap": (top_z[..., k - 1] - top_z[..., k]) / std,
                                    "followed": followed, "refused": refused, "reach": reach}


def routed(m, lp, hp, first=None, held=None, levels=0.0, follow=None):
    """The routed experts' part for the experts ``first .. first + held``
    that ``lp`` holds (all of ``lp``'s by default), one expert at a time;
    and :func:`route`'s info."""
    first = hp["first"] if first is None else first
    held = lp["w1"].shape[0] if held is None else held
    w, info = route(m, lp, hp, follow)
    wide = lambda x: _rounded(x.astype(jnp.float32), levels, 0)

    def one(acc, e):
        y = _ffn(m, wide(lp["w1"][e]), wide(lp["w3"][e]), wide(lp["w2"][e]))
        return acc + jnp.take(w, first + e, axis=-1)[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    return out, info


def shared(m, lp):
    return _ffn(m, lp["s1"], lp["s3"], lp["s2"])


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, the router and the embedding stay as they are
_ROUNDED = {"wqa": 0, "wqb": 0, "wkva": 0, "wkvb": 0, "wo": (0, 1), "s1": 0, "s3": 0, "s2": 0}


def layer(h, lp, hp, levels=0.0, pool_levels=0.0, follow=None):
    """One block: (h (B, T, H), :func:`route`'s info). The lower-precision
    probes: ``levels`` > 0 rounds the weight matrices, ``pool_levels`` > 0
    each position's latent row, to that many integer levels (127 is int8, the
    nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        experts = {k: lp[k] for k in ("w1", "w3", "w2")}  # widened an expert at a time
        lp = dict({k: jnp.asarray(v, jnp.float32) for k, v in lp.items() if k not in experts},
                  **experts)
        lp.update({k: _rounded(lp[k], levels, axis) for k, axis in _ROUNDED.items()})
        h = h + attention(_rms(h, lp["ln1"], hp["eps"]), lp, hp, pool_levels)
        m = _rms(h, lp["ln2"], hp["eps"])
        r, info = routed(m, lp, hp, levels=levels, follow=follow)
        return h + r + shared(m, lp), info


def head(h, p, hp, levels=0.0):
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(p["final_norm"]), hp["eps"]) @ _rounded(f32(p["head"]), levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = _thaw(hp_key)
    return (jax.jit(lambda h, lp, lv, plv, follow: layer(h, lp, hp, lv, plv, follow)),
            jax.jit(lambda h, p, lv: head(h, p, hp, lv)))


def _freeze(x):
    return tuple(sorted((k, _freeze(v)) for k, v in x.items())) if isinstance(x, dict) else x


def _thaw(x):
    return {k: _thaw(v) for k, v in x} if isinstance(x, tuple) else x


def forward(p, ids, hp, levels=0.0, pool_levels=None, choice=None):
    """``ids`` (B, T) int32 -> (logits (B, T, V) float32, routing). ``p``:
    :func:`from_tree`'s layout. One compiled program a layer shape, run a
    layer at a time. ``choice`` (L, B, T, k): the experts the program chose,
    followed where they are a near tie (module docstring). ``routing``:
    ``gap`` (B, T) each position's smallest own router gap over the layers,
    ``followed`` / ``refused`` (L, B, T) bool, ``reach`` (L, B, T).
    ``levels`` 127: the same
    forward with its weight matrices, and ``pool_levels`` (the same unless
    given) its latent rows, rounded to int8, the nearest precision below the
    configuration's bf16."""
    layer_fn, head_fn = _jitted(_freeze(hp))
    pool_levels = levels if pool_levels is None else pool_levels
    h = jnp.asarray(p["embed"], jnp.float32)[ids]
    none = jnp.full(ids.shape + (hp["top_k"], ), -1, jnp.int32)
    infos = []
    for i, lp in enumerate(p["layers"]):
        follow = none if choice is None else jnp.asarray(choice[i], jnp.int32)
        h, info = layer_fn(h, lp, jnp.float32(levels), jnp.float32(pool_levels), follow)
        infos.append(info)
    logits = head_fn(h, {"final_norm": p["final_norm"], "head": p["head"]}, jnp.float32(levels))
    return logits, {"gap": functools.reduce(jnp.minimum, [i["gap"] for i in infos]),
                    **{key: jnp.stack([i[key] for i in infos])
                       for key in ("followed", "refused", "reach")}}


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys and the sizes the program built (``first``)."""
    pub = config["published"]
    return {"eps": pub["rms_norm_eps"], "qk_nope_head_dim": pub["qk_nope_head_dim"],
            "top_k": pub["num_experts_per_tok"], "routed_scale": float(pub["routed_scaling_factor"]),
            "renormalise": bool(pub["norm_topk_prob"]), "interleave": bool(pub["rope_interleave"]),
            "first": int(model_cfg.moe_first_expert),
            "rope": {k: float(pub["rope_parameters"][k]) for k in (
                "rope_theta", "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
                "original_max_position_embeddings", "llama_4_scaling_beta")}}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, num_layers):
    """The serving engine's tree (flax names; unrolled ``layer_<i>`` or
    stacked ``layers``), leaves as they are (bf16 on the chip): the reference
    widens them to float32 a layer and an expert at a time."""
    def one(lt):
        at, moe = lt["attn"], lt["moe"]
        return dict(
            ln1=lt["attn_norm"]["scale"], ln2=lt["mlp_norm"]["scale"],
            wqa=at["q_a_proj"]["kernel"], qa_norm=at["q_a_norm"]["scale"],
            wqb=at["q_b_proj"]["kernel"], wkva=at["kv_a_proj"]["kernel"],
            kva_norm=at["kv_a_norm"]["scale"], wkvb=at["kv_b_proj"], wo=at["o_proj"]["kernel"],
            gate=moe["gate"], w1=moe["experts"]["gate_proj"], w3=moe["experts"]["up_proj"],
            w2=moe["experts"]["down_proj"], s1=moe["shared_expert"]["gate_proj"]["kernel"],
            s3=moe["shared_expert"]["up_proj"]["kernel"],
            s2=moe["shared_expert"]["down_proj"]["kernel"])

    if "layers" in tree:
        layers = [one(jax.tree_util.tree_map(lambda x, i=i: x[i], tree["layers"]))
                  for i in range(num_layers)]
    else:
        layers = [one(tree[f"layer_{i}"]) for i in range(num_layers)]
    return dict(embed=tree["embed"]["embedding"], layers=layers,
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"])


# ---- the comparison --------------------------------------------------------
def position_errors(got, ref):
    """Per position: |got - ref|_2 / |ref|_2 over the position's logits."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def max_errors(got, ref):
    """Per position: largest absolute difference over the largest absolute
    reference logit (reported beside :func:`position_errors`)."""
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.max(jnp.abs(got - ref), axis=-1) / jnp.max(jnp.abs(ref), axis=-1)


def compare(got, ref, followed=None, refused=None, tol=LOGITS_TOL):
    """``got``/``ref``: (P, V) logits of the compared positions; ``followed``
    / ``refused``: the forward's (layer, position) pairs, any shape, where the
    program's routing differed and was / was not taken (None: nothing was
    given to follow). ``ok``: every position's error finite and at most
    ``tol``, and at most ``MAX_FOLLOWED_SHARE`` of the pairs followed.
    Returns also the largest and the median error, ``routing_margin_rows``
    (pairs followed) and ``routing_refused_rows`` of ``routing_rows``, and
    every position's two errors for whoever sets the limits. ``tol``: float32
    tests pass a tight one."""
    err = position_errors(got, ref)
    n_followed = 0 if followed is None else int(jnp.sum(followed))
    n_pairs = 0 if followed is None else int(jnp.size(followed))
    ok = bool(jnp.all(err <= tol)) and n_followed <= MAX_FOLLOWED_SHARE * n_pairs  # NaN is over
    return {"ok": ok, "error": float(jnp.max(jnp.nan_to_num(err, nan=jnp.inf))),
            "median_error": float(jnp.median(err)), "rows": int(err.shape[0]),
            "routing_margin_rows": n_followed, "routing_rows": n_pairs,
            "routing_refused_rows": 0 if refused is None else int(jnp.sum(refused)),
            "errors": [round(float(e), 5) for e in err],
            "max_errors": [round(float(e), 5) for e in max_errors(got, ref)]}
