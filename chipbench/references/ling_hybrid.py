"""The plain reference of ``bailing_hybrid`` (inclusionAI Ling-3.0-flash) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``: ONE full
causal forward, no cache, no kernels, no chunks (the recurrence runs token by
token), no batching of experts (a loop).

Every block is ``h = x + Mixer_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``
(RMSNorm ``x / rms(x) * g``, eps 1e-6, no bias anywhere); ``logits =
RMSNorm_f(x) W_head`` (untied). ``Mixer``:

- ``linear_attention``, Kimi delta attention (arXiv:2510.26692), ``n`` heads
  of ``dk = dv``: ``q~, k~, v~ = a W_q, a W_k, a W_v``; each through a causal
  depthwise convolution of 4 taps (zeros before position 0) and SiLU; ``q = q
  / |q| * dk^-1/2``, ``k = k / |k|`` a head (``|x| = sqrt(sum x^2 + 1e-6)``);
  ``beta = sigmoid(a W_b)`` a head; the log decay ONE A KEY CHANNEL, ``g = lb
  * sigmoid(exp(A_log_h) * (a W_f + dt_bias))`` with ``lb`` -5, so ``g`` in
  (-5, 0); then, a token at a time from ``S = 0`` (``dk x dv``),

      S_t = (I - beta k k^T) Diag(e^g) S_(t-1) + beta k v^T ;  o_t = S_t^T q_t

  and ``y = [RMSNorm_dv(o) * sigmoid(a W_g)] W_o``.
- ``full_attention``, latent attention with ONE query projection: ``q_i = a
  W_q,i = [q_nope_i ; q_rope_i]``; ``[c_kv ; k_r] = a W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``[k_nope_i ; v_i] = c_kv W_kvb,i``; rotary positions on
  ``q_rope_i`` and on ``k_r`` (one for all heads), dimensions ``2j`` and ``2j
  + 1`` together (``rope_interleave``), no scaling; ``s = (q_nope . k_nope +
  q_rope . k_r) / sqrt(nope + rope)``, causal softmax, ``o_i = sum p v_i``;
  ``y = [sigmoid(a W_gate)_i o_i]_i W_o``, one gate a head. The EXPANDED form:
  per-head keys and values, no absorbed products.

``FFN`` of the leading dense layers: ``(silu(x W_1) * x W_3) W_2``. Above
them: ``s = sigmoid(x W_r)`` over ALL experts, ``c = s + b`` (``b``: the stored
selection bias); group ``j`` is experts ``[j E / G, (j + 1) E / G)``, its score
the sum of its two largest ``c``; the ``topk_group`` groups of the largest
score are kept (ties to the lowest); the k experts are the largest ``c`` among
the kept groups' experts; ``w_e = scale * s_e / (sum_chosen s + 1e-20)``
WITHOUT the bias; ``sum_e w_e Expert_e(x) + Expert_shared(x)``, each the same
gated form at its width.

**The share.** ``lp`` holds the experts ``first .. first + held`` of the
router's ``E``: the router scores and chooses over ALL of them, the loop runs
over the held ones, and what the absent experts would have added is left
out, here as in the program. The shared expert is what every chip computes
alike. The head is the held rows of the vocabulary.

**What is read and what is refused**, each also under ``assumed`` /
``not_served`` in the configuration file: ``use_qk_norm`` is read as the norms
above (the L2 norm of q and k in a KDA layer, the latent's RMSNorm) and no
further norm a head; ``group_norm_size`` 1 as the output norm over ONE head's
values; the drafting module and the clamped activations of the top layers are
not here (no held layer has a non-zero limit). The builder had no network:
where ``modeling_bailing_hybrid.py`` differs, the code wins and this file is
to be corrected.

**Routing is discontinuous** (``references/mistral_small_4.py``'s argument),
twice here: a near tie between two groups' scores moves a row to other
experts altogether. :func:`forward` takes the experts the program chose
(``choice``) and follows, first, its set of GROUPS where the lowest of them
lies less than ``GROUP_ROUTING_MARGIN`` (in standard deviations of the row's
group scores) under the reference's own ``topk_group``-th, then its experts
where its lowest choice lies less than ``ROUTING_MARGIN`` (of the row's
selection scores) under the reference's own k-th among the groups kept; the
weights stay the reference's own ``s``.

**Controls**, each the reference against itself, each has to come out NOT ok:
``levels`` 127 (its weight matrices at int8, the precision below bf16);
``head_decay`` (every channel's log decay replaced by its head's mean: a gated
delta rule with one decay a head, not KDA); ``group_limit=False`` (the k
largest ``c`` of all experts, no groups). The job adds a control of the
PROGRAM (its state leaves zeroed between syncs).

The reference takes its own parameter layout; :func:`from_tree` translates
the program's tree and is the only place that knows its names. It runs a
layer at a time, an expert at a time and the head a block of the vocabulary
at a time, on the positions asked for, so that float32 copies of the chip's
10.5 GB of bf16 weights never exist at once beside the engine.
"""

import functools

import jax
import jax.numpy as jnp

# A position's error is |got - ref|_2 / |ref|_2 over its 39,296 logits, of
# prefill + 16 decode steps of two requests (prompts 300 and 1,100) through
# the scheduler's pool (bf16 weights, activations, latent rows, state and
# window at rest; float32 state arithmetic, softmax, router and norms) against
# this reference's full forward on the same bf16 weights, following the
# program's routing where it is a near tie. The weights are the benchmark's
# draw (``jobs/serve_ling_hybrid.py: ling_params``).
#
# LOGITS_TOL, EVERY compared position's limit, between its two readings (my
# chip runs, PR 54, 14 runs of 34 positions, each with a seed of its own, 192
# slots): the program reads 0.0301-0.0332 at its WORST position (a run's
# median 0.027-0.029: 14 bf16 sublayers at hidden 2,560), this reference with
# its weight matrices at int8 0.1080-0.1177 at its BEST position (0.15-0.22 at
# its worst): a factor of 1.81 above the one and 1.80 under the other. The
# controls: every channel's decay at its head's mean 0.86-0.94 at its best
# position, the group limit off 0.15-0.20 at its best, the program with its
# state zeroed between syncs 1.36-1.38 at its median.
LOGITS_TOL = 6.0e-2

# A program's set (of groups, of experts) is followed where its lowest member
# lies less than this under the reference's own last-kept score, in standard
# deviations of the row's scores (``references/lfm2_moe.py``'s margin: the
# stacks are of a depth). Every refusal seen was at the group boundary.
ROUTING_MARGIN = 0.20

# ... and its set of GROUPS where the lowest of them lies less than this under
# the reference's own last-kept group, in standard deviations of the row's 8
# GROUP scores. A group's score is the sum of the two largest of 64 scores:
# the 8 of a row lie close together (their deviation is ~0.05 where the 512
# selection scores' is ~0.2), so what bf16 hidden states move a score by is a
# tenth of a deviation HERE where it is a fiftieth there. At 0.20 (my chip
# runs, PR 54, six seeds) 4 to 13 of a run's ~1,600 differing pairs lay
# further and were refused, and the ONE refused pair that fell on a compared
# position read 0.058 where that run's other 33 read 0.025-0.031. At 0.50
# (seven more seeds) none is refused and the farthest followed lies 0.28-0.39
# under; a group rule that scores otherwise differs by whole deviations.
GROUP_ROUTING_MARGIN = 0.50

# The (layer, position) pairs followed, as a share of all pairs of the
# forward: 1,531 to 1,723 of 8,592 (17.8 to 20.1%) over 14 runs, two and a
# half times cell 9's 7%: a row here stands at TWO kinds of boundary, the 4th
# against the 5th of 8 groups' scores and the 8th against the 9th of the kept
# groups' 256 experts, and the bf16 program's hidden states differ from the
# float32 reference's by 2.5%. A router that scores or selects wrongly
# differs by whole standard deviations in most rows: those are REFUSED, not
# followed, and the logits then fail.
MAX_FOLLOWED_SHARE = 0.30

# a position's limit by the dtype the program is served in. float32 (the CPU
# tests and rehearsals): the served path reads 1e-6 at worst; a wrong state,
# span, weight, decay or choice gives 1e-3 and up
TOL = {"bfloat16": LOGITS_TOL, "float32": 1.0e-5}

L2_EPS = 1e-6  # under the root of q's and k's L2 norm


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rounded(x, levels, axis):
    """``x`` rounded to ``levels`` symmetric integer levels of its largest
    magnitude along ``axis`` (127: int8); unchanged where ``levels`` is 0."""
    step = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / jnp.maximum(levels, 1.0)
    return jnp.where(levels > 0, jnp.round(x / jnp.where(step == 0, 1.0, step)) * step, x)


def _rotated_pairs(x, theta):
    """x (..., T, d) rotated by position, dimensions ``2j`` and ``2j + 1``
    together at frequency ``theta^(-2j / d)``."""
    d, T = x.shape[-1], x.shape[-2]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _causal_conv(z, taps):
    """z (B, T, C), taps (C, W): ``sum_j taps[:, j] z_(t - (W - 1) + j)``,
    zeros before position 0."""
    T, W = z.shape[1], taps.shape[1]
    out = jnp.zeros_like(z)
    for j in range(W):
        back = W - 1 - j
        out = out + jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :T] * taps[:, j]
    return out


def kda(u, lp, hp, head_decay=False):
    """u (B, T, H) -> (B, T, H): Kimi delta attention, the state a token at a
    time. ``head_decay``: the control whose channels all decay by their
    head's mean log decay."""
    B, T, _ = u.shape
    n = lp["a_log"].shape[0]
    dk = lp["wq"].shape[1] // n
    dv = lp["wv"].shape[1] // n
    heads = lambda y, d: y.reshape(B, T, n, d)
    taps = lp["taps"]
    conv = lambda y, lo, hi: jax.nn.silu(_causal_conv(y, taps[lo:hi]))
    q = heads(conv(u @ lp["wq"], 0, n * dk), dk)
    k = heads(conv(u @ lp["wk"], n * dk, 2 * n * dk), dk)
    v = heads(conv(u @ lp["wv"], 2 * n * dk, 2 * n * dk + n * dv), dv)
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = jax.nn.sigmoid(u @ lp["wb"])  # (B, T, n)
    raw = heads(u @ lp["wf"] + lp["dt_bias"], dk)
    g = hp["decay_lower_bound"] * jax.nn.sigmoid(jnp.exp(lp["a_log"])[:, None] * raw)
    g = jnp.where(head_decay, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), g)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, n, dk) ... (B, n)
        S = jnp.exp(g_t)[..., None] * S  # Diag(e^g) S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bnd,bndv->bnv", k_t, S))[..., None, :]
        return S, jnp.einsum("bnd,bndv->bnv", q_t, S)

    _, o = jax.lax.scan(token, jnp.zeros((B, n, dk, dv), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), lp["o_ln"], hp["eps"])  # (B, T, n, dv)
    o = o * jax.nn.sigmoid(heads(u @ lp["wg"], dv))
    return jnp.einsum("btnd,ndh->bth", o, lp["wo"])


def latent_attention(u, lp, hp):
    """u (B, T, H) -> (B, T, H): latent attention in its expanded form."""
    T = u.shape[1]
    rank = lp["kv_ln"].shape[0]
    nope = lp["w_kvb"].shape[-1] - lp["wo"].shape[1]
    q = jnp.einsum("bth,hnd->bntd", u, lp["wq"])  # (B, n, T, nope + rope)
    kv_a = u @ lp["w_kva"]
    c_kv = _rms(kv_a[..., :rank], lp["kv_ln"], hp["eps"])
    k_r = _rotated_pairs(kv_a[..., rank:], hp["theta"])  # (B, T, rope): one for all heads
    kv = jnp.einsum("btr,rnd->bntd", c_kv, lp["w_kvb"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = _rotated_pairs(q[..., nope:], hp["theta"])
    s = (jnp.einsum("bnqd,bnkd->bnqk", q[..., :nope], k_nope)
         + jnp.einsum("bnqd,bkd->bnqk", q_rope, k_r)) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(s, axis=-1), v)
    o = o * jax.nn.sigmoid(u @ lp["w_head_gate"])[..., None]  # one gate a head
    return jnp.einsum("bqnd,ndh->bqh", o, lp["wo"])


def _gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _kept(scores, keep):
    """(..., n) -> bool (..., n): the ``keep`` largest (ties to the lowest)."""
    _, idx = jax.lax.top_k(scores, keep)
    return jnp.any(idx[..., None] == jnp.arange(scores.shape[-1]), axis=-2)


def route(u, lp, hp, follow=None, group_limit=True):
    """Router over ALL experts: (weights (B, T, E) zero outside the chosen k,
    info). ``follow`` (B, T, k) int32: the program's choice (-1: none given);
    its groups, then its experts, are taken where they are a near tie with
    the reference's own (module docstring), with the reference's own ``s``.
    ``group_limit`` False: the control that chooses among all experts.
    ``info``: ``followed`` / ``refused`` (B, T) bool, ``reach`` (B, T): how far
    under the own last-kept score (of groups or of experts, the larger) the
    program's lowest lay, in standard deviations of the row's scores."""
    s = jax.nn.sigmoid(u @ lp["gate"])
    c = s + lp["bias"]  # the selection scores
    k, E, G = hp["top_k"], s.shape[-1], hp["n_group"]
    given = follow is not None
    theirs = jnp.sum(jax.nn.one_hot(follow, E, dtype=s.dtype), axis=-2) if given else None
    has = (follow[..., 0] >= 0) if given else None
    g_reach = jnp.zeros(s.shape[:-1], s.dtype)
    g_follow = g_refuse = jnp.zeros(s.shape[:-1], bool)
    if group_limit and G > 1:
        grouped = c.reshape(c.shape[:-1] + (G, E // G))
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (B, T, G)
        own = _kept(score, hp["topk_group"])
        if given:
            their_groups = jnp.any(theirs.reshape(grouped.shape) > 0, axis=-1)
            # (a choice may lie in FEWER groups than are kept: only a group the
            # reference did not keep is a difference)
            differs = jnp.any(their_groups & ~own, axis=-1) & has
            last_own = jnp.min(jnp.where(own, score, jnp.inf), axis=-1)
            lowest = jnp.min(jnp.where(their_groups, score, jnp.inf), axis=-1)
            g_reach = jnp.where(differs, (last_own - lowest) / jnp.std(score, axis=-1), 0.0)
            g_follow = differs & (g_reach < GROUP_ROUTING_MARGIN)
            g_refuse = differs & ~g_follow
            # the program's groups, filled up with the reference's best others
            filled = _kept(jnp.where(their_groups, jnp.inf, score), hp["topk_group"])
            own = jnp.where(g_follow[..., None], filled, own)
        c_open = jnp.where(jnp.repeat(own, E // G, axis=-1), c, -jnp.inf)
    else:
        c_open = c
    top_c, top_i = jax.lax.top_k(c_open, k)  # stable: ties to the lowest id
    std = jnp.std(c, axis=-1)
    chosen = jnp.sum(jax.nn.one_hot(top_i, E, dtype=s.dtype), axis=-2)
    followed, refused, reach = g_follow, g_refuse, g_reach
    if given:
        differs = jnp.any(theirs != chosen, axis=-1) & has & ~g_refuse
        lowest = jnp.min(jnp.take_along_axis(c_open, jnp.maximum(follow, 0), axis=-1), axis=-1)
        e_reach = jnp.where(differs, (top_c[..., k - 1] - lowest) / std, 0.0)
        e_follow = differs & (e_reach < ROUTING_MARGIN)
        followed, refused = g_follow | e_follow, g_refuse | (differs & ~e_follow)
        reach = jnp.maximum(g_reach, e_reach)
        chosen = jnp.where(e_follow[..., None], theirs, chosen)
    w = s * chosen
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + hp["renorm_eps"])
    return w * hp["routed_scale"], {"followed": followed, "refused": refused, "reach": reach}


def routed(u, lp, hp, first=None, held=None, levels=0.0, follow=None, group_limit=True):
    """The routed experts' part for the experts ``first .. first + held`` that
    ``lp`` holds (all of ``lp``'s by default), one expert at a time; and
    :func:`route`'s info."""
    first = hp["first"] if first is None else first
    held = lp["w_up"].shape[0] if held is None else held
    w, info = route(u, lp, hp, follow, group_limit)
    wide = lambda x: _rounded(x.astype(jnp.float32), levels, 0)

    def one(acc, e):
        y = _gated_ffn(u, wide(lp["w_gate"][e]), wide(lp["w_up"][e]), wide(lp["w_down"][e]))
        return acc + jnp.take(w, first + e, axis=-1)[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    return out, info


# the matrices an int8-weight deployment rounds (per output column, over the
# contraction); norms, taps, the small projections (beta, the head gate), the
# decay's bias and rate, the router and its bias and the embedding's lookup
# stay as they are
_ROUNDED = {"wq": 0, "wk": 0, "wv": 0, "wf": 0, "wg": 0, "wo": (0, 1), "w_kva": 0, "w_kvb": 0,
            "m_gate": 0, "m_up": 0, "m_down": 0, "s_gate": 0, "s_up": 0, "s_down": 0}
_EXPERTS = ("w_gate", "w_up", "w_down")  # widened (and rounded) an expert at a time


def layer(x, lp, hp, kind, levels=0.0, follow=None, head_decay=False, group_limit=True):
    """One block: ``(y (B, T, H), route's info or None)``. ``levels`` > 0, the
    lower-precision probe, rounds the weight matrices to that many integer
    levels (127 is int8, the nearest precision below bf16)."""
    with jax.default_matmul_precision("highest"):
        experts = {k: lp[k] for k in _EXPERTS if k in lp}
        lp = dict({k: jnp.asarray(v, jnp.float32) for k, v in lp.items() if k not in experts},
                  **experts)
        lp.update({k: _rounded(lp[k], levels, axis) for k, axis in _ROUNDED.items() if k in lp})
        u = _rms(x, lp["mix_ln"], hp["eps"])
        h = x + (kda(u, lp, hp, head_decay) if kind == "linear_attention"
                 else latent_attention(u, lp, hp))
        g = _rms(h, lp["ffn_ln"], hp["eps"])
        if "gate" not in lp:
            return h + _gated_ffn(g, lp["m_gate"], lp["m_up"], lp["m_down"]), None
        r, info = routed(g, lp, hp, levels=levels, follow=follow, group_limit=group_limit)
        return h + r + _gated_ffn(g, lp["s_gate"], lp["s_up"], lp["s_down"]), info


def head(h, g, w, hp, levels=0.0):
    """A block of the vocabulary: h (B, P, H), w (H, Vb) columns of the head
    -> (B, P, Vb)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        return _rms(h, f32(g), hp["eps"]) @ _rounded(f32(w), levels, 0)


@functools.lru_cache(maxsize=None)
def _jitted(hp_key):
    hp = dict(hp_key)
    return (jax.jit(lambda x, lp, lv, follow, head_decay, kind, group_limit: layer(
        x, lp, hp, kind, lv, follow, head_decay, group_limit), static_argnums=(5, 6)),
            jax.jit(lambda h, g, w, lv: head(h, g, w, hp, lv)))


VOCAB_BLOCK = 8192  # columns of the head widened to float32 at a time


def forward(p, ids, hp, levels=0.0, first=0, choice=None, head_decay=False, group_limit=True):
    """``ids`` (B, T) int32 -> (logits (B, T - first, V) float32 of positions
    ``first ..``, routing). ``p``: :func:`from_tree`'s layout. One compiled
    program a kind of block, run a layer at a time; the head a block of the
    vocabulary at a time. ``choice`` (expert layers, B, T, k): the experts the
    program chose in each expert layer, followed where they are a near tie
    (module docstring). ``routing``: ``followed`` / ``refused`` (expert layers,
    B, T) bool, ``reach`` alike. ``levels`` 127, ``head_decay``,
    ``group_limit=False``: the controls (module docstring)."""
    layer_fn, head_fn = _jitted(tuple(sorted(hp.items())))
    x = jnp.asarray(p["embed"][ids], jnp.float32)
    none = jnp.full(ids.shape + (hp["top_k"], ), -1, jnp.int32)
    infos = []
    for kind, lp in zip(p["layer_types"], p["layers"]):
        follow = None
        if "gate" in lp:
            follow = none if choice is None else jnp.asarray(choice[len(infos)], jnp.int32)
        x, info = layer_fn(x, lp, jnp.float32(levels), follow, jnp.bool_(head_decay), kind,
                           bool(group_limit))
        if info is not None:
            infos.append(info)
    x = x[:, first:]
    V = p["head"].shape[1]
    logits = jnp.concatenate([head_fn(x, p["final_norm"], p["head"][:, v0:v0 + VOCAB_BLOCK],
                                      jnp.float32(levels))
                              for v0 in range(0, V, VOCAB_BLOCK)], axis=-1)
    empty = jnp.zeros((0, ) + ids.shape)
    return logits, {key: (jnp.stack([i[key] for i in infos]) if infos else empty)
                    for key in ("followed", "refused", "reach")}


def kwargs_for(config, model_cfg):
    """The hyper-parameters :func:`forward` takes, from the configuration
    file's published keys and the sizes the program built (``first``)."""
    pub = config["published"]
    return {"eps": float(pub["rms_norm_eps"]), "top_k": int(pub["num_experts_per_tok"]),
            "routed_scale": float(pub["routed_scaling_factor"]),
            "renorm_eps": float(config["reference"]["renorm_eps"]),
            "n_group": int(pub["n_group"]), "topk_group": int(pub["topk_group"]),
            "decay_lower_bound": float(pub["kda_lower_bound"]),
            "theta": float(pub["rope_theta"]), "first": int(model_cfg.moe_first_expert)}


# ---- the program's parameter tree -> Params -------------------------------
def from_tree(tree, layer_types):
    """The serving engine's tree (flax names, unrolled ``layer_<i>``), leaves
    as they are (bf16 on the chip): the reference widens them to float32 a
    layer and an expert at a time."""
    def one(lt, kind):
        out = dict(mix_ln=lt["attn_norm"]["scale"], ffn_ln=lt["mlp_norm"]["scale"])
        if kind == "linear_attention":
            m = lt["gdn"]
            out.update(wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                       wv=m["v_proj"]["kernel"], wb=m["b_proj"]["kernel"],
                       wf=m["a_proj"]["kernel"], wg=m["g_proj"]["kernel"],
                       wo=m["o_proj"]["kernel"], a_log=m["A_log"], dt_bias=m["dt_bias"],
                       taps=m["conv"], o_ln=m["o_norm"]["scale"])
        else:
            m = lt["attn"]
            out.update(wq=m["q_proj"]["kernel"], w_kva=m["kv_a_proj"]["kernel"],
                       kv_ln=m["kv_a_norm"]["scale"], w_kvb=m["kv_b_proj"],
                       w_head_gate=m["g_proj"]["kernel"], wo=m["o_proj"]["kernel"])
        if "mlp" in lt:
            f = lt["mlp"]
            out.update(m_gate=f["gate_proj"]["kernel"], m_up=f["up_proj"]["kernel"],
                       m_down=f["down_proj"]["kernel"])
        else:
            f, sh = lt["moe"], lt["moe"]["shared_expert"]
            out.update(gate=f["gate"], bias=f["e_score_correction_bias"],
                       w_gate=f["experts"]["gate_proj"], w_up=f["experts"]["up_proj"],
                       w_down=f["experts"]["down_proj"], s_gate=sh["gate_proj"]["kernel"],
                       s_up=sh["up_proj"]["kernel"], s_down=sh["down_proj"]["kernel"])
        return out

    return dict(embed=tree["embed"]["embedding"], layer_types=tuple(layer_types),
                layers=[one(tree[f"layer_{i}"], kind) for i, kind in enumerate(layer_types)],
                final_norm=tree["final_norm"]["scale"], head=tree["lm_head"]["kernel"])


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
