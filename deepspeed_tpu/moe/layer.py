"""MoE layer.

Analogue of reference ``deepspeed/moe/layer.py`` (``MoE`` :16) +
``experts.py`` (``Experts`` :10). Experts are one batched weight with a
leading expert dim sharded over the ``expert`` mesh axis; dispatch/combine
einsums against expert-sharded intermediates make XLA insert the token
all-to-alls that the reference issues by hand (``_AllToAll``,
sharded_moe.py:90).
"""

import threading

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm import comm as dist
from .sharded_moe import sigmoid_serving_choice, top_k_gating, top_k_serving_choice

# Serving MoE dispatches traced by this thread, (sparse, dense): a scheduler
# reads it around a dispatch to learn which one a step program was built
# with, as it does for the K/V commit (``ops/pallas/kv_commit.traced``).
_traced = threading.local()


def tally_dispatch(sparse):
    n = traced_dispatches()
    _traced.counts = (n[0] + 1, n[1]) if sparse else (n[0], n[1] + 1)


def traced_dispatches():
    return getattr(_traced, "counts", (0, 0))


def _expert_constraint(x, spec):
    """Pin an (E, ...) intermediate to the expert axis when a mesh is live
    (works inside partial-manual regions too — dist.constrain drops the
    manually-partitioned axes and resolves over the auto remainder)."""
    if dist.has_mesh() and dist.get_mesh().shape[dist.EXPERT_AXIS] > 1:
        return dist.constrain(x, spec)
    return x


def _ep_size():
    """Live size of the ``expert`` mesh axis from this trace context."""
    if not dist.has_mesh() or dist.EXPERT_AXIS in dist.get_manual_axes():
        return 1
    return dist.get_mesh().shape[dist.EXPERT_AXIS]


def _tp_live():
    if not dist.has_mesh() or dist.TENSOR_AXIS in dist.get_manual_axes():
        return False
    return dist.get_mesh().shape[dist.TENSOR_AXIS] > 1


def _deq(q, s, dtype):
    """Dequantize a batched int8 expert kernel (E, K, N) with per-group
    scales (E, G, N) to ``dtype``."""
    E, k, n = q.shape
    G = s.shape[1]
    return (q.astype(dtype).reshape(E, G, k // G, n)
            * s[:, :, None, :].astype(dtype)).reshape(E, k, n)


def _kernel_leaves(kernels, activation, dtype):
    """(gate or None, up, down) expert kernels in ``dtype`` from the
    param-tree leaf dict (fp leaves or int8 ``*_q``/``*_scale`` pairs)."""
    glu = activation in ("swiglu", "geglu")
    if "up_proj_q" in kernels:
        leaf = lambda n: _deq(kernels[n + "_q"], kernels[n + "_scale"], dtype)
    else:
        leaf = lambda n: kernels[n].astype(dtype)
    return (leaf("gate_proj") if glu else None), leaf("up_proj"), leaf("down_proj")


def _pointwise(h, activation):
    """The activation of an expert of two matrices: gelu, relu, or relu
    squared (``relu2``)."""
    if activation == "gelu":
        return nn.gelu(h)
    return jnp.square(nn.relu(h)) if activation == "relu2" else nn.relu(h)


def expert_ffn(x, kernels, activation, dtype, bitwise_tp=False, keep_expert_axis=False):
    """Batched expert FFN math on EXPLICIT kernel leaves.

    ``x``: (E, C, H) per-expert token buffers (the leading axis matches the
    kernels' leading expert — or pool-page — axis). ``kernels``: a dict in
    the param-tree leaf naming: ``{gate,up,down}_proj`` fp kernels or their
    int8 ``*_q``/``*_scale`` pairs (detected by key), plus optional
    ``up_bias``/``down_bias``. Shared by :class:`Experts` (weights from the
    param tree, possibly expert-sharded) and the cold-expert paged pools
    (``moe/expert_store.py``, weights gathered from resident device pages):
    ONE math path, so offloaded and in-tree experts can never diverge.

    ``bitwise_tp``: serving all-gather layout — re-replicate the
    ffn-sharded activation over ``tensor`` before the down projection so
    its full contraction runs shard-local (no partial-sum reduction; the
    tp>1 == tp=1 bit-identity contract). ``keep_expert_axis`` preserves the
    leading axis's ``expert`` sharding through that constraint."""
    use_bias = "down_bias" in kernels
    gk, uk, dk = _kernel_leaves(kernels, activation, dtype)
    x = x.astype(dtype)
    if activation in ("swiglu", "geglu"):
        g = jnp.einsum("ech,ehf->ecf", x, gk)
        u = jnp.einsum("ech,ehf->ecf", x, uk)
        act = nn.silu(g) if activation == "swiglu" else nn.gelu(g)
        h = act * u
    else:
        h = jnp.einsum("ech,ehf->ecf", x, uk)
        if use_bias and "up_bias" in kernels:
            h = h + kernels["up_bias"][:, None, :].astype(h.dtype)
        h = _pointwise(h, activation)
    if bitwise_tp and _tp_live():
        # serving bitwise-TP: gather the ffn-sharded activation (exact
        # concat over `tensor`) so the replicated down_proj contracts fully
        # locally — same move MLP._tp_replicate makes on the dense path
        e_axis = dist.EXPERT_AXIS if (keep_expert_axis and _ep_size() > 1) else None
        h = dist.constrain(h, P(e_axis, None, None))
    out = jnp.einsum("ecf,efh->ech", h, dk)
    if use_bias:
        out = out + kernels["down_bias"][:, None, :].astype(out.dtype)
    return out


def row_major_format(leaf):
    """The :class:`~jax.experimental.layout.Format` in which an expert kernel
    ``(E, K, N)`` rests as the grouped products read it: row-major, ``N`` in
    the lanes. Where ``N`` fills no whole 128-lane tiles (1,856) the device's
    default layout puts ``K`` in the lanes instead, and every program that
    reads the leaf would first copy it whole into this form (0.63 GB a layer,
    read and written, a sync; a step program of 7 such layers does not fit
    the chip). A committed array keeps its layout into ``jax.jit``."""
    from jax.experimental.layout import Format, Layout
    return Format(Layout(major_to_minor=tuple(range(leaf.ndim))), leaf.sharding)


def _expert_kernel_row_major(x):
    return x


def rest_experts_row_major(params):
    """``params`` with every float expert kernel (a 3-d leaf under an
    ``experts`` scope) at rest in :func:`row_major_format`. A leaf that
    rests so already (every leaf on the CPU; ``N`` a multiple of 128 on the
    chip) is handed back as it is.

    The one program that relays a leaf is kept OUT of the persistent
    compile cache (it compiles in a tenth of a second): an executable read
    back from that cache hands out row-major buffers that REPORT the
    default layout (jaxlib 0.9.0 on the v5e, my chip runs, PR 39), every
    ``jax.jit`` then compiles for the default layout, and the step program
    either copies the leaf after all or refuses the buffer by its size."""
    relay = {}

    def rest(path, leaf):
        if (not isinstance(leaf, jax.Array) or leaf.ndim != 3
                or "['experts']" not in jax.tree_util.keystr(path)
                or not jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        want = tuple(range(leaf.ndim))
        layout = getattr(leaf.format, "layout", None)
        if layout is None or tuple(layout.major_to_minor) == want:
            return leaf
        fmt = row_major_format(leaf)
        if fmt not in relay:
            relay[fmt] = jax.jit(_expert_kernel_row_major, out_shardings=fmt)
        out = relay[fmt](leaf)
        if tuple(out.format.layout.major_to_minor) != want:
            raise RuntimeError(
                f"expert kernel {jax.tree_util.keystr(path)} was relaid row-major and reports "
                f"layout {out.format.layout}: the step programs would compile for the wrong one")
        return out

    key = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, key)
    jax.config.update(key, float("inf"))  # nothing compiled in here is written to the cache
    try:
        return jax.tree_util.tree_map_with_path(rest, params)
    finally:
        jax.config.update(key, was)


def expert_rank(ids):
    """``ids`` (N, k), a row's k distinct experts -> (N, k) int32: each
    choice's place among them in increasing expert order."""
    return jnp.sum(ids[:, None, :] < ids[:, :, None], axis=-1, dtype=jnp.int32)


def in_expert_order(x, rank):
    """``x`` (N, k) by choice -> (N, k) by place (:func:`expert_rank`)."""
    return jnp.stack([jnp.sum(jnp.where(rank == r, x, 0), axis=1)
                      for r in range(x.shape[1])], axis=1)


def combine_chosen(y, weights):
    """``y`` (N, k, H): each row's k expert results IN INCREASING EXPERT
    ORDER, ``weights`` (N, k) their fp32 combine weights -> (N, H) fp32, one
    ``acc + w * y`` a step. The one accumulation of the serving dispatches
    (sparse, dense broadcast, paged experts): whichever computed ``y``, the
    same products are added in the same order by the same expression, so
    they agree bit for bit."""
    acc = jnp.zeros((y.shape[0], y.shape[2]), jnp.float32)
    for j in range(y.shape[1]):
        acc = acc + weights[:, j:j + 1] * y[:, j].astype(jnp.float32)
    return acc


# pairs one grouped product takes: a wider step (chunked prefill, speculative
# verify) walks its pairs in tiles of this many, as far as the pairs reach
SPARSE_TILE = 1024


def sparse_expert_ffn(tokens, ids, weights, valid, kernels, first, activation, dtype):
    """The routed experts' part of the result for the experts HELD here, by
    a sparse dispatch: row-expert pairs are sorted by expert and each held
    expert multiplies only the rows routed to it (``jax.lax.ragged_dot``,
    one grouped product per projection).

    ``tokens``: (N, H); ``ids``/``weights``: (N, k) the router's choice over
    ALL its experts; ``valid``: (N,) live rows (padding past a slot's span is
    not dispatched); ``kernels``: the held experts' leaves, leading axis =
    experts ``first .. first + held``. Pairs routed elsewhere, and what those
    experts would add, are left out. Returns (N, H) fp32.

    A row's result is a pure function of the row, and the same sum as the
    dense broadcast's: each pair's product accumulates over the contraction
    alone and lands in the pair's OWN place of an ``(N, k, H)`` buffer, the
    row's k places in increasing expert order (a scatter of distinct targets,
    nothing is added there), then each row adds its k weighted results by
    :func:`combine_chosen`, as the dense broadcast does (a pair routed
    elsewhere adds ``0 * 0``). Where a pair sits among the sorted pairs, and
    what else shares the step, changes nothing. Pairs are walked in tiles of
    :data:`SPARSE_TILE`, only as many as hold a pair."""
    N, H = tokens.shape
    k = ids.shape[1]
    gk, uk, dk = _kernel_leaves(kernels, activation, dtype)
    held = uk.shape[0]
    P = N * k
    tile = min(P, SPARSE_TILE)
    n_tiles = -(-P // tile)
    pad = n_tiles * tile - P
    with jax.named_scope("moe_router"):
        local = ids - first
        here = (local >= 0) & (local < held) & valid[:, None]
        key = jnp.where(here, local, held).reshape(P)  # pairs routed elsewhere sort last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((held + 1, ), jnp.int32).at[key].add(1)[:held]
        ends = jnp.cumsum(sizes)
        n_here = ends[-1]
        row_of = jnp.pad(order // k, (0, pad))
        # sorted position -> where its pair's result goes: the row's k places
        # in increasing expert order (P: no pair held here, the write is dropped)
        rank = expert_rank(ids)
        place = (jnp.arange(N)[:, None] * k + rank).reshape(P)
        dest_of = jnp.pad(jnp.where(jnp.arange(P) < n_here, place[order], P), (0, pad),
                          constant_values=P)
        expert_of = jnp.pad(jnp.minimum(key[order], held - 1), (0, pad))
        tokens = tokens.astype(dtype)

    def product(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs, preferred_element_type=jnp.float32).astype(dtype)

    def one_tile(t, buf):
        lo = t * tile
        with jax.named_scope("moe_router"):
            rows = jax.lax.dynamic_slice_in_dim(row_of, lo, tile)
            e = jax.lax.dynamic_slice_in_dim(expert_of, lo, tile)
            # this tile's share of every group: [start_e, end_e) cut to [lo, lo + tile)
            gs = jnp.clip(jnp.minimum(ends, lo + tile) - jnp.maximum(ends - sizes, lo), 0, tile)
            x = jnp.take(tokens, rows, axis=0)
        with jax.named_scope("moe_experts"):
            up = product(x, uk, gs)
            if gk is not None:
                g = product(x, gk, gs)
                h = (nn.silu(g) if activation == "swiglu" else nn.gelu(g)) * up
            else:
                if "up_bias" in kernels:
                    up = up + jnp.take(kernels["up_bias"], e, axis=0).astype(dtype)
                h = _pointwise(up, activation)
            y = product(h, dk, gs)
            if "down_bias" in kernels:
                y = y + jnp.take(kernels["down_bias"], e, axis=0).astype(dtype)
        with jax.named_scope("moe_router"):
            dest = jax.lax.dynamic_slice_in_dim(dest_of, lo, tile)
            return buf.at[dest].set(y, mode="drop", unique_indices=True)

    buf = jnp.zeros((P, H), dtype)
    if n_tiles == 1:
        buf = one_tile(0, buf)
    else:
        buf = jax.lax.fori_loop(0, (n_here + tile - 1) // tile, one_tile, buf)
    with jax.named_scope("moe_router"):
        w = in_expert_order(jnp.where(here, weights, 0.0), rank)
        return combine_chosen(buf.reshape(N, k, H), w)


class Experts(nn.Module):
    """Batched expert FFNs: weights (E, H, F)/(E, F, H). ``int8`` serves
    from per-expert group-quantized weights (reference
    ``moe_inference.py``'s int8 expert path): each kernel becomes
    (int8 (E, K, N), fp32 scales (E, G, N)) built by ``quantize_params``."""
    num_experts: int
    hidden: int
    ffn: int
    activation: str
    dtype: any
    int8: bool = False
    int8_groups: int = 0  # scale-group SIZE (0 = default rule, 128)
    use_bias: bool = False  # Megatron-style biased expert FFNs
    bitwise_tp: bool = False  # serving all-gather layout (see expert_ffn)

    def _qparam(self, name, k, n):
        E = self.num_experts
        gs = self.int8_groups or 128
        G = k // gs if k % gs == 0 else 1
        q = self.param(name + "_q", nn.initializers.zeros, (E, k, n), jnp.int8)
        s = self.param(name + "_scale", nn.initializers.ones, (E, G, n), jnp.float32)
        return q, s

    def _kernels(self):
        """Declare this module's kernel/bias params and return them in the
        leaf-name dict :func:`expert_ffn` and :func:`sparse_expert_ffn`
        consume (one set of leaves for the dense, sparse and paged paths)."""
        init = nn.initializers.normal(0.02)
        E, H, F = self.num_experts, self.hidden, self.ffn
        glu = self.activation in ("swiglu", "geglu")
        kernels = {}
        if self.int8:
            # gate declared unconditionally (matching the fp branch): the
            # param tree must not depend on the activation family
            for name, k, n in (("gate_proj", H, F), ("up_proj", H, F),
                               ("down_proj", F, H)):
                kernels[name + "_q"], kernels[name + "_scale"] = self._qparam(name, k, n)
        else:
            if self.activation != "relu2":  # an expert of two matrices has no gate leaf
                kernels["gate_proj"] = self.param("gate_proj", init, (E, H, F), jnp.float32)
            kernels["up_proj"] = self.param("up_proj", init, (E, H, F), jnp.float32)
            kernels["down_proj"] = self.param("down_proj", init, (E, F, H), jnp.float32)
        if self.use_bias:  # Megatron-style biased expert FFNs
            kernels["down_bias"] = self.param("down_bias", nn.initializers.zeros,
                                              (E, H), jnp.float32)
            if not glu:
                # no up_bias on the glu branch: it never applies one, so
                # declaring it would add a dead trainable param
                kernels["up_bias"] = self.param("up_bias", nn.initializers.zeros,
                                                (E, F), jnp.float32)
        return kernels

    @nn.compact
    def __call__(self, x, keep_expert_axis=False, route=None):
        """``x``: (E, C, H) per-expert row buffers, every expert on every
        row of its buffer (the dense broadcast); or, with ``route = (ids,
        weights, valid, first)``, the (N, H) rows themselves, dispatched
        sparsely to the experts held (:func:`sparse_expert_ffn`)."""
        if route is not None:
            return sparse_expert_ffn(x, *route[:3], self._kernels(), route[3],
                                     self.activation, self.dtype)
        return expert_ffn(x, self._kernels(), self.activation, self.dtype,
                          bitwise_tp=self.bitwise_tp,
                          keep_expert_axis=keep_expert_axis)


class MoE(nn.Module):
    """Top-k routed MoE FFN; returns (output, aux_loss)."""
    cfg: any  # TransformerConfig

    def _token_spec(self, B, T):
        """Canonical (N, H) token layout: the flattened B·T dim carries the
        batch axes (expert,data) major and seq minor — exactly what reshaping
        a (B@dp, T@seq, H) activation preserves. Pinning it (and therefore
        its cotangent) keeps the partitioner from dragging tensor-axis tiling
        of H into the dispatch/combine einsums (involuntary full remat)."""
        import math
        mesh = dist.get_mesh()
        axes = [a for a in (dist.EXPERT_AXIS, dist.DATA_AXIS) if mesh.shape[a] > 1]
        if axes and B % math.prod(mesh.shape[a] for a in axes) != 0:
            axes = []
        if mesh.shape[dist.SEQ_AXIS] > 1 and T % mesh.shape[dist.SEQ_AXIS] == 0:
            axes = axes + [dist.SEQ_AXIS]
        return P(tuple(axes) if axes else None, None)

    @nn.compact
    def __call__(self, x, serving=False, q_spans=None, expert_ops=None):
        """``x``: (B, T, H). Training (default) returns ``(output,
        aux_loss)`` through the capacity-buffered dispatch. ``serving=True``
        (the KV-cache forward — slot-pool decode, chunked prefill, static
        generate) routes per token with NO capacity competition (see
        :func:`~deepspeed_tpu.moe.sharded_moe.top_k_serving_weights`) and
        returns ``output`` alone: no aux loss is sown, every token is a
        pure function of itself, and ep>1 sharded compute is bit-identical
        to the ep=1 replicated program (all-gather combine in fixed expert
        order). ``q_spans``: per-row live query counts (padding columns are
        excluded from the expert-usage stats). ``expert_ops``: cold-expert
        paging operands for THIS layer — ``(expert->page map (E,), pools
        {leaf: (R, ...)})`` gathered from the
        :class:`~deepspeed_tpu.moe.expert_store.PagedExpertStore`; the
        expert params are then host-resident and never read."""
        if serving:
            return self._serving(x, q_spans, expert_ops)
        cfg = self.cfg
        B, T, H = x.shape
        N, E = B * T, cfg.num_experts
        tokens = x.reshape(N, H)
        if dist.has_mesh():
            tokens = dist.constrain(tokens, self._token_spec(B, T))

        gate_w = self.param("gate", nn.initializers.normal(0.02), (H, E), jnp.float32)
        logits = tokens.astype(jnp.float32) @ gate_w
        dispatch, combine, aux_loss, _ = top_k_gating(logits, cfg.moe_top_k, cfg.moe_capacity_factor)
        if dist.has_mesh():
            # dispatch/combine stay token-sharded; the expert_in/out einsums
            # contract over n (psum over the token axes) — tiling them by e
            # mid-build is the involuntary-remat path
            gspec = P(self._token_spec(B, T)[0], None, None)
            dispatch = dist.constrain(dispatch, gspec)
            combine = dist.constrain(combine, gspec)

        expert_in = jnp.einsum("nec,nh->ech", dispatch.astype(cfg.dtype), tokens)
        expert_in = _expert_constraint(expert_in, P(dist.EXPERT_AXIS, None, None))
        expert_out = Experts(E, H, cfg.expert_ffn_size, cfg.activation, cfg.dtype,
                             int8=getattr(cfg, "int8_weights", False),
                             int8_groups=getattr(cfg, "int8_group_size", 0),
                             # explicit flag, NOT inferred from cfg.norm: bias
                             # presence changes the param tree, so it must be
                             # a deliberate config choice (ADVICE r5)
                             use_bias=getattr(cfg, "moe_expert_bias", False),
                             name="experts")(expert_in)
        expert_out = _expert_constraint(expert_out, P(dist.EXPERT_AXIS, None, None))
        out = jnp.einsum("nec,ech->nh", combine.astype(cfg.dtype), expert_out)
        if dist.has_mesh():
            out = dist.constrain(out, self._token_spec(B, T))
        out = out.reshape(B, T, H)
        if cfg.moe_shared_experts:
            out = out + self._shared(x)
        return out, aux_loss

    def _shared(self, x):
        """The shared experts: one always-on FFN of their joint width,
        computed once for every row (what every chip of an expert-parallel
        deployment computes alike)."""
        import dataclasses
        from ..models.transformer import MLP
        cfg = self.cfg
        wide = dataclasses.replace(cfg, intermediate_size=cfg.shared_ffn_size)
        with jax.named_scope("moe_shared"):
            return MLP(wide, name="shared_expert")(x)

    def _serving(self, x, q_spans, expert_ops):
        """Serving forward: per-token capacity-free top-k dispatch.

        Bitwise-EP discipline (the PR-10 layout rule applied to the expert
        axis): per-expert FFNs run batched over the leading expert axis —
        sharded over ``expert`` when it divides ``num_experts``, each shard
        computing its experts' FULL (H, F) contractions — then the (E, N, H)
        expert outputs ALL-GATHER to replicated (pure concatenation) and the
        combine adds each row's k chosen results in fp32 in increasing
        expert order (:func:`combine_chosen`, shared with the sparse
        dispatch that one device takes). No cross-shard reduction ever
        happens, so ep>1 logits are bit-identical to the ep=1 program's
        (on the CPU, where both dispatches' products round alike; on the
        chip the mesh paths agree among themselves); a non-dividing
        expert count skips the constraints entirely (loud replicated
        fallback, the engine's ready line says so).

        Cold-expert offload: with ``expert_ops`` the R resident pool pages
        compute physically and the logical (E, N, H) outputs gather through
        the expert->page map, so the combine runs in the SAME expert order
        as the in-tree path — offloaded all-hot output is bit-identical to
        non-offloaded, and a page miss only garbles tokens routed to the
        missing expert (the scheduler detects it via the sown counts and
        re-dispatches after the hot-load; every KV row the garbage forward
        wrote is rewritten by the replay).

        Sows per-layer ``(E,)`` int32 routed-token counts into the
        ``expert_stats`` collection (live columns only, per ``q_spans``) —
        the residency/replay signal and the load-balance telemetry. The
        collection is opt-in ``mutable``; when the caller doesn't open it,
        the sow is dropped and XLA dead-code-eliminates the counts. Likewise
        ``expert_choice``: the (B, T, k) expert ids each row chose, for a
        reference that follows the program's routing."""
        cfg = self.cfg
        B, T, H = x.shape
        N, E = B * T, cfg.num_experts
        k = cfg.moe_top_k
        first, held = cfg.moe_first_expert, cfg.experts_held
        tokens = x.reshape(N, H)

        with jax.named_scope("moe_router"):
            gate_w = self.param("gate", nn.initializers.normal(0.02), (H, E), jnp.float32)
            logits = tokens.astype(jnp.float32) @ gate_w
            if q_spans is not None:
                valid = (jnp.arange(T)[None, :] < q_spans[:, None]).reshape(N)
            else:
                valid = jnp.ones((N, ), bool)

        experts = Experts(held, H, cfg.expert_ffn_size, cfg.activation, cfg.dtype,
                          int8=getattr(cfg, "int8_weights", False),
                          int8_groups=getattr(cfg, "int8_group_size", 0),
                          use_bias=getattr(cfg, "moe_expert_bias", False),
                          bitwise_tp=getattr(cfg, "bitwise_tp", False),
                          name="experts")
        # the dense broadcast stays where the expert or tensor mesh axis is
        # live (its all-gather combine is what keeps ep>1 bit-identical to
        # ep=1) and for paged experts; everything on one device is sparse
        sparse = expert_ops is None and _ep_size() == 1 and not _tp_live()
        tally_dispatch(sparse)
        with jax.named_scope("moe_router"):
            # (N, k) over ALL E, per token
            if cfg.moe_scoring == "sigmoid":
                bias = self.param("e_score_correction_bias", nn.initializers.zeros, (E, ),
                                  jnp.float32)
                ids, w = sigmoid_serving_choice(logits, bias, k)
            else:
                ids, w = top_k_serving_choice(logits, k)
            w = w * cfg.moe_routed_scale
            counts = jnp.zeros((E, ), jnp.int32).at[ids].add(
                jnp.broadcast_to(valid[:, None], ids.shape).astype(jnp.int32))
        self.sow("expert_stats", "counts", counts)
        self.sow("expert_choice", "ids", ids.reshape(B, T, k))
        if sparse:
            acc = experts(tokens, route=(ids, w, valid, first))
            out = acc.astype(cfg.dtype).reshape(B, T, H)
            return out + self._shared(x) if cfg.moe_shared_experts else out

        if held != E:
            raise NotImplementedError(
                "a layer holding a share of the experts serves through the sparse "
                "dispatch only: no live expert/tensor mesh axis, no paged experts")
        ep_ok = _ep_size() > 1 and E % _ep_size() == 0
        if expert_ops is None:
            xin = jnp.broadcast_to(tokens[None].astype(cfg.dtype), (E, N, H))
            if ep_ok:
                xin = dist.constrain(xin, P(dist.EXPERT_AXIS, None, None))
            eo = experts(xin, keep_expert_axis=ep_ok)
            if ep_ok:
                eo = dist.constrain(eo, P(dist.EXPERT_AXIS, None, None))
                # all-gather (exact concat) so the combine below reduces
                # over the FULL expert axis locally on every shard
                eo = dist.constrain(eo, P(None, None, None))
        else:
            emap, pools = expert_ops  # (E,) int32 map, {leaf: (R, ...)} pages
            R = jax.tree_util.tree_leaves(pools)[0].shape[0]
            xin = jnp.broadcast_to(tokens[None].astype(cfg.dtype), (R, N, H))
            phys = expert_ffn(xin, pools, cfg.activation, cfg.dtype,
                              bitwise_tp=getattr(cfg, "bitwise_tp", False))
            eo = jnp.take(phys, emap, axis=0)  # (E, N, H) logical expert outputs

        # fixed-order fp32 combine of each row's k chosen results, the same
        # expression for every program variant (sparse/dense, ep1/ep2,
        # in-tree/paged): einsum would leave the reduction order to each
        # program's XLA schedule
        rank = expert_rank(ids)
        ids, w = in_expert_order(ids, rank), in_expert_order(w, rank)
        acc = combine_chosen(eo[ids, jnp.arange(N)[:, None]], w)  # (N, k, H) of (E, N, H)
        out = acc.astype(cfg.dtype).reshape(B, T, H)
        return out + self._shared(x) if cfg.moe_shared_experts else out
