"""SPMD pipeline schedule.

TPU-native replacement for the reference's pipeline instruction interpreter
(``runtime/pipe/engine.py:40`` ``PipelineEngine``, ``schedule.py:189``
``TrainSchedule`` 1F1B, ``p2p.py`` wire). Design translation (SURVEY §7):
instead of N processes interpreting per-rank instruction streams and
exchanging tensors over NCCL P2P, ONE compiled program runs a circular
pipeline inside ``jax.shard_map`` that is *manual only over the* ``pipe``
*axis* — activations move between stages with ``lax.ppermute`` over ICI
neighbors while the other mesh axes (data/tensor/expert/seq) stay under the
automatic SPMD partitioner. Backward is just ``jax.grad`` through the scan:
``ppermute`` differentiates to the reverse permute, which reproduces the
backward P2P exchange of the reference schedule without an interpreter.

Schedule shape: with M microbatches and S stages, the scan runs M+S-1 steps;
stage s works on microbatch t-s at step t (classic fill/drain pipeline).
The reference's 1F1B ordering is an eager-mode *memory* optimization; under
XLA the whole program is compiled and activation liveness is bounded by
rematerialization instead (pass ``remat_policy``).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...comm import comm as dist


def num_pipeline_steps(num_microbatches, num_stages):
    return num_microbatches + num_stages - 1


def _vma(v):
    """The value's varying-manual-axes set."""
    return jax.typeof(v).vma


def _pipe_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map manual over the ``pipe`` axis; every other mesh axis stays
    under the automatic partitioner."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names={dist.PIPE_AXIS})


def spmd_pipeline(stage_fn, stage_params, x_stream, mesh=None, remat=False, with_aux=False):
    """Run ``x_stream`` through a ``pipe``-partitioned layer stack.

    ``stage_fn(local_params, x, t) -> y`` (or ``(y, aux)`` with
    ``with_aux=True``): applies one stage's layer slice at pipeline step
    ``t`` (an i32 scalar; use it to decorrelate per-step rngs); ``x``/``y``
    may be pytrees — non-activation leaves (e.g. an attention mask) ride
    along with their microbatch through every stage; ``stage_params``:
    pytree whose leaves have leading layer dim divisible by the ``pipe``
    axis size (sharded dim 0 across stages); ``x_stream``: pytree of
    (M, ...) microbatch streams entering stage 0.

    Returns the stream leaving the last stage, replicated over pipe; with
    ``with_aux`` also a scalar: the sum of ``aux`` over every VALID
    (stage, microbatch) tick, psum'd across stages — fill/drain ticks
    compute on garbage activations and are masked out. This is how
    per-stage side losses (MoE load-balancing aux, reference
    ``engine.py:2880`` composes MoE under PP) survive the pipeline.
    """
    mesh = mesh or dist.get_mesh()
    n_stages = mesh.shape[dist.PIPE_AXIS]
    if n_stages == 1:
        return _single_stage(stage_fn, stage_params, x_stream, remat, with_aux)
    M = jax.tree_util.tree_leaves(x_stream)[0].shape[0]
    steps = num_pipeline_steps(M, n_stages)
    fn = jax.checkpoint(stage_fn, static_argnums=()) if remat else stage_fn

    def tmap(f, *trees):
        return jax.tree_util.tree_map(f, *trees)

    def run(local_params, xs):
        stage = jax.lax.axis_index(dist.PIPE_AXIS)
        # carries become stage-varying inside the loop; mark them so upfront
        pvary = lambda v: jax.lax.pcast(v, (dist.PIPE_AXIS, ), to="varying")
        state = tmap(lambda x: pvary(jnp.zeros_like(x[0])), xs)
        out_stream = tmap(lambda x: pvary(jnp.zeros_like(x)), xs)
        aux_total = pvary(jnp.zeros((), jnp.float32))
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            state, out_stream, aux_total = carry
            feed = tmap(lambda x: jax.lax.dynamic_index_in_dim(x, jnp.minimum(t, M - 1), 0,
                                                               keepdims=False), xs)
            cur = tmap(lambda f, s: jnp.where(stage == 0, f, s), feed, state)
            out = fn(local_params, cur, t)
            y, aux = out if with_aux else (out, None)
            if with_aux:
                # stage s holds microbatch t-s; outside [0, M) it's fill/drain
                mb = t - stage
                valid = (mb >= 0) & (mb < M)
                aux_total = aux_total + jnp.where(valid, aux.astype(jnp.float32), 0.0)
            nxt = tmap(lambda v: jax.lax.ppermute(v, dist.PIPE_AXIS, perm), y)
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            out_stream = tmap(
                lambda os, v: jnp.where(
                    write, jax.lax.dynamic_update_index_in_dim(os, v, jnp.maximum(out_idx, 0), 0),
                    os), out_stream, y)
            return (nxt, out_stream, aux_total), None

        (_, out_stream, aux_total), _ = jax.lax.scan(
            step, (state, out_stream, aux_total), jnp.arange(steps))
        # deliver the last stage's stream to every stage (head/loss run replicated)
        out_stream = tmap(
            lambda os: jax.lax.psum(jnp.where(stage == n_stages - 1, os, jnp.zeros_like(os)),
                                    dist.PIPE_AXIS), out_stream)
        if with_aux:
            return out_stream, jax.lax.psum(aux_total, dist.PIPE_AXIS)
        return out_stream

    in_specs = (jax.tree_util.tree_map(lambda _: P(dist.PIPE_AXIS), stage_params),
                jax.tree_util.tree_map(lambda _: P(), x_stream))
    out_specs = jax.tree_util.tree_map(lambda _: P(), x_stream)
    if with_aux:
        out_specs = (out_specs, P())
    with dist.manual_axes({dist.PIPE_AXIS}):
        # grad_through: the engine differentiates jax.grad-style THROUGH
        # this call (backward is the transposed scan/ppermute)
        return _pipe_shard_map(run, mesh, in_specs, out_specs)(stage_params, x_stream)


def spmd_pipeline_1f1b(stage_fn, loss_head, stage_params, head_params, x_stream,
                       mesh=None, loss_denom=None):
    """One-pass interleaved 1F1B (reference ``TrainSchedule``,
    ``pipe/schedule.py:189``): every tick runs one (masked) forward micro-step
    AND one (masked) backward micro-step, so a stage holds at most
    ``2*(S-1-s)+1`` in-flight activations instead of all M — the 1F1B memory
    bound, here enforced by a ring buffer of stored stage INPUTS whose
    backward rematerializes the stage (activation-checkpoint style, the same
    recompute jax.grad-through-scan performs for the fill-drain schedule).

    ``stage_fn(local_params, x, t) -> y`` — fill-drain contract;
    ``loss_head(head_params, y, m) -> scalar`` — microbatch ``m``'s RAW loss
    contribution (e.g. summed token CE), evaluated at the last stage the
    moment its forward finishes — that is what lets backward start
    immediately (the 1F1B property). ``loss_denom``: global normalizer (e.g.
    total valid-token count) the SCHEDULE divides by, so summing microbatch
    contributions reproduces the fill-drain mean — callers cannot
    mis-normalize (pass None only if loss_head already returns its share of
    the final mean).

    Returns ``(loss, stage_grads, head_grads, dx_stream)``: total loss;
    gradients of the pipe-sharded stage params (same layout as
    ``stage_params``); head gradients (replicated; zero except the last
    stage's contribution, psum'd); and the gradient w.r.t. ``x_stream`` for
    the caller's embedding backward.
    """
    if loss_denom is not None:
        raw_head = loss_head
        loss_head = lambda hp, y, m: raw_head(hp, y, m) / loss_denom
    mesh = mesh or dist.get_mesh()
    n = mesh.shape[dist.PIPE_AXIS]
    M = jax.tree_util.tree_leaves(x_stream)[0].shape[0]
    if n == 1:
        return _single_stage_1f1b(stage_fn, loss_head, stage_params, head_params, x_stream)
    R = min(M, 2 * (n - 1) + 1)  # ring slots (worst-case in-flight at stage 0)
    T = M + 2 * (n - 1)

    def tmap(f, *trees):
        return jax.tree_util.tree_map(f, *trees)

    def run(local_params, head_p, xs):
        stage = jax.lax.axis_index(dist.PIPE_AXIS)

        def pvary(v):
            # idempotent invariant->varying promotion (stage params arrive
            # already pipe-varying; the replicated streams do not)
            return (v if dist.PIPE_AXIS in _vma(v)
                    else jax.lax.pcast(v, (dist.PIPE_AXIS, ), to="varying"))

        # head params MUST be promoted to pipe-varying before value_and_grad:
        # differentiating a varying loss w.r.t. an INVARIANT input makes
        # shard_map's transpose psum the cotangent across stages, polluting
        # the last stage's head grad with every other stage's masked-out
        # garbage ticks (the loss VALUE is unaffected — only grads)
        head_p = tmap(pvary, head_p)
        zero_x = tmap(lambda x: pvary(jnp.zeros_like(x[0])), xs)
        ring = tmap(lambda x: pvary(jnp.zeros((R, ) + x.shape[1:], x.dtype)), xs)
        carry = {
            "fwd_in": zero_x,  # activation arriving from stage-1
            "bwd_in": tmap(lambda x: jnp.zeros_like(x), zero_x),  # dy from stage+1
            "ring": ring,
            "dstage": tmap(lambda p: pvary(jnp.zeros_like(p)), local_params),
            "dhead": tmap(lambda p: pvary(jnp.zeros_like(p)), head_p),
            "dxs": tmap(lambda x: pvary(jnp.zeros_like(x)), xs),
            "loss": pvary(jnp.zeros((), jnp.float32)),
        }
        fwd_perm = [(i, (i + 1) % n) for i in range(n)]
        bwd_perm = [(i, (i - 1) % n) for i in range(n)]

        def tick(c, t):
            f = t - stage
            b = t - 2 * (n - 1) + stage
            f_ok = (f >= 0) & (f < M)
            b_ok = (b >= 0) & (b < M)
            f_idx = jnp.clip(f, 0, M - 1)
            b_idx = jnp.clip(b, 0, M - 1)

            # ---- forward half: mb f through this stage ----
            x_in = tmap(lambda x, s: jnp.where(stage == 0,
                                               jax.lax.dynamic_index_in_dim(x, f_idx, 0,
                                                                            keepdims=False), s),
                        xs, c["fwd_in"])
            y = stage_fn(local_params, x_in, t)
            # last stage: this microbatch's loss + dy, fed to backward NOW
            (loss_f, (dhead_f, dy_self)) = jax.value_and_grad(
                lambda hp, yy: loss_head(hp, yy, f_idx), argnums=(0, 1))(head_p, y)
            is_last = stage == n - 1
            take_loss = f_ok & is_last
            c_loss = c["loss"] + jnp.where(take_loss, loss_f, 0.0)
            c_dhead = tmap(lambda a, g: a + jnp.where(take_loss, g, jnp.zeros_like(g)),
                           c["dhead"], dhead_f)
            # store this stage's INPUT for the recompute at backward time
            slot_w = jnp.mod(f_idx, R)
            c_ring = tmap(lambda r, v: jnp.where(
                f_ok, jax.lax.dynamic_update_index_in_dim(r, v, slot_w, 0), r),
                c["ring"], x_in)

            # ---- backward half: mb b (rematerialized from the ring) ----
            x_b = tmap(lambda r: jax.lax.dynamic_index_in_dim(r, jnp.mod(b_idx, R), 0,
                                                              keepdims=False), c_ring)
            t_b = b_idx + stage  # the tick mb b was forwarded at this stage
            _, vjp = jax.vjp(lambda p, x: stage_fn(p, x, t_b), local_params, x_b)
            dy = jnp.where(is_last, dy_self, c["bwd_in"])
            dp, dx = vjp(dy)
            c_dstage = tmap(lambda a, g: a + jnp.where(b_ok, g, jnp.zeros_like(g)),
                            c["dstage"], dp)
            # stage 0: dx is the embedding-output gradient for mb b
            c_dxs = tmap(lambda acc, g: jnp.where(
                b_ok & (stage == 0),
                jax.lax.dynamic_update_index_in_dim(acc, g, b_idx, 0), acc),
                c["dxs"], dx)

            # ---- wire: activations forward, grads backward ----
            fwd_in = jax.lax.ppermute(y, dist.PIPE_AXIS, fwd_perm)
            bwd_in = jax.lax.ppermute(dx, dist.PIPE_AXIS, bwd_perm)
            return {"fwd_in": fwd_in, "bwd_in": bwd_in, "ring": c_ring,
                    "dstage": c_dstage, "dhead": c_dhead, "dxs": c_dxs,
                    "loss": c_loss}, None

        c, _ = jax.lax.scan(tick, carry, jnp.arange(T))
        sel_last = lambda v: jax.lax.psum(jnp.where(stage == n - 1, v, jnp.zeros_like(v)),
                                          dist.PIPE_AXIS)
        sel_first = lambda v: jax.lax.psum(jnp.where(stage == 0, v, jnp.zeros_like(v)),
                                           dist.PIPE_AXIS)
        loss = sel_last(c["loss"])
        dhead = tmap(sel_last, c["dhead"])
        dxs = tmap(sel_first, c["dxs"])
        return loss, c["dstage"], dhead, dxs

    in_specs = (jax.tree_util.tree_map(lambda _: P(dist.PIPE_AXIS), stage_params),
                jax.tree_util.tree_map(lambda _: P(), head_params),
                jax.tree_util.tree_map(lambda _: P(), x_stream))
    out_specs = (P(),
                 jax.tree_util.tree_map(lambda _: P(dist.PIPE_AXIS), stage_params),
                 jax.tree_util.tree_map(lambda _: P(), head_params),
                 jax.tree_util.tree_map(lambda _: P(), x_stream))
    with dist.manual_axes({dist.PIPE_AXIS}):
        # 1F1B computes loss AND grads inside the region and returns them
        # as plain outputs — nothing transposes through the shard_map
        return _pipe_shard_map(run, mesh, in_specs, out_specs)(
            stage_params, head_params, x_stream)


def _single_stage_1f1b(stage_fn, loss_head, stage_params, head_params, x_stream):
    """n=1 degenerate case: per-microbatch fwd+loss+bwd, accumulated."""
    M = jax.tree_util.tree_leaves(x_stream)[0].shape[0]

    def one(m, acc):
        dstage, dhead, dxs, loss = acc
        x = jax.tree_util.tree_map(
            lambda v: jax.lax.dynamic_index_in_dim(v, m, 0, keepdims=False), x_stream)

        def f(p, hp, x):
            y = stage_fn(p, x, m)
            y = y[0] if isinstance(y, tuple) else y
            return loss_head(hp, y, m)

        l, (dp, dh, dx) = jax.value_and_grad(f, argnums=(0, 1, 2))(stage_params,
                                                                   head_params, x)
        add = lambda a, g: jax.tree_util.tree_map(jnp.add, a, g)
        dxs = jax.tree_util.tree_map(
            lambda acc_, g: jax.lax.dynamic_update_index_in_dim(acc_, g, m, 0), dxs, dx)
        return add(dstage, dp), add(dhead, dh), dxs, loss + l

    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    acc = (zeros(stage_params), zeros(head_params), zeros(x_stream),
           jnp.zeros((), jnp.float32))
    acc = jax.lax.fori_loop(0, M, lambda m, a: one(m, a), acc)
    dstage, dhead, dxs, loss = acc
    return loss, dstage, dhead, dxs


def _single_stage(stage_fn, stage_params, x_stream, remat, with_aux=False):
    fn = jax.checkpoint(stage_fn, static_argnums=()) if remat else stage_fn
    M = jax.tree_util.tree_leaves(x_stream)[0].shape[0]

    def one(x_and_t):
        x, t = x_and_t
        return fn(stage_params, x, t)

    out = jax.lax.map(one, (x_stream, jnp.arange(M)))
    if with_aux:
        stream, aux = out
        return stream, jnp.sum(aux.astype(jnp.float32))
    return out
