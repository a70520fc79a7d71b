"""What one sync of the serving pump is made of, shared by the scheduler
(``scheduler.py``) and the device drafter's launches (``device_draft.py``):
the flight a launch leaves behind for its landing, the host block of a launch
and the mark of a token that is still on the device, and the choice of a token
from a step program's logits."""

import collections

import jax
import jax.numpy as jnp

from ..comm import comm as dist


class _Flight:
    """A sync that was launched and has not landed: what the landing needs
    to fetch its block and deliver it. ``out`` is the step program's result
    behind the pool (tokens, then logits, routing choice and MoE stats where
    the program returns them), still on the device; ``rows`` the decode rows
    it advances ``K`` tokens each; ``chunk`` the prefill row's ``(request,
    pos, take, final)`` on a chunk sync; ``t0`` when its iteration began;
    ``program`` what the capacity gauges price it as (``DecodeScheduler.
    _dispatched`` at its launch; None with the sink off)."""

    __slots__ = ("out", "K", "collect", "rows", "chunk", "t0", "program")

    def __init__(self, out, K, collect, rows, chunk=None):
        self.out = out
        self.K = K
        self.collect = collect
        self.rows = rows
        self.chunk = chunk
        self.t0 = 0.0
        self.program = None

    @property
    def final(self):
        """Whether this sync carries a prompt's last chunk."""
        return self.chunk is not None and self.chunk[3]


# the id of a row whose last token is still on the device: column 0 of the
# host's ids block says so with this, and :func:`_merge_carried` fills it in
_CARRIED = -1

# The host block of one launch (:meth:`DecodeScheduler._assemble`): the numpy
# operands of a step program in its argument order, then whether any row
# samples and whether any collects logits (which pick the program's variant).
_Operands = collections.namedtuple(
    "_Operands", "ids lens spans seeds steps flags temps topks topps sampling collect")


def _merge_carried(ids, toks):
    """The ids block of a sync launched ahead: a row flagged ``_CARRIED`` in
    column 0 takes its id from the last row of ``toks``, the (K, num_slots)
    token block of the sync in flight; prompt tokens and the ids the host
    knew stay as they are. Outside the step programs, which take the same
    operands as when the host fed every id."""
    col = ids[:, 0]
    return ids.at[:, 0].set(jnp.where(col == _CARRIED, toks[-1], col))


def _replicate_logits(l, tp_size):
    """Gather vocab-sharded step logits to replicated BEFORE sampling
    (tp>1 only): the gather is exact concatenation, and `jax.random`
    bit-generation is NOT sharding-invariant on every jax version — a
    categorical draw over a vocab-sharded operand can partition the
    counter differently and change the sample. Replicated operands make
    the sampling math byte-identical to the tp=1 program's. (N, V) per
    sync is noise next to the model forward."""
    if tp_size > 1:
        from jax.sharding import PartitionSpec
        l = jax.lax.with_sharding_constraint(
            l, jax.sharding.NamedSharding(dist.get_mesh(),
                                          PartitionSpec(*([None] * l.ndim))))
    return l


def _sample_slot(seed, step, logits, do_sample, temperature, top_k, top_p):
    """Per-slot token choice with fully-dynamic sampling params (one compiled
    program serves any mix of greedy/sampled requests). ``logits``: (V,)
    f32. top-k uses a dynamic kth-largest threshold (sort is static-shape);
    top-p then keeps the smallest prefix with cumulative prob >= top_p of
    the top-k-FILTERED distribution (same sequential-filter semantics as
    the static path's ``_sample_tokens``)."""
    V = logits.shape[0]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    x = logits / jnp.maximum(temperature, 1e-6)
    kth = jnp.sort(x)[::-1][jnp.clip(top_k - 1, 0, V - 1)]
    x = jnp.where((top_k > 0) & (x < kth), -jnp.inf, x)
    desc = jnp.sort(x)[::-1]  # re-sort AFTER top-k: nucleus over the filtered dist
    probs = jax.nn.softmax(desc)
    cum = jnp.cumsum(probs)
    keep = jnp.concatenate([jnp.ones((1, ), bool), cum[:-1] < top_p])
    threshold = jnp.min(jnp.where(keep, desc, jnp.inf))
    x = jnp.where((top_p < 1.0) & (x < threshold), -jnp.inf, x)
    key = jax.random.fold_in(jax.random.key(seed), step)
    sampled = jax.random.categorical(key, x).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)


def _sampler(sampling):
    """Every step program's choice of a token a row from ``(rows, V)`` logits,
    under the ``sample`` scope: the seeded draw of :func:`_sample_slot` at each
    row's absolute step where some row of the program samples, else the
    arg-max."""
    def sample(l2, seeds, steps, flags, temps, topks, topps):
        with jax.named_scope("sample"):
            if sampling:
                return jax.vmap(_sample_slot)(seeds, steps, l2, flags, temps, topks, topps)
            return jnp.argmax(l2, axis=-1).astype(jnp.int32)

    return sample
