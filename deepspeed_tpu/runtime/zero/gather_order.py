"""Which product a ZeRO-3 weight gather is due behind.

Under stage 3 every large weight rests as a shard and is gathered twice a
step, once for its forward product and once more for the backward's. The
TPU compiler runs such a gather asynchronously inside ONE product's fusion
(two where it has them to spare): the product it schedules in front of the
gather's consumer, and a product carries one gather. Left to itself that is
an accident of the program's text: in cell 3 (OPT-1.3B over four chips) the
33.5 MB gather of a layer's first MLP weight rode the attention output's
product, a third of its length, and the regather of its last MLP weight
rode the layer above's attention products in the backward, which dragged
the three small gathers beside it along. The compiler honours an order, so
the program states one (the reference's ``stage3_prefetch_bucket_size`` /
``PartitionedParameterCoordinator`` in the only form a compiled step has):

- *forward* (:func:`due_behind`): the gather is made explicit (the shard
  constrained to its gathered placement, :func:`gather`) and tied with
  ``jax.lax.optimization_barrier`` to the result of the product it is to
  ride; the layer loop of ``models/transformer.py`` decides which, so that
  every product carries the gather that fits it.
- *backward* (:func:`ordered_product` with ``dx_behind_dw``): a sharded
  weight's two products are ordered, ``dX`` behind ``dW``, so that the
  partitioner's own regather of the weight for ``dX`` stands behind ``dW``,
  which has exactly ``dX``'s operations. The model asks for it where a
  regather has nothing of its size in front of it (a layer's last product,
  the backward's first); elsewhere the barrier only costs: on a product
  whose ``dy`` is an activation's gradient it writes out what the compiler
  fuses into both consumers (+0.55 GiB live in cell 3's compiled step).

The memory contract stays ZeRO-3's: a product's residual is the SHARD, the
gathered copy reaches the forward product under ``stop_gradient`` and is
never saved, and the backward regathers. What NOT to write (each seen in a
compile for a described ``v5e:2x2``): a barrier on a gather's INPUT leaves a
blocking ``all-gather`` between the two products; an explicit gather in the
backward is merged with the forward's and the gathered weight is kept from
forward to backward (ZeRO-2's memory); and a product hides one gather, so a
gather displaced from its product needs another of its own.
``tools/gather_riders.py`` reads from a compiled step's text which product
each gather rides.

The engine hands the model the plan as an argument
(``ShardingPlanner.gathered_placements``: the placements of the leaves the
program orders); nothing here is module state but the note of what a
traced step placed (:func:`tally` / :func:`traced`), which
``engine.train_batch`` turns into the gauges
``zero/param_gathers_pinned_per_step`` and
``zero/param_gather_bytes_per_step``.
"""

import dataclasses
import math
import threading
from functools import partial

import jax
import jax.numpy as jnp

GATHER_SCOPE = "zero3_gather"
_traced = threading.local()


def tally(path, direction, w):
    """Note that the program being traced states the place of the
    ``direction`` (``"forward"`` / ``"backward"``) gather of the weight ``w``
    at ``path``."""
    nbytes = math.prod(w.shape) * jnp.dtype(w.dtype).itemsize
    traced().append(((path, direction), nbytes))


def traced():
    """``[((path, direction), gathered bytes)]`` noted on this thread so
    far, in trace order; a program traced twice notes its gathers twice, so
    a reader keys what one step added by ``(path, direction)``."""
    return _traced.__dict__.setdefault("gathers", [])


def gather(w, placement, path=""):
    """The shard ``w`` at its gathered placement, outside differentiation:
    the explicit form of the gather the partitioner would place just in time."""
    tally(path, "forward", w)
    with jax.named_scope(f"{GATHER_SCOPE}/{path}"):  # the gather's name in the compiled text
        return jax.lax.with_sharding_constraint(jax.lax.stop_gradient(w), placement)


@jax.custom_vjp
def due_behind(y, gathered):
    """``(y, gathered)``, neither usable before both are there: the gathers
    that make ``gathered`` are due behind the product that makes ``y``, and
    the compiler lays them beside it. The cotangents pass untied."""
    return jax.lax.optimization_barrier((y, gathered))


def _due_behind_fwd(y, gathered):
    return due_behind(y, gathered), None


def _due_behind_bwd(_, cts):
    return cts


due_behind.defvjp(_due_behind_fwd, _due_behind_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _product(spec, dx_behind_dw, x, w, gathered):
    return jnp.einsum(spec, x, gathered)


def _product_fwd(spec, dx_behind_dw, x, w, gathered):
    # the residual is the SHARD: the gathered copy is not kept
    return jnp.einsum(spec, x, gathered), (x, w)


def _product_bwd(spec, dx_behind_dw, res, dy):
    x, w = res
    ins, out = spec.split("->")
    a, b = ins.split(",")
    dw = jnp.einsum(f"{a},{out}->{b}", x, dy)
    if dx_behind_dw:
        # the partitioner's regather of w for dX stands behind a product
        # of dX's own length
        dy, dw = jax.lax.optimization_barrier((dy, dw))
    dx = jnp.einsum(f"{out},{b}->{a}", dy, w)
    return dx, dw, jnp.zeros_like(w)


_product.defvjp(_product_fwd, _product_bwd)


def ordered_product(spec, x, w, placement, gathered=None, path="", dx_behind_dw=False):
    """``einsum(spec, x, w)`` for a weight that rests as a ZeRO-3 shard and
    whose gathered placement is ``placement``. ``gathered``: the weight
    already gathered and tied behind an earlier product (:func:`due_behind`);
    without it the forward gather is explicit here, in front of the product,
    where the partitioner would have put it. The backward regathers (the
    partitioner's gather of the shard, just in time); with ``dx_behind_dw``
    the program states its place too: ``dX`` is due behind ``dW``."""
    if gathered is None:
        gathered = gather(w, placement, path)
    if dx_behind_dw:
        tally(path, "backward", w)
    return _product(spec, dx_behind_dw, x, w, gathered)


@dataclasses.dataclass(frozen=True)
class GatherOrder:
    """What a module is told of the order. ``placements``: ``{leaf path:
    gathered placement}`` of the leaves the program orders (the engine's
    plan, whole). ``gathered``: ``{leaf path: weight}`` already gathered and
    tied behind an earlier product; ONE dict for a whole forward, filled by
    :meth:`due_behind` and emptied by the products that take from it.
    ``prefix``: the module's own path. ``handed_down``: ``{leaf path:
    shard}`` of weights of a LATER module that this one is to gather behind
    a product of its own (a layer's first projection rides the layer
    below's last attention product)."""
    placements: dict
    gathered: dict = dataclasses.field(default_factory=dict)
    prefix: str = ""
    handed_down: dict = dataclasses.field(default_factory=dict)

    def sub(self, name, handed_down=None):
        """The order of the child module ``name``, or None if the plan holds
        no leaf of it."""
        prefix = f"{self.prefix}{name}/"
        if not any(path.startswith(prefix) for path in self.placements):
            return None
        return GatherOrder(self.placements, self.gathered, prefix, handed_down or {})

    def holds(self, path):
        return self.prefix + path in self.placements

    def due_behind(self, y, shards):
        """``y``, with the weights ``shards`` (``{path under this module:
        shard}``; those the plan does not hold are skipped) gathered and due
        behind the product that made it; the products that read them find
        them in ``gathered``."""
        tied = {self.prefix + path: gather(w, self.placements[self.prefix + path],
                                           self.prefix + path)
                for path, w in shards.items() if self.holds(path)}
        if not tied:
            return y
        y, tied = due_behind(y, tied)
        self.gathered.update(tied)
        return y

    def hand_down(self, y):
        """``y`` with the weights handed down to this module gathered and
        due behind the product that made it."""
        return GatherOrder(self.placements, self.gathered).due_behind(y, self.handed_down)

    def product(self, spec, x, w, path, dx_behind_dw=False):
        """``einsum(spec, x, w)`` for the weight at ``path``, ordered if the
        plan holds the path (``dx_behind_dw``: in the backward too)."""
        placement = self.placements.get(self.prefix + path)
        if placement is None:
            return jnp.einsum(spec, x, w)
        return ordered_product(spec, x, w, placement, self.gathered.pop(self.prefix + path, None),
                               self.prefix + path, dx_behind_dw)
