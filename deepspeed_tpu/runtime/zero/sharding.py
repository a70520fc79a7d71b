"""ZeRO as sharding rules.

This module is the TPU-native replacement for the reference's hook-driven
ZeRO machinery (``runtime/zero/partition_parameters.py`` :601/:874/:940,
``partitioned_param_coordinator.py`` :43, ``parameter_offload.py`` :201 —
~2.6k LoC of monkey-patching and prefetch scheduling). Here the same
semantics are *declared* as ``jax.sharding`` placements and XLA's SPMD
partitioner + latency-hiding scheduler perform the all-gather/reduce-scatter
scheduling that DeepSpeed drives by hand (SURVEY §7 design translation):

- stage 0: params, grads, optimizer state replicated over DP.
- stage 1: optimizer state (and fp32 master params) sharded over DP.
- stage 2: + gradients reduce-scattered into the same sharding.
- stage 3: + model params sharded over DP; each is all-gathered for its
  forward product, freed, and gathered again for the backward's.

Who places a stage-3 gather. The partitioner places one just in time, in
front of its consumer, and the TPU compiler runs it asynchronously beside
exactly ONE product, the one it schedules there: an accident of the
program's text that put half of the large gathers beside products a fifth
of their length. So for the leaves :meth:`ShardingPlanner.gathered_placements`
names (sharded by ZeRO alone, over a group larger than one) the PROGRAM
states which product a gather is due behind (``gather_order.py``; the
reference's fetch/release/prefetch coordinator in the only form a compiled
step has): the engine hands the plan to a loss that takes one (a
``gather_order`` parameter: ``CausalLMModel.loss``), whose unrolled training
forward gathers a weight explicitly behind the product it is to ride and
orders a layer's last product's ``dX`` behind its ``dW``, so that the
partitioner's regather stands behind ``dW``.
Still the compiler's: that a gather is asynchronous at all, how many steps
it takes and how far in front of its due point it starts; every gather of a
scanned layer stack (one body has no next layer to name), of a
rematerialised block, of a leaf a tensor-parallel or pipeline rule splits,
of the embedding and the head, of a model that takes no plan; and every
reduce-scatter. ``stage3_prefetch_bucket_size``, ``stage3_max_live_parameters``
and ``stage3_max_reuse_distance`` are accepted and unread: the order is by
product, not by element count, and a gathered weight is never kept from
forward to backward.

DeepSpeed concepts that survive as rules:
- ``stage3_param_persistence_threshold`` → small params stay replicated.
- MoE-aware groups (``moe/utils.py``) → expert params shard over the
  ``data`` axis only; dense params over ``('expert','data')``.
- TP (Megatron-style, reference delegates to user mpu) → per-param
  PartitionSpec rules matched by path regex, applied before DP sharding.
"""

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm import comm as dist
from ...utils.logging import logger
from .config import ZeroStageEnum


def _path_str(path):
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _spec_axes(spec):
    """Flatten axis names used in a PartitionSpec."""
    used = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.extend(entry)
        else:
            used.append(entry)
    return used


class TensorParallelRules:
    """Ordered (regex, PartitionSpec) rules; first match wins.

    The TPU-native form of inference AutoTP's row/col parser
    (``module_inject/auto_tp.py:84``) generalized to training: rules name
    which dims of which params split over the ``tensor`` (and ``expert``)
    axes.
    """

    def __init__(self, rules=()):
        self.rules = [(re.compile(pat), P(*spec) if not isinstance(spec, P) else spec) for pat, spec in rules]

    def match(self, path_str, ndim):
        for pat, spec in self.rules:
            if pat.search(path_str):
                if len(spec) > ndim:
                    raise ValueError(f"TP rule {pat.pattern} spec {spec} has more dims than param "
                                     f"{path_str} (ndim={ndim})")
                return P(*(tuple(spec) + (None, ) * (ndim - len(spec))))
        return None

    def __bool__(self):
        return bool(self.rules)


def best_shardable_dim(shape, size, taken):
    """Largest dim divisible by ``size`` and not already sharded; None if none.

    Replaces DeepSpeed's flat-buffer padding (``partition_parameters.py:1091``
    pads 1-D partitions): XLA shards a real tensor dim instead, so no padding
    or flattening is needed.
    """
    best = None
    for d, extent in enumerate(shape):
        if d in taken:
            continue
        if extent % size == 0 and extent >= size:
            if best is None or extent > shape[best]:
                best = d
    return best


class ShardingPlanner:
    """Plans NamedShardings for params / grads / optimizer state.

    ``fsdp_axes``: mesh axes forming the ZeRO data-parallel group
    (``('expert','data')`` for dense params; expert params drop ``'expert'``).
    """

    def __init__(self, mesh, zero_config=None, tp_rules=None, expert_pattern=None,
                 pipe_pattern=None):
        self.mesh = mesh
        self.zero = zero_config
        self.stage = zero_config.stage if zero_config is not None else 0
        self.tp_rules = tp_rules if isinstance(tp_rules, TensorParallelRules) else TensorParallelRules(tp_rules or ())
        self.expert_pattern = re.compile(expert_pattern) if expert_pattern else None
        self.pipe_pattern = re.compile(pipe_pattern) if pipe_pattern else None
        self.persistence_threshold = (zero_config.stage3_param_persistence_threshold
                                      if zero_config is not None else int(1e5))

    # -- single-leaf planning ------------------------------------------------
    def _validate(self, spec, shape, path_str):
        """Drop sharding entries whose dim extent isn't divisible by the axis
        size (e.g. 2 kv-heads under tensor=4 fall back to replication)."""
        entries = list(spec)
        changed = False
        for d, entry in enumerate(entries):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry, )
            size = int(np.prod([self.mesh.shape[a] for a in axes]))
            if d >= len(shape) or shape[d] % size != 0:
                entries[d] = None
                changed = True
        if changed:
            logger.debug(f"{path_str}: shape {shape} not divisible by rule {spec}; "
                         f"relaxed to {P(*entries)}")
        return P(*entries)

    def _apply_pipe(self, spec, shape, path_str):
        """Stage-partition layer-stacked params: leading (layer) dim over
        ``pipe`` (the sharding form of reference ``PipelineModule``'s layer
        assignment, ``pipe/module.py:353``)."""
        pipe = self.mesh.shape[dist.PIPE_AXIS]
        if pipe == 1 or self.pipe_pattern is None or not self.pipe_pattern.search(path_str):
            return spec
        if not shape or shape[0] % pipe != 0:
            logger.warning(f"{path_str}: leading dim {shape and shape[0]} not divisible by "
                           f"pipe={pipe}; layer stack left unsharded over pipe")
            return spec
        entries = list(spec)
        if entries[0] is None:
            entries[0] = dist.PIPE_AXIS
        return P(*entries)

    def _dp_axes_for(self, path_str):
        if self.expert_pattern is not None and self.expert_pattern.search(path_str):
            return (dist.DATA_AXIS, )
        return (dist.EXPERT_AXIS, dist.DATA_AXIS)

    def _dp_size(self, axes):
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    def _apply_dp(self, spec, shape, path_str):
        """Append the ZeRO dp axes to the largest free divisible dim."""
        axes = [a for a in self._dp_axes_for(path_str) if self.mesh.shape[a] > 1]
        if not axes:
            return spec
        size = self._dp_size(axes)
        taken = {d for d, e in enumerate(spec) if e is not None}
        dim = best_shardable_dim(shape, size, taken)
        if dim is None:
            logger.debug(f"param {path_str} shape {shape} not divisible by dp={size}; replicating")
            return spec
        entries = list(spec)
        entries[dim] = tuple(axes) if len(axes) > 1 else axes[0]
        return P(*entries)

    def _ruled_spec(self, path_str, shape):
        """What the tensor-parallel and pipeline rules say of a leaf, before
        any ZeRO sharding."""
        ndim = len(shape)
        spec = self.tp_rules.match(path_str, ndim) or P(*([None] * ndim))
        return self._apply_pipe(self._validate(spec, shape, path_str), shape, path_str)

    def param_spec(self, path_str, shape):
        """PartitionSpec for a *model* (compute) parameter."""
        spec = self._ruled_spec(path_str, shape)
        if self.stage >= ZeroStageEnum.weights:
            n_elem = int(np.prod(shape)) if shape else 1
            if n_elem > self.persistence_threshold:
                spec = self._apply_dp(spec, shape, path_str)
        return spec

    def master_spec(self, path_str, shape):
        """PartitionSpec for fp32 master params + optimizer moments."""
        spec = self._ruled_spec(path_str, shape)
        if self.stage >= ZeroStageEnum.optimizer_states:
            spec = self._apply_dp(spec, shape, path_str)
        return spec

    def grad_spec(self, path_str, shape):
        """PartitionSpec for gradients/accumulators: stage >= 2 scatters."""
        spec = self._ruled_spec(path_str, shape)
        if self.stage >= ZeroStageEnum.gradients:
            spec = self._apply_dp(spec, shape, path_str)
        return spec

    def offload_spec(self, path_str, shape):
        """PartitionSpec for *offloaded* optimizer state and the gradients
        feeding it: always scattered over the ZeRO dp axes regardless of
        stage. ZeRO-Offload partitions optimizer state per DP rank so each
        host steps only its shard (reference ``stage_1_and_2.py:1031`` CPU
        accumulation of this rank's partition; ``stage3.py:463``)."""
        return self._apply_dp(self._ruled_spec(path_str, shape), shape, path_str)

    # -- pytree planning -----------------------------------------------------
    def _tree_specs(self, params, leaf_fn):
        def plan(path, leaf):
            shape = np.shape(leaf) if not hasattr(leaf, "shape") else tuple(leaf.shape)
            return leaf_fn(_path_str(path), shape)

        return jax.tree_util.tree_map_with_path(plan, params)

    def param_specs(self, params):
        return self._tree_specs(params, self.param_spec)

    def master_specs(self, params):
        return self._tree_specs(params, self.master_spec)

    def grad_specs(self, params):
        return self._tree_specs(params, self.grad_spec)

    def offload_specs(self, params):
        return self._tree_specs(params, self.offload_spec)

    def shardings(self, specs):
        return jax.tree_util.tree_map(lambda s: NamedSharding(self.mesh, s),
                                      specs,
                                      is_leaf=lambda x: isinstance(x, P))

    def param_shardings(self, params):
        return self.shardings(self.param_specs(params))

    def gathered_placements(self, params):
        """``{leaf path: NamedSharding}`` of the compute parameters whose
        gathers the PROGRAM places under stage 3 (``gather_order.py``): the
        leaves :meth:`param_spec` shards over a ZeRO group larger than one
        and no tensor-parallel or pipeline rule splits (over an axis larger
        than one), each with the placement it has once gathered (replicated). Empty where nothing is
        sharded: stages 0-2, one device, a leaf under the persistence
        threshold. A leaf a rule splits is left to the partitioner."""
        if self.stage < ZeroStageEnum.weights:
            return {}
        out = {}

        def plan(path, leaf):
            ps, shape = _path_str(path), tuple(leaf.shape)
            split = lambda spec: [a for a in _spec_axes(spec) if self.mesh.shape[a] > 1]
            if not split(self._ruled_spec(ps, shape)) and split(self.param_spec(ps, shape)):
                out[ps] = NamedSharding(self.mesh, P())

        jax.tree_util.tree_map_with_path(plan, params)
        return out

    def master_shardings(self, params):
        return self.shardings(self.master_specs(params))

    def opt_state_shardings(self, opt_state, params):
        """Optimizer state leaves that mirror a param get the master sharding;
        scalars (step counts) replicate."""
        master = self.master_specs(params)
        flat_master, _ = jax.tree_util.tree_flatten(master)
        by_shape = {}
        for p_leaf, spec in zip(jax.tree_util.tree_leaves(params), flat_master):
            by_shape.setdefault(tuple(p_leaf.shape), spec)

        def plan(leaf):
            shape = tuple(np.shape(leaf))
            spec = by_shape.get(shape)
            if spec is None:
                spec = P()
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map(plan, opt_state)

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def describe(self, params):
        """Human-readable plan dump (ds_report-style aid)."""
        lines = []

        def show(path, leaf):
            ps = _path_str(path)
            lines.append(f"{ps:60s} {str(tuple(leaf.shape)):20s} param={self.param_spec(ps, tuple(leaf.shape))} "
                         f"master={self.master_spec(ps, tuple(leaf.shape))}")
            return leaf

        jax.tree_util.tree_map_with_path(show, params)
        return "\n".join(lines)
