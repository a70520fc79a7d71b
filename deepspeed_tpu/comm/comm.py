"""Communication layer.

TPU-native analogue of ``deepspeed/comm/comm.py`` (reference :526
``init_distributed``, :444 ``all_reduce``, :290 ``all_gather_into_tensor``,
:273 ``reduce_scatter_tensor``, :324 ``all_to_all_single``). Design
translation (SURVEY §2.2/§5):

- Process groups → **mesh axis names**. Every collective takes a ``group``
  argument that is an axis name (or tuple of axis names) of the active
  ``jax.sharding.Mesh`` instead of a torch ProcessGroup.
- Two calling contexts:
  * **traced** (inside ``shard_map``): ops lower to XLA collectives
    (``psum``/``all_gather``/``psum_scatter``/``all_to_all``/``ppermute``)
    over ICI/DCN.
  * **host** (outside jit): cross-process ops via
    ``jax.experimental.multihost_utils`` for control-plane exchange.
- ``@timed_op`` → trace-time comms logging (op name, bytes, group) +
  ``jax.named_scope`` so ops are attributable in profiler traces; runtime
  latency inside a compiled program is not observable per-op by design.
"""

import os
import threading
from contextlib import contextmanager, nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import logger
from .overlap import CommOverlapTracker, get_overlap_tracker  # noqa: F401

# ---------------------------------------------------------------------------
# Canonical mesh axis names (process-group equivalents)
# ---------------------------------------------------------------------------
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"
MESH_AXES = (PIPE_AXIS, EXPERT_AXIS, DATA_AXIS, SEQ_AXIS, TENSOR_AXIS)

# Non-expert parameters are data-parallel over expert×data (reference
# expert-data-parallel group, utils/groups.py:202); expert parameters only
# over data.
DP_AXES = (EXPERT_AXIS, DATA_AXIS)

WORLD = DP_AXES + (SEQ_AXIS, TENSOR_AXIS)


class ReduceOp:
    SUM = "sum"
    PRODUCT = "prod"
    MIN = "min"
    MAX = "max"
    AVG = "avg"
    BAND = "band"
    BOR = "bor"
    BXOR = "bxor"
    UNUSED = "unused"


_state = {
    "initialized": False,
    "mesh": None,
    "comms_logger": None,
    # axes currently under manual (shard_map) partitioning — sharding
    # constraints over the full mesh are illegal inside such a region
    "manual_axes": frozenset(),
}


@contextmanager
def manual_axes(axes):
    """Mark ``axes`` as manually partitioned while tracing a shard_map body."""
    prev = _state["manual_axes"]
    _state["manual_axes"] = prev | frozenset(axes)
    try:
        yield
    finally:
        _state["manual_axes"] = prev


def in_manual_region():
    return bool(_state["manual_axes"])


def dp_axes():
    """The data-parallel axes of the mesh that are more than one: what a
    batch axis is sharded over."""
    mesh = get_mesh()
    return tuple(a for a in DP_AXES if mesh.shape[a] > 1)


# The chunked cross-entropy backwards this thread traced, and the cross-chip
# sums of the vocabulary projection's weight gradient the last of them itself
# asks for, ``(backwards, (sums, bytes))``: the training engine reads it
# around a step's first dispatch.
_traced = threading.local()


def tally_head_grad(sums, nbytes):
    _traced.head_grad = (traced_head_grad()[0] + 1, (sums, nbytes))


def traced_head_grad():
    return getattr(_traced, "head_grad", (0, (0, 0)))


def get_manual_axes():
    """Axis names bound by enclosing ``manual_axes`` regions (frozenset)."""
    return _state["manual_axes"]


def constrain(x, spec):
    """``with_sharding_constraint`` that also works inside a PARTIAL-manual
    shard_map region (e.g. the pipeline, manual over ``pipe`` only): entries
    naming manually-partitioned axes are dropped and the constraint resolves
    against the abstract mesh, whose axis types mark the manual split. A
    spec left with no axes after dropping is a no-op."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    if not has_mesh():
        return x
    manual = _state["manual_axes"]
    if manual:
        entries = []
        for e in spec:
            if e is None:
                entries.append(None)
            elif isinstance(e, (tuple, list)):
                kept = tuple(a for a in e if a not in manual)
                entries.append(kept if kept else None)
            else:
                entries.append(None if e in manual else e)
        if all(e is None for e in entries):
            return x
        am = jax.sharding.get_abstract_mesh()
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, PartitionSpec(*entries)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(get_mesh(), spec))


def attention_partition_axes(batch_size, num_heads):
    """Mesh placement for an attention computation on (B, T, H, D) tensors:
    batch over the data axes, heads over (seq, tensor) — the Ulysses-style
    head-scatter layout. Returns ``(dp_axes, head_axes)``; an axis group is
    dropped (empty tuple) when the corresponding dim is not divisible, so the
    kernel wrapper and the model constraints always agree on placement."""
    mesh = get_mesh()
    dp = dp_axes()
    if dp and batch_size % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = ()
    # tensor-major head tiling: the projection side keeps heads sharded by
    # tensor (Megatron-TP layout) and T by seq; the Ulysses all-to-all over
    # seq then appends seq as the MINOR tiling on heads — (tensor, seq) is
    # the only order the partitioner can reach in one collective
    head = tuple(a for a in (TENSOR_AXIS, SEQ_AXIS) if mesh.shape[a] > 1)
    if head and num_heads % int(np.prod([mesh.shape[a] for a in head])) != 0:
        head = ()
    return dp, head


# ---------------------------------------------------------------------------
# Init / world queries
# ---------------------------------------------------------------------------
def init_distributed(dist_backend="xla",
                     auto_mpi_discovery=True,
                     distributed_port=29500,
                     verbose=True,
                     timeout=None,
                     init_method=None,
                     dist_init_required=None,
                     config=None,
                     rank=-1,
                     world_size=-1):
    """Initialize multi-process JAX if a coordinator is configured.

    Reference: ``comm/comm.py:526``. On TPU pods each *host* is one process
    and ``jax.distributed.initialize`` plays the role of the NCCL/MPI
    rendezvous. Single-process (including 1 host × N chips) needs no
    rendezvous and this is a no-op.
    """
    if _state["initialized"]:
        return
    coord = os.environ.get("COORDINATOR_ADDRESS") or os.environ.get("JAX_COORDINATOR_ADDRESS")
    n_proc = os.environ.get("JAX_NUM_PROCESSES") or os.environ.get("WORLD_SIZE")
    proc_id = os.environ.get("JAX_PROCESS_ID") or os.environ.get("RANK")
    if proc_id is None and auto_mpi_discovery:
        # MPI/Slurm launcher rank discovery (reference comm.py:591
        # mpi_discovery): OpenMPI, hydra/MPICH/MVAPICH, srun
        for k in ("OMPI_COMM_WORLD_RANK", "PMI_RANK", "SLURM_PROCID"):
            if k in os.environ:
                proc_id = os.environ[k]
                break
    if n_proc is None and auto_mpi_discovery:
        for k in ("OMPI_COMM_WORLD_SIZE", "PMI_SIZE", "SLURM_NTASKS"):
            if k in os.environ:
                n_proc = os.environ[k]
                break
    if coord is None and os.environ.get("MASTER_ADDR"):
        # torch/DeepSpeed-launcher style rendezvous env
        coord = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', distributed_port)}"
    any_set = coord is not None or n_proc is not None or proc_id is not None
    if any_set and n_proc is None:
        raise RuntimeError(
            "Partial distributed env: found a coordinator address or process id but no process count. "
            "Set JAX_NUM_PROCESSES (or WORLD_SIZE) alongside COORDINATOR_ADDRESS/MASTER_ADDR and "
            "JAX_PROCESS_ID (or RANK).")
    if n_proc is not None and int(n_proc) > 1:
        if verbose:
            logger.info(f"Initializing jax.distributed: coordinator={coord} "
                        f"num_processes={n_proc} process_id={proc_id}")
        # argless path: on Cloud TPU pods jax auto-detects from TPU metadata
        if coord is None:
            jax.distributed.initialize()
        else:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=int(n_proc),
                                       process_id=int(proc_id) if proc_id is not None else None)
        if jax.process_count() != int(n_proc):
            raise RuntimeError(f"distributed init came up with {jax.process_count()} processes, "
                               f"expected {n_proc}")
    _state["initialized"] = True


def is_initialized():
    return _state["initialized"]


def is_available():
    return True


def get_world_size(group=None):
    """Total number of devices (chips), or the size of a mesh axis group."""
    if group is not None:
        mesh = get_mesh()
        axes = (group, ) if isinstance(group, str) else tuple(group)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return size
    return jax.device_count()


def get_rank(group=None):
    """Process index (host rank). Per-chip rank only exists inside shard_map
    (use ``axis_index``)."""
    return jax.process_index()


def get_local_rank():
    return 0


def get_process_count():
    return jax.process_count()


def _tracked_host(op_name):
    """Realized/exposed bracket for a synchronous host-context collective
    (see ``comm/overlap.py``); a no-op context unless a telemetry sink is
    live — the default-off path stays untouched."""
    from ..telemetry import get_sink
    sink = get_sink()
    if sink is not None and sink.enabled:
        return get_overlap_tracker().track_host(op_name)
    return nullcontext()


def barrier(group=None):
    """Cross-process barrier (host context)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        with _tracked_host("barrier"):
            multihost_utils.sync_global_devices("deepspeed_tpu_barrier")


# ---------------------------------------------------------------------------
# Mesh management
# ---------------------------------------------------------------------------
def _default_device_reshape(devices, shape):
    return np.asarray(devices).reshape(shape)


def initialize_mesh(pipe=1, expert=1, data=None, seq=1, tensor=1, devices=None):
    """Create and install the global device mesh.

    Axis order outer→inner: (pipe, expert, data, seq, tensor). Outer axes map
    to slower links (DCN across slices), inner axes ride ICI — the standard
    layout so TP/SP collectives stay on-chip-neighbor links.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    fixed = pipe * expert * seq * tensor
    if data is None:
        if n % fixed != 0:
            raise ValueError(f"device count {n} not divisible by pipe*expert*seq*tensor={fixed}")
        data = n // fixed
    if pipe * expert * data * seq * tensor != n:
        raise ValueError(f"mesh {pipe}x{expert}x{data}x{seq}x{tensor} != {n} devices")
    mesh_devices = _default_device_reshape(devices, (pipe, expert, data, seq, tensor))
    mesh = jax.sharding.Mesh(mesh_devices, MESH_AXES)
    _state["mesh"] = mesh
    return mesh


def set_mesh(mesh):
    _state["mesh"] = mesh


def get_mesh():
    if _state["mesh"] is None:
        initialize_mesh()
    return _state["mesh"]


def has_mesh():
    return _state["mesh"] is not None


@contextmanager
def mesh_context(mesh):
    prev = _state["mesh"]
    _state["mesh"] = mesh
    try:
        yield mesh
    finally:
        _state["mesh"] = prev


def new_group(ranks=None, axis_name=None):
    """Process-group parity shim: groups are mesh axes; returns the axis name."""
    if axis_name is None:
        raise ValueError("TPU build: groups are mesh axes; pass axis_name=")
    return axis_name


# ---------------------------------------------------------------------------
# Comms logging (trace-time)
# ---------------------------------------------------------------------------
def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None):
    from ..utils.comms_logging import CommsLogger
    cfg = getattr(deepspeed_config, "comms_logger", None) if deepspeed_config is not None else None
    logger_ = CommsLogger(cfg)
    if enabled is not None:
        logger_.enabled = enabled
    if verbose is not None:
        logger_.verbose = verbose
    if prof_all is not None:
        logger_.prof_all = prof_all
    if prof_ops is not None:
        logger_.prof_ops = prof_ops
    _state["comms_logger"] = logger_
    return logger_


def get_comms_logger():
    return _state["comms_logger"]


def log_summary():
    if _state["comms_logger"] is not None:
        _state["comms_logger"].log_all()


def _record(op_name, tensor, group):
    cl = _state["comms_logger"]
    from ..telemetry import get_sink
    sink = get_sink()
    if not ((cl is not None and cl.enabled) or (sink is not None and sink.enabled)):
        return
    try:
        size = tensor.size * tensor.dtype.itemsize
    except Exception:
        size = 0
    if cl is not None and cl.enabled:
        cl.append(op_name, str(group), size)
    if sink is not None and sink.enabled:
        # trace-time accounting (same contract as CommsLogger.append: per
        # traced op, not per execution — see utils/comms_logging.py); the
        # group is part of the counter name so TP vs DP traffic of the same
        # op accumulates separately
        gname = "_".join(group) if isinstance(group, (tuple, list)) else str(group)
        sink.counter(f"comm/{op_name}/{gname}/bytes", size)


def _axes(group):
    if group is None:
        return WORLD
    if isinstance(group, str):
        return (group, )
    return tuple(group)


# ---------------------------------------------------------------------------
# Traced collectives — call inside shard_map over the active mesh
# ---------------------------------------------------------------------------
def all_reduce(tensor, op=ReduceOp.SUM, group=None, async_op=False):
    """XLA all-reduce over mesh axis group. Reference ``comm.py:444``."""
    axes = _axes(group)
    _record("all_reduce", tensor, axes)
    with jax.named_scope(f"all_reduce_{'_'.join(axes)}"):
        if op == ReduceOp.SUM:
            return jax.lax.psum(tensor, axes)
        if op == ReduceOp.AVG:
            return jax.lax.pmean(tensor, axes)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(tensor, axes)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(tensor, axes)
        if op == ReduceOp.PRODUCT:
            # exp(psum(log|x|)) with sign parity and zero propagation
            magnitude = jnp.exp(jax.lax.psum(jnp.log(jnp.abs(tensor)), axes))
            neg_count = jax.lax.psum((tensor < 0).astype(jnp.int32), axes)
            sign = jnp.where(neg_count % 2 == 1, -1.0, 1.0).astype(tensor.dtype)
            any_zero = jax.lax.pmax((tensor == 0).astype(jnp.int32), axes)
            return jnp.where(any_zero == 1, jnp.zeros_like(tensor), sign * magnitude)
        raise ValueError(f"Unsupported reduce op {op}")


def inference_all_reduce(tensor, op=ReduceOp.SUM, group=None):
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor, group=None, axis=0, tiled=True):
    """Gather shards along ``axis`` from every member of ``group``.

    Reference ``all_gather_into_tensor`` (``comm.py:290``): with
    ``tiled=True`` the result is concatenated along ``axis`` (flat-tensor
    form); otherwise a new leading group dimension is added.
    """
    axes = _axes(group)
    _record("all_gather", tensor, axes)
    with jax.named_scope(f"all_gather_{'_'.join(axes)}"):
        out = tensor
        for a in reversed(axes):
            out = jax.lax.all_gather(out, a, axis=axis, tiled=tiled)
        return out


# torch.distributed name parity
all_gather_into_tensor = all_gather


def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, scatter_dimension=0, tiled=True):
    """Reduce then scatter along ``scatter_dimension``. Reference ``comm.py:273``."""
    axes = _axes(group)
    _record("reduce_scatter", tensor, axes)
    with jax.named_scope(f"reduce_scatter_{'_'.join(axes)}"):
        out = tensor
        for a in axes:
            out = jax.lax.psum_scatter(out, a, scatter_dimension=scatter_dimension, tiled=tiled)
        return out


reduce_scatter_tensor = reduce_scatter


def all_to_all_single(tensor, group=None, split_axis=0, concat_axis=0, tiled=True):
    """All-to-all over one mesh axis. Reference ``comm.py:324``. Used by MoE
    token dispatch and Ulysses-style sequence↔head redistribution."""
    axes = _axes(group)
    assert len(axes) == 1, "all_to_all runs over exactly one axis"
    _record("all_to_all", tensor, axes)
    with jax.named_scope(f"all_to_all_{axes[0]}"):
        return jax.lax.all_to_all(tensor, axes[0], split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


all_to_all = all_to_all_single


def broadcast(tensor, src=0, group=None):
    """Broadcast from group member ``src`` (traced context)."""
    axes = _axes(group)
    _record("broadcast", tensor, axes)
    with jax.named_scope(f"broadcast_{'_'.join(axes)}"):
        idx = axis_index(axes)
        masked = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
        return jax.lax.psum(masked, axes)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None):
    """All-reduce then mask to dst (XLA has no single-root reduce; the
    all-reduce form is what the compiler would emit on ICI anyway)."""
    return all_reduce(tensor, op=op, group=group)


def ppermute(tensor, perm, group=None):
    """Point-to-point ring exchange; the TPU equivalent of pipeline p2p
    send/recv (reference ``runtime/pipe/p2p.py``)."""
    axes = _axes(group)
    assert len(axes) == 1
    _record("ppermute", tensor, axes)
    with jax.named_scope(f"ppermute_{axes[0]}"):
        return jax.lax.ppermute(tensor, axes[0], perm)


def send_recv_next(tensor, group=PIPE_AXIS):
    """Shift +1 along a ring: rank i's value arrives at rank i+1."""
    n = get_world_size(group)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return ppermute(tensor, perm, group=group)


def send_recv_prev(tensor, group=PIPE_AXIS):
    """Shift -1 along a ring: rank i's value arrives at rank i-1."""
    n = get_world_size(group)
    perm = [(i, (i - 1) % n) for i in range(n)]
    return ppermute(tensor, perm, group=group)


def axis_index(group=None):
    """Linearized index of this device within the group (traced context)."""
    axes = _axes(group)
    idx = jnp.zeros((), dtype=jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def axis_size(group=None):
    axes = _axes(group)
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Host-context cross-process ops (control plane)
# ---------------------------------------------------------------------------
def host_broadcast(in_tree, src=0):
    """Broadcast a pytree from process ``src`` to all processes."""
    if jax.process_count() == 1:
        return in_tree
    from jax.experimental import multihost_utils
    with _tracked_host("host_broadcast"):
        return multihost_utils.broadcast_one_to_all(in_tree,
                                                    is_source=jax.process_index() == src)


def host_allgather(in_tree):
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(lambda x: np.asarray(x)[None], in_tree)
    from jax.experimental import multihost_utils
    with _tracked_host("host_allgather"):
        return multihost_utils.process_allgather(in_tree)


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False):
    barrier(group)


def destroy_process_group(group=None):
    pass


def get_global_rank(group=None, group_rank=0):
    return group_rank
