"""ZeRO config.

Analogue of reference ``deepspeed/runtime/zero/config.py`` (``ZeroStageEnum``
:263 area) and ``offload_config.py:94``. Same JSON keys. On TPU the stages map
to sharding rules over the ``data`` mesh axis (see ``zero/sharding.py``)
rather than hook-driven partitioning; the tuning knobs that only make sense
for hook scheduling (prefetch buckets, reuse distance) are accepted for config
compatibility and surfaced to the sharding planner where meaningful.
"""

from ..config_utils import DeepSpeedConfigModel, ConfigField


class ZeroStageEnum:
    disabled = 0
    optimizer_states = 1
    gradients = 2
    weights = 3
    max_stage = 3


class OffloadDeviceEnum:
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


def _check_offload_device(value):
    valid = (OffloadDeviceEnum.none, OffloadDeviceEnum.cpu, OffloadDeviceEnum.nvme)
    if value not in valid:
        raise ValueError(f"offload device must be one of {valid}, got {value}")
    return value


class DeepSpeedZeroOffloadParamConfig(DeepSpeedConfigModel):
    device = ConfigField(default=OffloadDeviceEnum.none, validator=_check_offload_device)
    nvme_path = ConfigField(default=None)
    buffer_count = ConfigField(default=5)
    buffer_size = ConfigField(default=int(1e8))
    max_in_cpu = ConfigField(default=int(1e9))
    pin_memory = ConfigField(default=False)


def _check_nonneg_int(value):
    value = int(value)
    if value < 0:
        raise ValueError(f"expected a non-negative integer, got {value}")
    return value


def _check_pos_int(value):
    value = int(value)
    if value < 1:
        raise ValueError(f"expected a positive integer, got {value}")
    return value


class DeepSpeedZeroOffloadOptimizerConfig(DeepSpeedConfigModel):
    device = ConfigField(default=OffloadDeviceEnum.none, validator=_check_offload_device)
    nvme_path = ConfigField(default=None)
    buffer_count = ConfigField(default=4)
    pin_memory = ConfigField(default=False)
    pipeline_read = ConfigField(default=False)
    pipeline_write = ConfigField(default=False)
    fast_init = ConfigField(default=False)
    ratio = ConfigField(default=1.0)
    # ZeRO-Infinity streaming pipeline (zero/param_offload.py
    # LayerStreamExecutor): depth of the bidirectional host->device
    # parameter / NVMe optimizer-state look-ahead, and the max in-flight
    # gradient device->host fetches. prefetch_depth=0 is the fully
    # SYNCHRONOUS no-overlap step (every put fenced at point of use) — a
    # measurement/debug mode, slower than the pre-pipeline 1-deep async
    # look-ahead; use prefetch_depth=1 for that legacy behavior. Numerics
    # are bit-identical at any setting; each extra depth step costs ~one
    # layer block of HBM headroom.
    prefetch_depth = ConfigField(default=2, validator=_check_nonneg_int)
    fetch_window = ConfigField(default=4, validator=_check_pos_int)

    @property
    def pipeline(self):
        return self.pipeline_read or self.pipeline_write


def _check_stage(value):
    if value is True:
        return ZeroStageEnum.optimizer_states
    if value is False:
        return ZeroStageEnum.disabled
    value = int(value)
    if not (0 <= value <= ZeroStageEnum.max_stage):
        raise ValueError(f"zero stage must be in [0, {ZeroStageEnum.max_stage}]")
    return value


class DeepSpeedZeroConfig(DeepSpeedConfigModel):
    """``zero_optimization`` section (same keys as the reference).

    ``stage3_prefetch_bucket_size`` is accepted and unread: where the
    reference prefetches by element count, the compiled step states which
    PRODUCT each large gather is due behind (``gather_order.py``), for every
    leaf over ``stage3_param_persistence_threshold`` that ZeRO alone shards."""

    stage = ConfigField(default=0, validator=_check_stage)
    contiguous_gradients = ConfigField(default=True)
    reduce_scatter = ConfigField(default=True)
    reduce_bucket_size = ConfigField(default=int(5e8))
    allgather_partitions = ConfigField(default=True)
    allgather_bucket_size = ConfigField(default=int(5e8))
    overlap_comm = ConfigField(default=None)  # resolved: default True at stage 3
    load_from_fp32_weights = ConfigField(default=True)
    elastic_checkpoint = ConfigField(default=False)
    offload_param = ConfigField(default=DeepSpeedZeroOffloadParamConfig)
    offload_optimizer = ConfigField(default=DeepSpeedZeroOffloadOptimizerConfig)
    sub_group_size = ConfigField(default=int(1e9))
    cpu_offload_param = ConfigField(default=None)  # deprecated in ref; kept
    cpu_offload_use_pin_memory = ConfigField(default=None)
    cpu_offload = ConfigField(default=None)
    stage3_max_live_parameters = ConfigField(default=int(1e9))
    stage3_max_reuse_distance = ConfigField(default=int(1e9))
    stage3_prefetch_bucket_size = ConfigField(default=int(5e7))
    stage3_param_persistence_threshold = ConfigField(default=int(1e5))
    stage3_gather_16bit_weights_on_model_save = ConfigField(
        default=False, aliases=("stage3_gather_fp16_weights_on_model_save",))
    ignore_unused_parameters = ConfigField(default=True)
    legacy_stage1 = ConfigField(default=False)
    round_robin_gradients = ConfigField(default=False)
    zero_hpz_partition_size = ConfigField(default=1)
    memory_efficient_linear = ConfigField(default=True)

    def __init__(self, param_dict=None):
        super().__init__(param_dict)
        if self.overlap_comm is None:
            self.overlap_comm = self.stage == ZeroStageEnum.weights
        # deprecated cpu_offload flags fold into offload_optimizer/param
        if self.cpu_offload:
            self.offload_optimizer.device = OffloadDeviceEnum.cpu
        if self.cpu_offload_param:
            self.offload_param.device = OffloadDeviceEnum.cpu
