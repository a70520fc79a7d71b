"""Top-level config.

Analogue of reference ``deepspeed/runtime/config.py`` (``DeepSpeedConfig``
:674, ``_initialize_params`` :767, batch-size triple resolution :738-760).
Accepts the same JSON document (path or dict). TPU extension: a ``mesh``
section declaring parallel axis sizes (tensor/pipeline/sequence/expert); the
data axis is inferred from world size.
"""

import json
import os

from .config_utils import DeepSpeedConfigModel, ConfigField, dict_raise_error_on_duplicate_keys
from .zero.config import DeepSpeedZeroConfig, ZeroStageEnum
from ..utils.logging import logger


class FP16Config(DeepSpeedConfigModel):
    enabled = ConfigField(default=False)
    auto_cast = ConfigField(default=False)
    loss_scale = ConfigField(default=0)
    initial_scale_power = ConfigField(default=16)
    loss_scale_window = ConfigField(default=1000)
    hysteresis = ConfigField(default=2)
    min_loss_scale = ConfigField(default=1)
    fp16_master_weights_and_grads = ConfigField(default=False)
    fp16_opt_level = ConfigField(default=None)  # accepted, unused (apex-ism)


class BF16Config(DeepSpeedConfigModel):
    enabled = ConfigField(default=False)


class OptimizerConfig(DeepSpeedConfigModel):
    type = ConfigField(default=None)
    params = ConfigField(default=dict)
    legacy_fusion = ConfigField(default=False)


class SchedulerConfig(DeepSpeedConfigModel):
    type = ConfigField(default=None)
    params = ConfigField(default=dict)


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference ``runtime/activation_checkpointing/config.py`` keys."""
    partition_activations = ConfigField(default=False)
    contiguous_memory_optimization = ConfigField(default=False)
    cpu_checkpointing = ConfigField(default=False)
    number_checkpoints = ConfigField(default=None)
    synchronize_checkpoint_boundary = ConfigField(default=False)
    profile = ConfigField(default=False)
    # TPU extension: jax.checkpoint policy name (e.g. "dots_saveable",
    # "nothing_saveable", "dots_with_no_batch_dims_saveable")
    policy = ConfigField(default=None)


class MonitorBackendConfig(DeepSpeedConfigModel):
    enabled = ConfigField(default=False)
    output_path = ConfigField(default="")
    job_name = ConfigField(default="DeepSpeedJobName")
    # wandb-only
    team = ConfigField(default=None)
    group = ConfigField(default=None)
    project = ConfigField(default=None)


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled = ConfigField(default=False)
    verbose = ConfigField(default=False)
    prof_all = ConfigField(default=True)
    debug = ConfigField(default=False)
    prof_ops = ConfigField(default=list)


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled = ConfigField(default=False)
    recompute_fwd_factor = ConfigField(default=0.0)
    profile_step = ConfigField(default=1)
    module_depth = ConfigField(default=-1)
    top_modules = ConfigField(default=1)
    detailed = ConfigField(default=True)
    output_file = ConfigField(default=None)


class TelemetryConfig(DeepSpeedConfigModel):
    """TPU extension: the unified telemetry sink (``deepspeed_tpu/telemetry``).

    Default-off; when enabled the engine writes a structured event stream
    (``telemetry.jsonl``) and a Perfetto-loadable ``trace.json`` under
    ``output_path``. See ``benchmarks/OBSERVABILITY.md``.
    """
    enabled = ConfigField(default=False)
    output_path = ConfigField(default="telemetry")
    # events buffered before an automatic flush (spans + gauges; counters
    # and histograms snapshot at each flush)
    flush_interval = ConfigField(default=100)
    # "chrome" writes trace.json in Chrome-trace format; "none" disables it
    trace_format = ConfigField(default="chrome")
    # histogram sliding window: percentiles always describe roughly the
    # last hist_window_s seconds from a bounded chunked reservoir of
    # hist_max_samples values (long-running serving never freezes on
    # startup-era samples)
    hist_window_s = ConfigField(default=300.0)
    hist_max_samples = ConfigField(default=4096)
    # per-request tracing (gateway/scheduler span trees + flow links);
    # rides the enabled sink — flip off to keep only aggregate telemetry
    request_tracing = ConfigField(default=True)
    # anomaly flight recorder (telemetry/flight_recorder.py): always-on
    # ring of recent full-resolution events, dumped around anomalies.
    # Keys: enabled (true) / capacity (8192) / post_window_s (0.25) /
    # min_interval_s (1.0)
    flight_recorder = ConfigField(default=dict)
    # SLO engine (telemetry/slo.py): objectives + multi-window burn-rate
    # alerting. Keys: objectives (list of specs) / fast_window_s /
    # slow_window_s / burn_threshold / eval_interval_s; the serving
    # gateway falls back to its default objective slate when none given
    slo = ConfigField(default=dict)
    # on-demand XLA profiling (telemetry/profiler.py): capture one device
    # trace of this many seconds at the training engine's next report
    # interval (0 = off; serving uses POST /v1/debug/profile instead)
    profile_report_s = ConfigField(default=0.0)


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation = ConfigField(default="Warn")
    load_universal = ConfigField(default=False)
    use_node_local_storage = ConfigField(default=False)
    parallel_write = ConfigField(default=dict)
    # TPU extension: async checkpointing via a background commit thread
    async_save = ConfigField(default=False)


class MeshConfig(DeepSpeedConfigModel):
    """TPU extension: parallel axis sizes for the device mesh.

    Axis order (outer→inner, DCN-slowest to ICI-fastest):
    ``('pipe', 'data', 'seq', 'tensor', 'expert-implied')``. The reference has
    no first-class mesh; TP was delegated to a user mpu (SURVEY §2.3).
    """
    tensor_parallel_size = ConfigField(default=1, aliases=("model_parallel_size",))
    pipeline_parallel_size = ConfigField(default=1)
    sequence_parallel_size = ConfigField(default=1)
    expert_parallel_size = ConfigField(default=1)
    data_parallel_size = ConfigField(default=None)  # inferred if None
    # device assignment order, advanced use
    axis_order = ConfigField(default=("pipe", "data", "seq", "tensor"))


class DeepSpeedConfigError(Exception):
    pass


class DeepSpeedConfig(DeepSpeedConfigModel):
    _allow_extra = True  # top level tolerates sections consumed elsewhere

    train_batch_size = ConfigField(default=None)
    train_micro_batch_size_per_gpu = ConfigField(default=None)
    gradient_accumulation_steps = ConfigField(default=None)
    steps_per_print = ConfigField(default=10)
    dump_state = ConfigField(default=False)
    disable_allgather = ConfigField(default=False)
    communication_data_type = ConfigField(default=None)
    prescale_gradients = ConfigField(default=False)
    gradient_predivide_factor = ConfigField(default=1.0)
    sparse_gradients = ConfigField(default=False)
    gradient_clipping = ConfigField(default=0.0)
    fp32_allreduce = ConfigField(default=False)
    seed = ConfigField(default=1234)

    optimizer = ConfigField(default=OptimizerConfig)
    scheduler = ConfigField(default=SchedulerConfig)
    fp16 = ConfigField(default=FP16Config)
    bf16 = ConfigField(default=BF16Config, aliases=("bfloat16",))
    amp = ConfigField(default=dict)
    zero_optimization = ConfigField(default=DeepSpeedZeroConfig)
    activation_checkpointing = ConfigField(default=ActivationCheckpointingConfig)
    # HF-style boolean alias; folded into activation_checkpointing in __init__
    gradient_checkpointing = ConfigField(default=None)

    tensorboard = ConfigField(default=MonitorBackendConfig)
    csv_monitor = ConfigField(default=MonitorBackendConfig)
    wandb = ConfigField(default=MonitorBackendConfig)
    comms_logger = ConfigField(default=CommsLoggerConfig)
    telemetry = ConfigField(default=TelemetryConfig)
    flops_profiler = ConfigField(default=FlopsProfilerConfig)

    wall_clock_breakdown = ConfigField(default=False)
    memory_breakdown = ConfigField(default=False)
    dataloader_drop_last = ConfigField(default=False)
    data_types = ConfigField(default=dict)
    checkpoint = ConfigField(default=CheckpointConfig)
    # RLHF hybrid engine (reference runtime/hybrid_engine.py; keys:
    # enabled, max_out_tokens, kernel_inject)
    hybrid_engine = ConfigField(default=dict)
    elasticity = ConfigField(default=dict)
    autotuning = ConfigField(default=dict)
    compression_training = ConfigField(default=dict)
    data_efficiency = ConfigField(default=dict)
    curriculum_learning = ConfigField(default=dict)
    progressive_layer_drop = ConfigField(default=dict)
    sparse_attention = ConfigField(default=dict)
    aio = ConfigField(default=dict)
    mesh = ConfigField(default=MeshConfig)
    # pipeline section (used when model is a PipelineModule)
    pipeline = ConfigField(default=dict)
    zero_allow_untested_optimizer = ConfigField(default=True)
    zero_force_ds_cpu_optimizer = ConfigField(default=False)

    def __init__(self, config, mpu=None, world_size=None):
        if isinstance(config, (str, os.PathLike)):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"Config file {config} not found")
            with open(config, "r") as f:
                config_dict = json.load(f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            config_dict = config
        elif config is None:
            config_dict = {}
        else:
            raise DeepSpeedConfigError(f"Expected a config path or dict, got {type(config)}")

        super().__init__(config_dict)
        self.raw_config = config_dict
        self._warn_inert_sections(config_dict)

        if world_size is None:
            try:
                from .. import comm as dist
                world_size = dist.get_world_size() if dist.is_initialized() else 1
            except Exception:
                world_size = 1
        self.world_size = world_size
        self.mpu = mpu
        if mpu is not None and self.mesh.data_parallel_size is None:
            try:
                # mpu reports the combined DP group (DeepSpeed convention,
                # includes expert ranks); our data axis excludes expert
                mpu_dp = mpu.get_data_parallel_world_size()
                if mpu_dp % self.mesh.expert_parallel_size == 0:
                    self.mesh.data_parallel_size = mpu_dp // self.mesh.expert_parallel_size
            except Exception:
                pass
        if self.gradient_checkpointing is not None:
            if self.gradient_checkpointing and self.activation_checkpointing.policy is None:
                self.activation_checkpointing.policy = "nothing_saveable"
        if dict(config_dict.get("nebula", {}) or {}).get("enabled"):
            # nebula shim (reference nebula/config.py): the service's async
            # tiered persistence maps onto the native Orbax async engine —
            # but an EXPLICIT checkpoint.async_save in the config wins
            from ..nebula import DeepSpeedNebulaConfig
            self.nebula = DeepSpeedNebulaConfig(config_dict)
            if "async_save" not in dict(config_dict.get("checkpoint", {}) or {}):
                self.checkpoint.async_save = True
        else:
            self.nebula = None
        if dict(config_dict.get("elasticity", {})).get("enabled"):
            # elastic batch resolution (reference engine.py:462 guard +
            # elasticity.py:233): the pre-computed elastic batch overrides any
            # explicit batch keys so resizes keep the effective batch fixed
            from ..elasticity import compute_elastic_config
            final_batch, _, micro = compute_elastic_config(
                config_dict, world_size=self.world_size, return_microbatch=True)
            self.train_batch_size = final_batch
            if micro is not None:
                self.train_micro_batch_size_per_gpu = micro
                self.gradient_accumulation_steps = None
        self._resolve_data_parallel_size()
        self._configure_train_batch_size()
        self._do_sanity_check()

    # Config sections parsed for DeepSpeed-JSON compatibility but not (yet)
    # backed by an implementation. Silent acceptance would be a correctness
    # trap for users porting configs, so their presence warns loudly. Remove
    # entries as the corresponding subsystem lands.
    # ("sparse_attention" stays here deliberately: the block-sparse subsystem
    # ships as an ops-level API — ops/sparse_attention — but this config
    # *section* does not rewire a model's attention by itself.)
    INERT_SECTIONS = frozenset({
        "amp", "sparse_attention", "sparse_gradients", "communication_data_type",
        "fp32_allreduce", "disable_allgather", "memory_breakdown", "dump_state",
        "data_types", "zero_force_ds_cpu_optimizer",
    })

    def _warn_inert_sections(self, config_dict):
        for key in sorted(set(config_dict) & self.INERT_SECTIONS):
            val = config_dict[key]
            if val in (False, None) or val == {} or val == []:
                continue  # explicitly disabled / empty: nothing being ignored
            if isinstance(val, dict) and val.get("enabled", True) is False:
                continue  # {"enabled": false, ...}: disabled section
            logger.warning(
                f"config section '{key}' is accepted for DeepSpeed-JSON compatibility but "
                f"has NO effect in this build — remove it or expect different behavior")

    # -- batch size arithmetic (reference config.py:738-760) ---------------
    def _resolve_data_parallel_size(self):
        """The ZeRO data-parallel group spans expert×data; data is what's
        left of the world after tp/pp/sp/ep are laid out."""
        m = self.mesh
        non_dp = m.tensor_parallel_size * m.pipeline_parallel_size * m.sequence_parallel_size
        if self.world_size % non_dp != 0:
            raise DeepSpeedConfigError(
                f"world size {self.world_size} not divisible by tp*pp*sp = {non_dp}")
        combined_dp = self.world_size // non_dp  # expert * data
        if combined_dp % m.expert_parallel_size != 0:
            raise DeepSpeedConfigError(
                f"dp group size {combined_dp} not divisible by expert_parallel_size "
                f"{m.expert_parallel_size}")
        inferred_data = combined_dp // m.expert_parallel_size
        if m.data_parallel_size is None:
            m.data_parallel_size = inferred_data
        elif m.data_parallel_size != inferred_data and self.world_size > 1:
            raise DeepSpeedConfigError(
                f"data_parallel_size {m.data_parallel_size} inconsistent with world size "
                f"{self.world_size} / (tp*pp*sp*ep) = {inferred_data}")

    def _configure_train_batch_size(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        # batch replicas span the full ZeRO dp group: expert × data
        dp = self.mesh.data_parallel_size * self.mesh.expert_parallel_size

        if train_batch is not None and micro_batch is not None and grad_acc is not None:
            pass
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // micro_batch
            grad_acc //= dp
            grad_acc = max(1, grad_acc)
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // dp
            micro_batch //= grad_acc
            micro_batch = max(1, micro_batch)
        elif micro_batch is not None and grad_acc is not None:
            train_batch = micro_batch * grad_acc * dp
        elif train_batch is not None:
            grad_acc = 1
            micro_batch = train_batch // dp
        elif micro_batch is not None:
            train_batch = micro_batch * dp
            grad_acc = 1
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu needs to be provided")

        self.train_batch_size = train_batch
        self.train_micro_batch_size_per_gpu = micro_batch
        self.gradient_accumulation_steps = grad_acc

        if train_batch != micro_batch * grad_acc * dp:
            raise DeepSpeedConfigError(
                f"Check batch related parameters. train_batch_size is not equal to "
                f"micro_batch_per_gpu * gradient_acc_step * world_size "
                f"{train_batch} != {micro_batch} * {grad_acc} * {dp}")

    def _do_sanity_check(self):
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        if self.zero_optimization.stage > 0 and self.optimizer.type is None:
            logger.debug("ZeRO enabled with client/implicit optimizer")
        if self.gradient_accumulation_steps < 1:
            raise DeepSpeedConfigError("gradient_accumulation_steps must be >= 1")

    # -- convenience properties mirroring engine accessors ------------------
    @property
    def zero_enabled(self):
        return self.zero_optimization.stage > 0

    @property
    def zero_stage(self):
        return self.zero_optimization.stage

    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @property
    def loss_scale(self):
        return self.fp16.loss_scale if self.fp16.enabled else 0

    @property
    def dynamic_loss_scale(self):
        return self.fp16.enabled and self.fp16.loss_scale == 0

    def print_config(self, name="DeepSpeedConfig"):
        logger.info("{}:".format(name))
        logger.info(json.dumps(self.to_dict(), indent=2, default=str, sort_keys=True))
