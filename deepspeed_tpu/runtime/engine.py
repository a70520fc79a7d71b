"""Training engine.

TPU-native analogue of reference ``deepspeed/runtime/engine.py``
(``DeepSpeedEngine`` :181, ``forward`` :1624, ``backward`` :1765, ``step``
:1961, ``save_checkpoint`` :2802, ``load_checkpoint`` :2497). Design
translation (SURVEY §7): instead of wrapping an eager module with hooks, the
engine compiles ONE fused train step — forward, backward, gradient
accumulation (``lax.scan``), ZeRO resharding, clipping, optimizer update,
loss-scale management — into a single pjit program over the device mesh.
A ``forward()/backward()/step()`` 3-call facade is kept for API parity.

Model contract (the eager-module contract cannot survive tracing): ``model``
is a pure loss function ``loss_fn(params, batch, rng) -> loss`` (or
``(loss, aux_dict)``), or an object exposing ``.loss`` with that signature
(all models in ``deepspeed_tpu.models`` do), or a Flax module whose
``apply`` returns the loss.
"""

import inspect
import json
import os
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..accelerator import get_accelerator
from ..comm import comm as dist
from ..utils.logging import logger, log_dist
from ..utils.timer import (SynchronizedWallClockTimer, ThroughputTimer, NoopTimer, FORWARD_GLOBAL_TIMER,
                           BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER, _device_sync)
from .config import DeepSpeedConfig
from .constants import (ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM_OPTIMIZER, CPU_ADAM_OPTIMIZER,
                        ADAGRAD_OPTIMIZER, LAMB_OPTIMIZER, SGD_OPTIMIZER, LION_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                        ONEBIT_LAMB_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER)
from .fp16.loss_scaler import create_loss_scaler
from .lr_schedules import get_lr_schedule, _LRSchedule
from .zero import gather_order
from .zero.config import ZeroStageEnum
from .zero.sharding import ShardingPlanner, TensorParallelRules


class TrainState(NamedTuple):
    """All mutable training state, as one sharded pytree."""
    step: Any  # i32 scalar
    params: Any  # fp32 master params (ZeRO-sharded per stage)
    opt_state: Any  # optimizer moments (ZeRO-sharded at stage >= 1)
    grad_acc: Any  # gradient accumulator — empty {} until the 3-call facade
    # is used (the fused train_batch path scans its own accumulator, so no
    # param-sized HBM buffer is carried there)
    micro_step: Any  # i32 scalar: micro-batches seen since last step()
    loss_scale: Any  # LossScaleState
    skipped_steps: Any  # i32 scalar


def _resolve_loss_fn(model):
    if hasattr(model, "loss") and callable(model.loss):
        return model.loss
    if hasattr(model, "apply"):  # Flax module

        def flax_loss(params, batch, rng):
            out = model.apply({"params": params}, batch, rngs={"dropout": rng} if rng is not None else None)
            if not (hasattr(out, "ndim") and out.ndim == 0):
                raise ValueError("Flax module passed as `model` must return a scalar loss from apply(); "
                                 "wrap it in a loss function or pass loss_fn(params, batch, rng) directly")
            return out

        return flax_loss
    if callable(model):
        return model
    raise ValueError(f"Cannot resolve a loss function from model of type {type(model)}")


class DeepSpeedEngine:

    def __init__(self,
                 model,
                 config=None,
                 config_class=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 dont_change_device=False,
                 tp_rules=None,
                 expert_pattern=None,
                 rng_seed=None):
        self.module = model
        self.loss_fn = _resolve_loss_fn(model)
        # a loss that takes the ZeRO-3 gather order is handed it (stage 3)
        self._loss_takes_order = "gather_order" in inspect.signature(self.loss_fn).parameters
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.global_steps = 0
        self._micro_traces = 0  # times a step program traced its loss (the gauges' cue)
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.loaded_checkpoint_tag = None

        # the batch triple counts the devices this engine runs on: an installed
        # mesh may cover fewer than jax.device_count() (one chip of four)
        self._config = config_class if config_class is not None else DeepSpeedConfig(
            config, mpu, world_size=(dist.get_mesh().size if dist.has_mesh()
                                     else dist.get_world_size()))

        # ---- mesh --------------------------------------------------------
        m = self._config.mesh
        if dist.has_mesh():
            self.mesh = dist.get_mesh()
        else:
            self.mesh = dist.initialize_mesh(pipe=m.pipeline_parallel_size,
                                             expert=m.expert_parallel_size,
                                             seq=m.sequence_parallel_size,
                                             tensor=m.tensor_parallel_size)

        # ---- precision ---------------------------------------------------
        self.compute_dtype = self._config.compute_dtype
        self.loss_scaler = create_loss_scaler(self._config.fp16 if self._config.fp16.enabled else None)
        self.dynamic_loss_scale = self._config.dynamic_loss_scale

        # ---- activation checkpointing (reference runtime/
        # activation_checkpointing/checkpointing.py:708; here a remat policy
        # applied to the model before compilation) ---------------------------
        ac = self._config.activation_checkpointing
        if ac.policy is not None or ac.partition_activations or ac.cpu_checkpointing:
            policy = ac.policy or "nothing_saveable"
            if hasattr(model, "set_remat_policy"):
                if getattr(getattr(model, "cfg", None), "remat_policy", None) != policy:
                    model.set_remat_policy(policy)
                    log_dist(f"activation checkpointing: remat policy '{policy}' applied", [0])
            else:
                logger.warning(
                    "activation_checkpointing configured but the model exposes no "
                    "set_remat_policy(policy) hook — section has NO effect; apply "
                    "jax.checkpoint in the model yourself")
            if ac.partition_activations:
                log_dist("activation_checkpointing.partition_activations: subsumed by the "
                         "sharding propagation of saved residuals (XLA keeps remat residuals "
                         "in their sharded layout; no gather/scatter pass is needed)", [0])

        # ---- sharding plan (ZeRO stages as placement rules) --------------
        if tp_rules is None and hasattr(model, "tp_rules"):
            tp_rules = model.tp_rules()
        if expert_pattern is None and hasattr(model, "expert_pattern"):
            expert_pattern = model.expert_pattern()
        pipe_pattern = model.pipeline_pattern() if hasattr(model, "pipeline_pattern") else None
        if self.mesh.shape[dist.PIPE_AXIS] > 1:
            if not (hasattr(model, "pipeline_loss") and pipe_pattern):
                raise ValueError(
                    "pipeline_parallel_size > 1 requires a model exposing pipeline_loss() and "
                    "pipeline_pattern() (all deepspeed_tpu.models with scan_layers=True do)")
            # MoE aux loss flows through the pipeline's aux channel
            # (spmd_pipeline with_aux; valid-tick masked, psum over pipe)
        self.planner = ShardingPlanner(self.mesh,
                                       self._config.zero_optimization,
                                       tp_rules=tp_rules,
                                       expert_pattern=expert_pattern,
                                       pipe_pattern=pipe_pattern)

        # ---- ZeRO-Offload (optimizer state in host DRAM) -----------------
        off = self._config.zero_optimization.offload_optimizer
        self.offload_optimizer = off.device in ("cpu", "nvme")
        if off.device == "nvme" and not off.nvme_path:
            raise ValueError("offload_optimizer.device='nvme' requires nvme_path")
        if self.offload_optimizer and self.mesh.shape[dist.PIPE_AXIS] > 1:
            raise NotImplementedError("offload_optimizer does not yet compose with "
                                      "pipeline_parallel_size > 1")
        self.host_opt = None

        # ---- ZeRO-Infinity parameter offload (streamed step) -------------
        offp = self._config.zero_optimization.offload_param
        self.offload_param = offp.device in ("cpu", "nvme")
        self.param_stream = None
        if self.offload_param:
            if self._config.zero_optimization.stage != 3:
                raise ValueError("offload_param requires zero stage 3 (reference "
                                 "zero/stage3.py:463 configures param swapping under "
                                 "stage 3 only)")
            if self.mesh.shape[dist.PIPE_AXIS] > 1:
                raise NotImplementedError("offload_param does not compose with "
                                          "pipeline_parallel_size > 1")
            if not hasattr(model, "stream_plan"):
                raise ValueError("offload_param requires a model exposing the parameter "
                                 "streaming protocol (stream_plan/stream_embed/stream_layer/"
                                 "stream_tail_loss — deepspeed_tpu.models transformers do)")
            if self.offload_optimizer:
                log_dist("offload_param subsumes offload_optimizer: the streamed step keeps "
                         "fp32 master + moments host-resident by construction", [0])
                self.offload_optimizer = False

        # ---- params ------------------------------------------------------
        if model_parameters is None and hasattr(model, "init_params"):
            model_parameters = None  # initialized sharded below
        self._seed = self._config.seed if rng_seed is None else rng_seed
        self._base_rng = jax.random.key(self._seed)

        if self.offload_param:
            # params never materialize on device: the runner owns host blocks
            # and the streamed step (no fused pjit state)
            from .zero.param_offload import ParamStreamRunner
            self.lr_schedule_fn, self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)
            self._onebit = None
            self.tx = None
            self.param_stream = ParamStreamRunner(
                model, self._config, self.mesh, self.planner, self.compute_dtype,
                self.lr_schedule_fn, rng_seed=self._seed)
            self.state_shardings = None
            self.state = TrainState(step=jnp.zeros((), jnp.int32), params={}, opt_state={},
                                    grad_acc={}, micro_step=jnp.zeros((), jnp.int32),
                                    loss_scale=self.loss_scaler.init_state(),
                                    skipped_steps=jnp.zeros((), jnp.int32))
        else:
            params = self._init_params(model, model_parameters)

            # ---- optimizer -----------------------------------------------
            self.lr_schedule_fn, self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)
            self._onebit = None  # set when a 1-bit/0-1 optimizer is configured
            self.tx = self._configure_optimizer(optimizer)

            # ---- state + shardings ---------------------------------------
            self.state_shardings = None
            if self.offload_optimizer:
                params = self._init_host_optimizer(params)
            self.state = self._init_state(params)
            del params

        # ---- curriculum learning + progressive layer drop ----------------
        # (legacy `curriculum_learning` section, reference engine.py:1663
        # seqlen truncation; `progressive_layer_drop`, engine.py:1658)
        cl_cfg = dict(self._config.raw_config.get("curriculum_learning", {}))
        self.curriculum_scheduler = None
        if cl_cfg.get("enabled"):
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(cl_cfg)
            self.curriculum_type = cl_cfg.get("curriculum_type", "seqlen")
        pld_cfg = dict(self._config.raw_config.get("progressive_layer_drop", {}))
        self.progressive_layer_drop = None
        if pld_cfg.get("enabled"):
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5), gamma=pld_cfg.get("gamma", 0.001))
        # random-LTD (reference data_efficiency.data_routing.random_ltd,
        # data_routing/scheduler.py:38): keep-length schedule; the model does
        # the per-layer token gather/scatter with a static keep per compile
        routing_cfg = dict(dict(self._config.raw_config.get("data_efficiency", {}))
                           .get("data_routing", {}))
        ltd_cfg = dict(routing_cfg.get("random_ltd", {}))
        self.random_ltd_scheduler = None
        if routing_cfg.get("enabled") and ltd_cfg.get("enabled"):
            from .data_pipeline.data_routing import RandomLTDScheduler
            if not getattr(model, "supports_random_ltd", False):
                raise ValueError("random_ltd enabled but the model does not support it "
                                 "(no set_random_ltd; deepspeed_tpu.models transformers do)")
            if self.mesh.shape[dist.PIPE_AXIS] > 1:
                raise NotImplementedError("random_ltd does not compose with "
                                          "pipeline_parallel_size > 1 (pipeline_loss does not "
                                          "consume the keep length)")
            self.random_ltd_scheduler = RandomLTDScheduler(ltd_cfg)
            if not self.random_ltd_scheduler.random_ltd_layer_id:
                # default: every layer (reference requires the list; all-layers
                # is the only choice that also matches scanned models)
                n_layers = getattr(getattr(model, "cfg", None), "num_layers", 0)
                self.random_ltd_scheduler.random_ltd_layer_id = list(range(n_layers))
            if getattr(getattr(model, "cfg", None), "scan_layers", False):
                n_layers = model.cfg.num_layers
                if len(self.random_ltd_scheduler.random_ltd_layer_id) != n_layers:
                    logger.warning("random_ltd: scan_layers models apply token dropping to "
                                   "EVERY layer; the configured random_ltd_layer_id subset "
                                   "is ignored (use scan_layers=False for per-layer control)")
            self._ltd_current = None
        # data_efficiency.data_sampling: consumed by deepspeed_io (reference
        # builds the curriculum sampler into its dataloader,
        # data_pipeline/data_sampler.py:36); flag it so deepspeed_io wires a
        # DeepSpeedDataSampler when the user hands us the training_data
        self._data_sampling_cfg = dict(dict(self._config.raw_config
                                            .get("data_efficiency", {}))
                                       .get("data_sampling", {}))
        self._data_sampler = None
        self._pending_sampler_state = None  # checkpoint state loaded pre-sampler

        # ---- timers / monitor / telemetry / io ---------------------------
        self.wall_clock_breakdown = self._config.wall_clock_breakdown
        from ..monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self._config)
        from ..telemetry import TelemetrySink, set_sink
        # the sink is the single reporting call site: gauges fan out to the
        # monitor backends; file output (JSONL + trace.json) only when the
        # 'telemetry' config section is enabled (default-off)
        self.telemetry = TelemetrySink(self._config.telemetry, monitor=self.monitor)
        if self.telemetry.enabled:
            set_sink(self.telemetry)
        self._trace_spans = self.wall_clock_breakdown or self.telemetry.enabled
        self.timers = SynchronizedWallClockTimer() if self._trace_spans else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size(),
                                          steps_per_output=self._config.steps_per_print)
        self._step_flops = None  # XLA cost-analysis FLOPs of one optimizer step
        self._last_step_dur = None  # seconds, measured around the last step
        self._grad_sync_bytes_cached = None
        # SLO engine (telemetry.slo section): evaluated at the reporting
        # interval so MFU/overlap-efficiency floors can burn-rate alert on
        # the training side too (the serving gateway builds its own)
        self._slo = None
        if self.telemetry.enabled and self.telemetry.slo_config.get("objectives"):
            from ..telemetry import SLOEngine
            self._slo = SLOEngine(self.telemetry, self.telemetry.slo_config)
        # on-demand XLA profiling (telemetry/profiler.py): captures
        # requested via request_profile() start at the next REPORT boundary
        # (never mid-dispatch); telemetry.profile_report_s > 0 auto-arms one
        # capture of that duration at the first report interval
        self.profiler = None
        if self.telemetry.enabled:
            from ..telemetry.profiler import XlaProfiler
            self.profiler = XlaProfiler(self.telemetry.output_path)
            auto_s = float(getattr(self._config.telemetry,
                                   "profile_report_s", 0.0) or 0.0)
            if auto_s > 0.0:
                self.profiler.request(auto_s)
        self._fwd_since_step = 0  # facade micro-steps since the last step()
        self._facade_t0 = None

        self.training_dataloader = self.deepspeed_io(training_data) if training_data is not None else None

        # ---- compiled steps ----------------------------------------------
        self._compiled = {}
        self._pending_batches = []
        self._last_metrics = None
        self._eigenvalue = None  # built lazily from the 'eigenvalue' section

        log_dist(
            f"DeepSpeedEngine ready: world={dist.get_world_size()} mesh={dict(self.mesh.shape)} "
            f"zero_stage={self.zero_optimization_stage()} dtype={jnp.dtype(self.compute_dtype).name} "
            f"micro_bs={self.train_micro_batch_size_per_gpu()} gas={self.gradient_accumulation_steps()}", [0])

    # ------------------------------------------------------------------ config accessors
    # (parity with reference engine.py:456-819 get_* properties)
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self._config.zero_optimization.stage

    def zero_optimization(self):
        return self._config.zero_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def steps_per_print(self):
        return self._config.steps_per_print

    def bfloat16_enabled(self):
        return self._config.bf16.enabled

    def fp16_enabled(self):
        return self._config.fp16.enabled

    def dp_world_size(self):
        return dist.get_world_size(dist.DP_AXES)

    @property
    def config(self):
        return self._config

    @property
    def params(self):
        return self.state.params

    def get_lr(self):
        return [float(self.lr_schedule_fn(jnp.asarray(self.global_steps, jnp.float32)))]

    def loss_scale(self):
        return float(self.state.loss_scale.cur_scale)

    # ------------------------------------------------------------------ init helpers
    def _init_params(self, model, model_parameters):
        """Materialize fp32 master params directly into their ZeRO sharding.

        The TPU equivalent of ``zero.Init`` (``partition_parameters.py:601``):
        parameters are *born sharded* — jit-evaluating the initializer with
        sharded out_shardings means no device ever holds the full model
        (critical for 70B-class models).
        """
        if model_parameters is not None:
            specs = self.planner.master_specs(model_parameters)
            shardings = self.planner.shardings(specs)
            cast = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), p),
                           out_shardings=shardings)
            return cast(model_parameters)
        if hasattr(model, "init_params"):
            abstract = jax.eval_shape(model.init_params, self._base_rng)
            specs = self.planner.master_specs(abstract)
            shardings = self.planner.shardings(specs)
            init = jax.jit(lambda rng: jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                                              model.init_params(rng)),
                           out_shardings=shardings)
            with self.mesh:
                return init(self._base_rng)
        raise ValueError("Provide model_parameters or a model with init_params(rng)")

    def _init_host_optimizer(self, params_f32):
        """ZeRO-Offload: move fp32 master + moments to host DRAM (or NVMe —
        ZeRO-Infinity), PARTITIONED per host over the DP axes, and return the
        compute-dtype device params that replace them in TrainState. HBM
        afterwards holds only ~2 bytes/param instead of 16, host DRAM holds
        12 bytes/param ÷ dp_world (and with NVMe, only a rotating block
        window)."""
        from .zero.offload import HostOffloadOptimizer
        off = self._config.zero_optimization.offload_optimizer
        if off.device == "nvme":
            from .swap_tensor import NVMeOffloadOptimizer, get_aio_config
            self.host_opt = NVMeOffloadOptimizer(
                self._config.optimizer, self.lr_schedule_fn, nvme_path=off.nvme_path,
                aio_config=get_aio_config(self._config.raw_config),
                pipeline_read=bool(off.pipeline_read),
                pipeline_write=bool(off.pipeline_write))
            self.host_opt.compute_dtype = self.compute_dtype
        else:
            self.host_opt = HostOffloadOptimizer(self._config.optimizer, self.lr_schedule_fn)
        # lay the master out in the offload sharding (scattered over DP even
        # at stage 0) so each host pulls exactly its partition
        off_shardings = self.planner.shardings(self.planner.offload_specs(params_f32))
        reshard = jax.jit(lambda p: p, donate_argnums=(0, ), out_shardings=off_shardings)
        with self.mesh:
            params_off = reshard(params_f32)
        self.host_opt.init_from_device(params_off)
        shardings = self.planner.shardings(self.planner.master_specs(params_off))
        cast = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.compute_dtype), p),
                       donate_argnums=(0, ), out_shardings=shardings)
        with self.mesh:
            compute_params = cast(params_off)
        tier = "NVMe" if off.device == "nvme" else "host DRAM"
        log_dist(f"ZeRO-Offload: {self.host_opt.num_params():,} params' optimizer state on {tier} "
                 f"(this host's partition, native cpu_adam), "
                 f"{jnp.dtype(self.compute_dtype).name} compute copy in HBM", [0])
        return compute_params

    def _init_state(self, params):
        master_specs = self.planner.master_specs(params)
        master_shardings = self.planner.shardings(master_specs)
        scalar = NamedSharding(self.mesh, P())

        if self.offload_optimizer:
            opt_state, opt_shardings = {}, {}
        elif self._onebit:
            # per-worker state (error feedback differs across DP ranks): every
            # leaf carries a leading dp dim, sharded over the data axis
            dp = self.mesh.shape[dist.DATA_AXIS]
            base = jax.eval_shape(self.tx.init, params)
            opt_state = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((dp, ) + x.shape, x.dtype), base)
            opt_shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P(dist.DATA_AXIS)), opt_state)
        else:
            opt_state = jax.eval_shape(self.tx.init, params)
            opt_shardings = self.planner.opt_state_shardings(opt_state, params)

        self.state_shardings = TrainState(
            step=scalar,
            params=master_shardings,
            opt_state=opt_shardings,
            grad_acc={},
            micro_step=scalar,
            loss_scale=jax.tree_util.tree_map(lambda _: scalar, self.loss_scaler.init_state()),
            skipped_steps=scalar,
        )

        def init_opt(p):
            if self.offload_optimizer:
                return {}
            if self._onebit:
                dp = self.mesh.shape[dist.DATA_AXIS]
                return jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (dp, ) + x.shape), self.tx.init(p))
            return self.tx.init(p)

        init_fn = jax.jit(
            lambda p: TrainState(
                step=jnp.zeros((), jnp.int32),
                params=p,
                opt_state=init_opt(p),
                grad_acc={},
                micro_step=jnp.zeros((), jnp.int32),
                loss_scale=self.loss_scaler.init_state(),
                skipped_steps=jnp.zeros((), jnp.int32),
            ),
            out_shardings=self.state_shardings,
        )
        with self.mesh:
            return init_fn(params)

    def _ensure_grad_acc(self):
        """Materialize the facade gradient-accumulation buffer on first use.

        The fused ``train_batch`` path never needs it, so a param-sized HBM
        buffer (~280 GB across the mesh at 70B fp32) is only paid when the
        forward/backward/step facade is actually exercised."""
        if jax.tree_util.tree_leaves(self.state.grad_acc):
            return
        grad_shardings = self.planner.shardings(self.planner.grad_specs(self.state.params))
        self.state_shardings = self.state_shardings._replace(grad_acc=grad_shardings)
        alloc = jax.jit(lambda s: s._replace(grad_acc=jax.tree_util.tree_map(jnp.zeros_like, s.params)),
                        donate_argnums=(0, ), out_shardings=self.state_shardings)
        with self.mesh:
            self.state = alloc(self.state)
        self._compiled.clear()  # compiled fns embed the old state shardings

    def _drop_grad_acc(self):
        """Return state to the canonical (no accumulator) structure."""
        if not jax.tree_util.tree_leaves(self.state.grad_acc):
            return
        self.state_shardings = self.state_shardings._replace(grad_acc={})
        self.state = self.state._replace(grad_acc={})
        self._compiled.clear()

    def _configure_lr_scheduler(self, client_lr_scheduler):
        """Returns (pure step->lr fn folded into the compiled step, stateful
        facade object or None). Reference engine.py:836."""
        sched_cfg = self._config.scheduler
        if client_lr_scheduler is not None:
            if isinstance(client_lr_scheduler, _LRSchedule):
                return client_lr_scheduler.__call__, client_lr_scheduler
            if callable(client_lr_scheduler):
                return client_lr_scheduler, None
            raise ValueError("lr_scheduler must be a deepspeed_tpu schedule or a step->lr callable")
        if sched_cfg.type is not None:
            sched = get_lr_schedule(sched_cfg.type, sched_cfg.params)
            return sched.__call__, sched
        base_lr = self._config.optimizer.params.get("lr", 1e-3)
        return (lambda step: jnp.asarray(base_lr, jnp.float32)), None

    def _configure_optimizer(self, client_optimizer):
        """Build the optax gradient transformation (reference
        ``_configure_basic_optimizer`` engine.py:1197). The LR schedule is
        passed as an optax schedule so it lives inside the compiled step.
        LoRA models with ``only_optimize_lora`` get the transformation
        masked to adapter leaves — optimizer state is allocated for adapters
        only (the DeepSpeed-Chat actor memory profile)."""
        from .lora import LoRAModel
        tx = self._configure_optimizer_inner(client_optimizer)
        if isinstance(self.module, LoRAModel) and self.module.only_optimize_lora:
            tx = optax.masked(tx, self.module.optimizer_mask)
            log_dist("LoRA: optimizer masked to adapter leaves "
                     f"(r={self.module.r}, alpha={self.module.alpha})", [0])
        return tx

    def _configure_optimizer_inner(self, client_optimizer):
        if client_optimizer is not None:
            if isinstance(client_optimizer, optax.GradientTransformation):
                return client_optimizer
            raise ValueError("client optimizer must be an optax.GradientTransformation")

        cfg = self._config.optimizer
        name = (cfg.type or ADAMW_OPTIMIZER).lower()
        p = dict(cfg.params)
        lr = self.lr_schedule_fn
        betas = p.get("betas", (0.9, 0.999))
        eps = p.get("eps", 1e-8)
        wd = p.get("weight_decay", 0.0)

        if name in (ADAM_OPTIMIZER, FUSED_ADAM_OPTIMIZER, CPU_ADAM_OPTIMIZER):
            # reference Adam defaults to adam_w_mode=True (ops/adam/fused_adam.py)
            if p.get("adam_w_mode", True):
                return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
            return optax.chain(optax.scale_by_adam(b1=betas[0], b2=betas[1], eps=eps),
                               optax.add_decayed_weights(wd) if wd else optax.identity(),
                               optax.scale_by_learning_rate(lr))
        if name == ADAMW_OPTIMIZER:
            return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
        if name == ADAGRAD_OPTIMIZER:
            return optax.chain(optax.scale_by_rss(initial_accumulator_value=p.get("initial_accumulator_value", 0.0),
                                                  eps=eps),
                               optax.scale_by_learning_rate(lr))
        if name == LAMB_OPTIMIZER:
            return optax.chain(
                optax.scale_by_adam(b1=betas[0], b2=betas[1], eps=eps),
                optax.add_decayed_weights(wd) if wd else optax.identity(),
                optax.scale_by_trust_ratio(min_norm=p.get("min_coeff", 0.01)),
                optax.scale_by_learning_rate(lr),
            )
        if name == SGD_OPTIMIZER:
            return optax.sgd(lr, momentum=p.get("momentum", 0.0), nesterov=p.get("nesterov", False))
        if name == LION_OPTIMIZER:
            return optax.lion(lr, b1=betas[0], b2=betas[1], weight_decay=wd)
        if name in (ONEBIT_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER):
            # Error-compensated compressed-communication optimizers (reference
            # fp16/onebit/adam.py:13 via _configure_basic_optimizer
            # engine.py:1197). The train step switches to a shard_map over the
            # data axis where gradients stay per-shard and the optimizer's
            # 1-bit momentum exchange is the only cross-DP wire traffic
            # (_build_onebit_train_fn). Momentum/variance/error-feedback are
            # per-worker full-size, so ZeRO sharding of optimizer state does
            # not apply.
            from ..ops.adam import onebit_adam, onebit_lamb, zero_one_adam
            if self._config.zero_optimization.stage > 0:
                raise ValueError(f"{cfg.type} is incompatible with ZeRO stage "
                                 f"{self._config.zero_optimization.stage}: its momentum/error-"
                                 f"feedback state is per-worker full-size (reference 1-bit Adam "
                                 f"likewise requires stage 0); set zero stage 0")
            if self.offload_optimizer:
                raise ValueError(f"{cfg.type} does not compose with offload_optimizer")
            for ax in (dist.PIPE_AXIS, dist.EXPERT_AXIS, dist.SEQ_AXIS, dist.TENSOR_AXIS):
                if self.mesh.shape[ax] > 1:
                    raise ValueError(f"{cfg.type} supports pure data-parallel meshes only "
                                     f"(mesh axis {ax!r}={self.mesh.shape[ax]})")
            if self._config.gradient_clipping:
                logger.warning(f"{cfg.type}: gradient clipping uses the proxy norm "
                               f"sqrt(mean_dp ||g_shard||^2) — an upper bound on the true "
                               f"averaged-gradient norm (the dense norm would need the dense "
                               f"allreduce the optimizer exists to avoid)")
            self._onebit = name
            common = dict(b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
            if name == ONEBIT_ADAM_OPTIMIZER:
                return onebit_adam(lr, dist.DATA_AXIS,
                                   freeze_step=p.get("freeze_step", 100), **common)
            if name == ONEBIT_LAMB_OPTIMIZER:
                return onebit_lamb(lr, dist.DATA_AXIS,
                                   freeze_step=p.get("freeze_step", 100),
                                   min_trust=p.get("min_coeff", 0.01),
                                   max_trust=p.get("max_coeff", 10.0), **common)
            return zero_one_adam(lr, dist.DATA_AXIS,
                                 var_freeze_step=p.get("var_freeze_step", 100),
                                 var_update_scaler=p.get("var_update_scaler", 16), **common)
        raise ValueError(f"Unknown optimizer type {cfg.type}")

    # ------------------------------------------------------------------ step math
    def _micro_loss_and_grads(self, params, batch, rng, scale):
        """One microbatch: cast master->compute, forward, backward, unscale later."""

        def scaled_loss(p):
            self._micro_traces += 1  # runs when the step's program is traced
            p_c = jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.compute_dtype), p)
            # compute-param placement: stage-3 params stay scattered (XLA
            # all-gathers just-in-time per layer); params under
            # stage3_param_persistence_threshold are pinned replicated here
            p_c = jax.lax.with_sharding_constraint(p_c, self.planner.param_shardings(p_c))
            # ... and under stage 3 the model is told which leaves those are,
            # so that its layer loop can state which product each large
            # gather is due behind (runtime/zero/gather_order.py)
            order = self.planner.gathered_placements(p_c) if self._loss_takes_order else None
            out = (self.loss_fn(p_c, batch, rng, gather_order=order) if order
                   else self.loss_fn(p_c, batch, rng))
            loss, aux = (out if isinstance(out, tuple) else (out, None))
            return loss.astype(jnp.float32) * scale, (loss, aux)

        grads, (loss, aux) = jax.grad(scaled_loss, has_aux=True)(params)
        return loss, grads

    def _grad_denom(self, scale):
        """Loss-scale x gas (x predivide) unscaling denominator."""
        denom = scale * self._config.gradient_accumulation_steps
        if self._config.prescale_gradients:
            denom = denom * self._config.gradient_predivide_factor
        return denom

    def _clip_coef(self, gnorm):
        """Gradient-clipping coefficient, or None when clipping is off."""
        clip = self._config.gradient_clipping
        if clip and clip > 0:
            return jnp.minimum(1.0, clip / (gnorm + 1e-6))
        return None

    def _apply_grads(self, state, grads, loss_mean):
        """Unscale, clip, update, handle overflow — shared by both paths."""
        scale = state.loss_scale.cur_scale
        # named regions of the compiled step (no flax module names them): the
        # device trace books an operation under the scope it was traced in
        with jax.named_scope("grad_norm"):
            denom = self._grad_denom(scale)
            grads = jax.tree_util.tree_map(lambda g: (g / denom).astype(jnp.float32), grads)
            # stage>=2: pin gradients to their scattered sharding
            grads = jax.lax.with_sharding_constraint(
                grads, self.planner.shardings(self.planner.grad_specs(state.params)))

            gnorm = optax.global_norm(grads)
            overflow = ~jnp.isfinite(gnorm)
            coef = self._clip_coef(gnorm)
            if coef is not None:
                grads = jax.tree_util.tree_map(lambda g: g * coef, grads)

        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)

            # overflow: skip the update entirely (reference loss-scaler semantics)
            def sel(new, old):
                return jax.tree_util.tree_map(lambda n, o: jnp.where(overflow, o, n), new, old)

            new_params = sel(new_params, state.params)
            new_opt = sel(new_opt, state.opt_state)
            new_scale = self.loss_scaler.update(state.loss_scale, overflow)

            new_state = state._replace(
                step=state.step + jnp.where(overflow, 0, 1),
                params=new_params,
                opt_state=new_opt,
                grad_acc=jax.tree_util.tree_map(jnp.zeros_like, state.grad_acc),
                micro_step=jnp.zeros((), jnp.int32),
                loss_scale=new_scale,
                skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
            )
        lr = self.lr_schedule_fn(state.step.astype(jnp.float32))
        metrics = {
            "loss": loss_mean,
            "grad_norm": gnorm,
            "lr": lr,
            "overflow": overflow,
            "loss_scale": scale,
        }
        return new_state, metrics

    def _build_pp_train_fn(self):
        """Pipeline-parallel fused step: the whole microbatch stream runs
        through the SPMD pipeline (reference ``PipelineEngine.train_batch``,
        pipe/engine.py:285) inside one pjit; jax.grad through the
        ppermute/scan pipeline is the backward schedule."""
        gas = self._config.gradient_accumulation_steps

        pipe_cfg = dict(self._config.pipeline or {})
        schedule = str(pipe_cfg.pop("schedule", "auto"))
        if pipe_cfg:
            # the reference PipelineModule section has more keys; only
            # 'schedule' is consumed here — silence would be a porting trap
            logger.warning(f"pipeline section keys {sorted(pipe_cfg)} are not consumed "
                           f"(only 'schedule' is); they have NO effect in this build")
        if schedule not in ("auto", "fill_drain", "1f1b"):
            raise ValueError(f"pipeline.schedule must be 'auto', 'fill_drain' or '1f1b', "
                             f"got {schedule!r}")
        if schedule == "auto":
            # 1F1B is the default where it composes (O(stages) activation
            # liveness, reference TrainSchedule); fall back where it can't:
            # fp16 loss scaling, tensor/seq under the auto partitioner
            # inside the pipe-manual region, MoE aux, unscanned layers.
            mc = getattr(self.module, "cfg", None)
            eligible = (hasattr(self.module, "pipeline_value_and_grad")
                        and not self._config.fp16.enabled
                        and self.mesh.shape[dist.TENSOR_AXIS] == 1
                        and self.mesh.shape[dist.SEQ_AXIS] == 1
                        and getattr(mc, "num_experts", 0) == 0
                        and getattr(mc, "scan_layers", False))
            schedule = "1f1b" if eligible else "fill_drain"
            auto_picked = True
            log_dist(f"pipeline.schedule=auto -> {schedule}", [0])
        else:
            auto_picked = False
        if schedule == "1f1b" and self._config.fp16.enabled:
            # the interleaved backward seeds per-microbatch cotangents BEFORE
            # the engine's loss scale is applied; fp16's dynamic scaling
            # cannot protect it (bf16/fp32 need no scaling)
            raise NotImplementedError("pipeline.schedule='1f1b' does not support fp16 "
                                      "loss scaling; use bf16 (TPU-native) or fill_drain")
        if schedule == "1f1b" and not hasattr(self.module, "pipeline_value_and_grad"):
            raise ValueError("pipeline.schedule='1f1b' requires a model exposing "
                             "pipeline_value_and_grad (deepspeed_tpu.models transformers do)")
        if schedule == "1f1b" and (self.mesh.shape[dist.TENSOR_AXIS] > 1
                                   or self.mesh.shape[dist.SEQ_AXIS] > 1):
            # the manual fwd+bwd interleave currently trips XLA's SPMD
            # partitioner when tensor/seq axes stay under the auto
            # partitioner inside the pipe-manual region
            raise NotImplementedError("pipeline.schedule='1f1b' composes with pipe x data "
                                      "meshes; use the default fill-drain schedule with "
                                      "tensor/sequence parallelism")

        def train_step(state, batch):
            rng = jax.random.fold_in(self._base_rng, state.step)

            # auto-picked 1F1B degrades to fill-drain for masked batches
            # (the interleaved schedule doesn't thread attention_mask);
            # batch STRUCTURE is static under jit, so this is a trace-time
            # branch, not data-dependent control flow
            use_1f1b = schedule == "1f1b" and not (
                auto_picked and batch.get("attention_mask") is not None)
            if use_1f1b:
                # interleaved one-pass schedule: fwd+bwd per tick, per-stage
                # activation liveness O(stages) (reference TrainSchedule 1F1B)
                p_c = jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.compute_dtype),
                                             state.params)
                p_c = jax.lax.with_sharding_constraint(p_c, self.planner.param_shardings(p_c))
                loss, grads = self.module.pipeline_value_and_grad(p_c, batch, rng,
                                                                  mesh=self.mesh)
                coef = state.loss_scale.cur_scale * gas
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) * coef, grads)
                return self._apply_grads(state, grads, loss)

            def scaled_loss(p):
                p_c = jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.compute_dtype), p)
                p_c = jax.lax.with_sharding_constraint(p_c, self.planner.param_shardings(p_c))
                loss = self.module.pipeline_loss(p_c, batch, rng, mesh=self.mesh)
                # x gas: _apply_grads divides by scale*gas (sum convention)
                return loss.astype(jnp.float32) * state.loss_scale.cur_scale * gas, loss

            grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params)
            return self._apply_grads(state, grads, loss)

        return jax.jit(train_step,
                       donate_argnums=(0, ),
                       in_shardings=(self.state_shardings, self._batch_shardings_cache()),
                       out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())))

    def _build_onebit_train_fn(self):
        """1-bit / 0-1 Adam fused step (reference ``fp16/onebit/adam.py:13``
        wired through ``engine.py:1197``): the whole step runs in a
        ``shard_map`` over the data axis. Gradients are computed and kept
        per-DP-shard — the error-compensated compressed-momentum exchange
        inside the optimizer (``runtime/comm/compressed.onebit_all_reduce``)
        is the ONLY cross-DP communication, so past ``freeze_step`` the wire
        carries ~1/32 of a dense allreduce's bytes (sign plane + scale)."""
        gas = self._config.gradient_accumulation_steps
        axis = dist.DATA_AXIS
        dp = self.mesh.shape[axis]
        compute_dtype = self.compute_dtype
        loss_fn = self.loss_fn
        tx = self.tx
        base_rng = self._base_rng

        def shard_fn(params, opt_state, scale, step, batch_shard):
            opt_local = jax.tree_util.tree_map(lambda x: x[0], opt_state)
            rng = jax.random.fold_in(jax.random.fold_in(base_rng, step),
                                     jax.lax.axis_index(axis))

            def scaled_loss(p, mb, r):
                p_c = jax.tree_util.tree_map(lambda x: jnp.asarray(x, compute_dtype), p)
                out = loss_fn(p_c, mb, r)
                loss = out[0] if isinstance(out, tuple) else out
                return loss.astype(jnp.float32) * scale, loss

            def micro(carry, mb):
                acc, loss_sum, i = carry
                grads, loss = jax.grad(scaled_loss, has_aux=True)(params, mb,
                                                                  jax.random.fold_in(rng, i))
                acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return (acc, loss_sum + loss.astype(jnp.float32), i + 1), None

            zero_acc = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
            (grads, loss_sum, _), _ = jax.lax.scan(
                micro, (zero_acc, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
                batch_shard)

            denom = self._grad_denom(scale)
            grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
            sumsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))
            mean_sq = jax.lax.psum(sumsq, axis) / dp
            overflow = ~jnp.isfinite(mean_sq)
            # proxy norm (see _configure_optimizer warning): upper bound on
            # the averaged-gradient norm without a dense allreduce
            gnorm = jnp.sqrt(mean_sq)
            coef = self._clip_coef(gnorm)
            if coef is not None:
                grads = jax.tree_util.tree_map(lambda g: g * coef, grads)
            # overflow: feed zeros through the exchange (keeps it finite),
            # then discard every result below
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(overflow, jnp.zeros_like(g), g), grads)
            updates, new_opt = tx.update(grads, opt_local, params)
            new_params = optax.apply_updates(params, updates)

            def sel(new, old):
                return jax.tree_util.tree_map(lambda n, o: jnp.where(overflow, o, n), new, old)

            new_params = sel(new_params, params)
            new_opt = sel(new_opt, opt_local)
            loss_mean = jax.lax.pmean(loss_sum, axis) / gas
            return (new_params, jax.tree_util.tree_map(lambda x: x[None], new_opt),
                    loss_mean, gnorm, overflow)

        def train_step(state, batch):
            # dim 0 is the gas scan dim; dim 1 (when present) is the batch dim
            # sharded over data; rank-1 leaves (e.g. __pld_theta__) replicate
            batch_specs = jax.tree_util.tree_map(
                lambda x: P(*(([None, axis] + [None] * max(x.ndim - 2, 0))[:x.ndim])), batch)
            opt_specs = jax.tree_util.tree_map(lambda _: P(axis), state.opt_state)
            new_params, new_opt, loss_mean, gnorm, overflow = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(), opt_specs, P(), P(), batch_specs),
                out_specs=(P(), opt_specs, P(), P(), P()), check_vma=False)(
                    state.params, state.opt_state, state.loss_scale.cur_scale,
                    state.step, batch)
            new_scale = self.loss_scaler.update(state.loss_scale, overflow)
            new_state = state._replace(
                step=state.step + jnp.where(overflow, 0, 1),
                params=new_params,
                opt_state=new_opt,
                micro_step=jnp.zeros((), jnp.int32),
                loss_scale=new_scale,
                skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
            )
            metrics = {
                "loss": loss_mean,
                "grad_norm": gnorm,
                "lr": self.lr_schedule_fn(state.step.astype(jnp.float32)),
                "overflow": overflow,
                "loss_scale": state.loss_scale.cur_scale,
            }
            return new_state, metrics

        return jax.jit(train_step,
                       donate_argnums=(0, ),
                       out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())))

    def _build_train_batch_fn(self):
        """Fused step: scan over gas microbatches, then update. ONE pjit."""
        if self.mesh.shape[dist.PIPE_AXIS] > 1:
            return self._build_pp_train_fn()

        def train_step(state, batch):
            rng = jax.random.fold_in(self._base_rng, state.step)

            def micro(carry, mb):
                acc, loss_sum, i = carry
                loss, grads = self._micro_loss_and_grads(state.params, mb, jax.random.fold_in(rng, i),
                                                         state.loss_scale.cur_scale)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return (acc, loss_sum + loss.astype(jnp.float32), i + 1), None

            zero_acc = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            (grads, loss_sum, _), _ = jax.lax.scan(micro, (zero_acc, jnp.zeros((), jnp.float32),
                                                           jnp.zeros((), jnp.int32)), batch)
            loss_mean = loss_sum / self._config.gradient_accumulation_steps
            return self._apply_grads(state, grads, loss_mean)

        return jax.jit(train_step,
                       donate_argnums=(0, ),
                       in_shardings=(self.state_shardings, self._batch_shardings_cache()),
                       out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())))

    def _batch_shardings_cache(self):
        return None  # resolved per-call from batch structure

    # ZeRO-Offload path ---------------------------------------------------
    def _build_offload_grad_fn(self):
        """Device half of the offloaded step: fwd+bwd over gas microbatches,
        emitting compute-dtype summed grads + the raw grad-norm. The
        unscale/clip/update half runs on the host (reference
        stage_1_and_2.py:1031 CPU accumulation + cpu_adam step)."""

        gas = self._config.gradient_accumulation_steps

        def fp32_norm(tree):
            return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                for g in jax.tree_util.tree_leaves(tree)))

        def grad_step(state, batch):
            rng = jax.random.fold_in(self._base_rng, state.step)

            if gas == 1:
                # no accumulator at all: grads stay in compute dtype, which is
                # what makes 1.5B-class models fit a single 16 GB chip
                # (an fp32 accumulator alone would add 6 GB at 1.5B params)
                mb = jax.tree_util.tree_map(lambda x: x[0], batch)
                loss, grads = self._micro_loss_and_grads(state.params, mb,
                                                         jax.random.fold_in(rng, 0),
                                                         state.loss_scale.cur_scale)
                return grads, {"loss_sum": loss.astype(jnp.float32), "gnorm_raw": fp32_norm(grads)}

            def micro(carry, mb):
                acc, loss_sum, i = carry
                loss, grads = self._micro_loss_and_grads(state.params, mb, jax.random.fold_in(rng, i),
                                                         state.loss_scale.cur_scale)
                # accumulate in fp32 regardless of compute dtype
                acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return (acc, loss_sum + loss.astype(jnp.float32), i + 1), None

            zero_acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (acc, loss_sum, _), _ = jax.lax.scan(micro, (zero_acc, jnp.zeros((), jnp.float32),
                                                         jnp.zeros((), jnp.int32)), batch)
            gnorm_raw = optax.global_norm(acc)
            # ship grads at compute precision (half the host-link bytes)
            grads_out = jax.tree_util.tree_map(lambda g: g.astype(self.compute_dtype), acc)
            return grads_out, {"loss_sum": loss_sum, "gnorm_raw": gnorm_raw}

        scalar = NamedSharding(self.mesh, P())
        # grads leave the device reduce-scattered into the offload layout so
        # each host fetches only its partition's shards (reference
        # stage_1_and_2.py:1031; fixes the fetch-the-world gather)
        grad_shardings = self.planner.shardings(self.planner.offload_specs(self.state.params))
        return jax.jit(grad_step,
                       in_shardings=(self.state_shardings, self._batch_shardings_cache()),
                       out_shardings=(grad_shardings,
                                      {"loss_sum": scalar, "gnorm_raw": scalar}))

    def _offload_train_batch(self, stacked):
        """Host half of the offloaded step: fetch grads, fused C AdamW over
        host-resident master/moments, push the bf16 compute params back."""
        cfg = self._config
        gas = cfg.gradient_accumulation_steps
        fn = self._get("offload_grads", self._build_offload_grad_fn)
        if self.telemetry.enabled and self._step_flops is None:
            self._step_flops = self._cost_analysis_flops(fn, self.state, stacked)
        with self.mesh:
            grads, dev_metrics = fn(self.state, stacked)

        gnorm_raw = float(dev_metrics["gnorm_raw"])
        loss_mean = float(dev_metrics["loss_sum"]) / gas
        scale = float(self.state.loss_scale.cur_scale)
        denom = scale * gas
        if cfg.prescale_gradients:
            denom *= cfg.gradient_predivide_factor
        overflow = not np.isfinite(gnorm_raw)
        gnorm = gnorm_raw / denom
        # LR keyed on applied steps (state.step), matching the fused path's
        # schedule position even across overflow-skipped steps
        lr = float(self.lr_schedule_fn(jnp.asarray(int(self.state.step), jnp.float32)))

        if not overflow:
            coef = 1.0 / denom
            clip = cfg.gradient_clipping
            if clip and clip > 0:
                coef *= min(1.0, clip / (gnorm + 1e-6))
            with self.telemetry.span("offload"):
                host_grads = self.host_opt.fetch_grads(grads)
                self.host_opt.step(host_grads, coef, lr)
                new_params = self.host_opt.compute_params(self.compute_dtype,
                                                          self.state_shardings.params)
        else:
            new_params = self.state.params

        new_scale = self.loss_scaler.update(self.state.loss_scale, jnp.asarray(overflow))
        self.state = self.state._replace(
            step=self.state.step + (0 if overflow else 1),
            params=new_params,
            loss_scale=new_scale,
            skipped_steps=self.state.skipped_steps + int(overflow),
        )
        metrics = {"loss": loss_mean, "grad_norm": gnorm, "lr": lr, "overflow": overflow,
                   "loss_scale": scale}
        # loss was computed against pre-update params; report it as the step loss
        return metrics

    # facade pieces -----------------------------------------------------
    def _build_micro_fn(self):

        def micro_step(state, batch):
            rng = jax.random.fold_in(jax.random.fold_in(self._base_rng, state.step), state.micro_step)
            loss, grads = self._micro_loss_and_grads(state.params, batch, rng, state.loss_scale.cur_scale)
            grads = jax.lax.with_sharding_constraint(
                grads, self.planner.shardings(self.planner.grad_specs(state.params)))
            new_state = state._replace(
                grad_acc=jax.tree_util.tree_map(jnp.add, state.grad_acc, grads),
                micro_step=state.micro_step + 1,
            )
            return new_state, loss

        return jax.jit(micro_step, donate_argnums=(0, ),
                       out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())))

    def _build_apply_fn(self):

        def apply_step(state, loss_mean):
            return self._apply_grads(state, state.grad_acc, loss_mean)

        return jax.jit(apply_step, donate_argnums=(0, ),
                       out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())))

    def _build_eval_fn(self):

        def eval_step(state, batch):
            p_c = jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.compute_dtype), state.params)
            if self.mesh.shape[dist.PIPE_AXIS] > 1:
                batch_mb = jax.tree_util.tree_map(lambda x: x[None], batch)
                return self.module.pipeline_loss(p_c, batch_mb, None, mesh=self.mesh)
            out = self.loss_fn(p_c, batch, None)
            loss, aux = (out if isinstance(out, tuple) else (out, None))
            return loss

        return jax.jit(eval_step, out_shardings=NamedSharding(self.mesh, P()))

    def _get(self, name, builder):
        if name not in self._compiled:
            self._compiled[name] = builder()
        return self._compiled[name]

    # ------------------------------------------------------------------ data placement
    def _shard_batch(self, batch, leading_scan_dim=False):
        """Place host arrays onto the mesh: batch dim over the DP axes, the
        sequence dim over ``seq`` when sequence parallelism is on."""
        dp = [a for a in (dist.EXPERT_AXIS, dist.DATA_AXIS) if self.mesh.shape[a] > 1]
        seq_on = self.mesh.shape[dist.SEQ_AXIS] > 1
        batch_dim = 1 if leading_scan_dim else 0
        track = self.telemetry.enabled
        if track:
            self.telemetry.counter(
                "comm/host_to_device/bytes",
                int(sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(batch))))
            t_place = time.perf_counter()

        def place(x):
            x = np.asarray(x)
            entries = [None] * x.ndim
            if x.ndim > batch_dim and dp:
                dp_size = int(np.prod([self.mesh.shape[a] for a in dp]))
                # each process holds 1/process_count of the global batch dim
                global_dim = x.shape[batch_dim] * jax.process_count()
                if global_dim % dp_size != 0:
                    raise ValueError(
                        f"global batch dim {global_dim} (local {x.shape[batch_dim]} x "
                        f"{jax.process_count()} processes) not divisible by the data-parallel "
                        f"degree {dp_size} (mesh axes {dp}); pad or resize the batch — "
                        f"silent replication would drop data parallelism")
                entries[batch_dim] = tuple(dp) if len(dp) > 1 else dp[0]
            if seq_on and x.ndim > batch_dim + 1 and x.shape[batch_dim + 1] % self.mesh.shape[dist.SEQ_AXIS] == 0:
                entries[batch_dim + 1] = dist.SEQ_AXIS
            sharding = NamedSharding(self.mesh, P(*entries))
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        placed = jax.tree_util.tree_map(place, batch)
        if track:
            # dispatch/realized split for the batch placement: device_put is
            # asynchronous, so the realized span (fence on the observer pool,
            # busy-interval union — comm/overlap.py) separates DMA completion
            # from the dispatch cost the hot loop actually paid
            dist.get_overlap_tracker().track_async("host_to_device", placed,
                                                   t0=t_place)
        return placed

    def _next_microbatches(self, data_iter, n):
        batches = []
        for _ in range(n):
            batch = next(data_iter)
            if self.collate_fn is not None:
                batch = self.collate_fn(batch)
            batches.append(batch)
        return batches

    # ------------------------------------------------------------------ public API
    def train_batch(self, data_iter=None, batch=None):
        """Run one full training step (gas microbatches + optimizer update)
        as a single compiled program. Returns the mean loss.

        Pass either ``data_iter`` (pulls ``gradient_accumulation_steps``
        microbatches, PipelineEngine-style reference pipe/engine.py:285) or a
        ``batch`` whose leaves carry this process's share of the train batch
        (``train_batch_size / process_count``; with a single controller that
        is the whole batch).
        """
        gas = self.gradient_accumulation_steps()
        if self.param_stream is not None:
            if batch is None:
                it = data_iter if data_iter is not None else iter(self.training_dataloader)
                micro = self._next_microbatches(it, gas)
                batch = jax.tree_util.tree_map(lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
                                               *micro)
            self.tput_timer.start()
            t0 = time.perf_counter() if self.telemetry.enabled else None
            metrics = self.param_stream.train_batch(batch)
            # overflow steps don't advance the runner's (or Adam's) counter;
            # mirror it so checkpoints and the lr schedule stay in sync
            self.global_steps = self.param_stream.global_steps
            self.global_samples += self.train_batch_size()
            self.micro_steps += gas
            self._last_metrics = metrics
            self.tput_timer.stop(global_step=True)
            if t0 is not None:
                dur = time.perf_counter() - t0
                self._last_step_dur = dur
                pt = self.param_stream.last_phase_times or {}
                self.telemetry.record_span(
                    "step", self.telemetry.now() - dur, dur,
                    attrs={"path": "param_stream",
                           "overlap_efficiency": round(pt.get("overlap_efficiency", 0.0), 4)})
                # realized (not dispatched) transfer-overlap evidence: the
                # executor fences every put, so these separate issue time
                # from transfer completion from critical-path exposure
                self.telemetry.gauges([
                    ("offload/put_dispatch_ms", pt.get("put_dispatch_s", 0.0) * 1e3,
                     self.global_samples),
                    ("offload/put_realized_ms", pt.get("put_realized_s", 0.0) * 1e3,
                     self.global_samples),
                    ("offload/fetch_wait_ms", pt.get("drain_s", 0.0) * 1e3,
                     self.global_samples),
                    ("offload/overlap_efficiency", pt.get("overlap_efficiency", 0.0),
                     self.global_samples),
                ])
                self._emit_comm_overlap()
            self._report(metrics)
            if self.lr_scheduler is not None:
                self.lr_scheduler.last_batch_iteration = self.global_steps
            return metrics["loss"]
        if batch is not None:
            # each feeding process supplies its share of the global batch
            # (single-controller: one process feeds everything)
            if self.train_batch_size() % jax.process_count() != 0:
                raise ValueError(f"train_batch_size {self.train_batch_size()} not divisible by "
                                 f"process count {jax.process_count()}")
            expected = self.train_batch_size() // jax.process_count()
            if expected % gas != 0:
                raise ValueError(f"per-process batch share {expected} not divisible by "
                                 f"gradient_accumulation_steps {gas}")
            leading = {np.shape(x)[0] for x in jax.tree_util.tree_leaves(batch)}
            if leading != {expected}:
                raise ValueError(
                    f"train_batch(batch=...) leaves have leading dim {sorted(leading)}; expected "
                    f"this process's share of {expected} samples (train_batch "
                    f"{self.train_batch_size()} = micro {self.train_micro_batch_size_per_gpu()} x "
                    f"gas {gas} x dp {self.dp_world_size()}, over {jax.process_count()} processes)")
            stacked = jax.tree_util.tree_map(
                lambda x: np.asarray(x).reshape((gas, -1) + np.shape(x)[1:]), batch)
        else:
            it = data_iter if data_iter is not None else iter(self.training_dataloader)
            micro = self._next_microbatches(it, gas)
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *micro)
        if self.curriculum_scheduler is not None and self.curriculum_type == "seqlen":
            seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
            # truncate only the known sequence-bearing keys (reference
            # engine.py:1663 curriculum_seqlen); other leaves pass untouched
            stacked = {k: (v[:, :, :seqlen] if k in ("input_ids", "labels", "attention_mask")
                           and np.ndim(v) >= 3 else v)
                       for k, v in stacked.items()}
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
            if getattr(self.module, "supports_pld", False):
                stacked = dict(stacked)
                # one theta per microbatch: every batch leaf must carry the
                # gas leading dim the fused step scans over
                stacked["__pld_theta__"] = np.full((gas, ), self.progressive_layer_drop.get_theta(),
                                                   np.float32)
            else:
                from ..utils.logging import warning_once
                warning_once("progressive_layer_drop enabled but the model does not consume it "
                             "(no supports_pld attribute; deepspeed_tpu.models transformers do) "
                             "— schedule advances with NO effect")
        if self.random_ltd_scheduler is not None:
            keep = int(self.random_ltd_scheduler.update_seq(self.global_steps))
            # clamp to the batch's sequence length: values past it are inert,
            # so advancing within the inert range must not retrace
            ref_leaf = stacked.get("input_ids", jax.tree_util.tree_leaves(stacked)[0])
            keep = min(keep, int(np.shape(ref_leaf)[-1]))
            if keep != self._ltd_current:
                self.module.set_random_ltd(keep, self.random_ltd_scheduler.random_ltd_layer_id)
                for name in ("train_batch", "offload_grads", "micro"):
                    self._compiled.pop(name, None)  # new static keep -> retrace
                self._ltd_current = keep
        stacked = self._shard_batch(stacked, leading_scan_dim=True)

        self.tput_timer.start()
        # compression scheduler (reference engine.py:1268): advance the step
        # and re-trace the compiled step when the compression graph changes
        # (a transform activates, MoQ drops a bit, act-quant switches on)
        if hasattr(self.module, "transforms") and hasattr(self.module, "_active"):
            self._maybe_update_eigenvalue(stacked)
            sig = getattr(self.module, "compression_signature", None)
            before = sig() if sig else len(self.module._active())
            self.module.global_step = self.global_steps
            after = sig() if sig else len(self.module._active())
            if after != before:
                self._compiled.clear()
        t0 = time.perf_counter() if self.telemetry.enabled else None
        if t0 is not None:
            from ..ops.pallas import flash_attention
            flash_before = flash_attention.traced()
            head_before, _ = dist.traced_head_grad()
            micro_before, gathers_before = self._micro_traces, len(gather_order.traced())
        if self.offload_optimizer:
            metrics = self._offload_train_batch(stacked)
        else:
            fn = self._get("train_batch", self._build_onebit_train_fn if self._onebit
                           else self._build_train_batch_fn)
            if self.telemetry.enabled and self._step_flops is None:
                self._step_flops = self._cost_analysis_flops(fn, self.state, stacked)
            with self.mesh:
                self.state, metrics = fn(self.state, stacked)
        if t0 is not None:
            _device_sync()
            dur = time.perf_counter() - t0
            self._last_step_dur = dur
            self.telemetry.record_span(
                "step", self.telemetry.now() - dur, dur,
                attrs={"path": "offload" if self.offload_optimizer else "fused",
                       "micro_batches": gas})
            self._emit_step_counters()
            # a step that traced its program says what its flash kernels
            # compute (scores against the mask's, the masked body's share)
            # and what lse and delta take in HBM a layer call, beside their
            # values: a layout that pads shows up as the ratio
            calls = flash_attention.traced()[len(flash_before):]
            if calls:
                mean = lambda get: sum(map(get, calls)) / len(calls)
                self.telemetry.gauges([(name, mean(get), self.global_samples) for name, get in (
                    ("kernels/flash_scores_computed_pct", lambda c: c.plan.computed_pct),
                    ("kernels/flash_scores_masked_pct", lambda c: c.plan.masked_pct),
                    ("kernels/flash_stats_bytes_at_rest", lambda c: c.stats_bytes_at_rest),
                    ("kernels/flash_stats_bytes_values", lambda c: c.stats_bytes_values))])
            # and what its cross-entropy asks of the links for the head's
            # weight gradient: one loss a microbatch, so the last backward
            # traced stands for each of them
            traced, (sums, nbytes) = dist.traced_head_grad()
            if traced > head_before:
                self.telemetry.gauges([
                    ("zero/head_grad_reductions_per_step", sums * gas, self.global_samples),
                    ("zero/head_grad_reduced_bytes_per_step", nbytes * gas, self.global_samples)])
            # and how many of its large weight gathers the program placed
            # (stage 3 across chips: forward and backward, a microbatch each)
            # with their gathered bytes; a step with nothing sharded reads 0
            if self._micro_traces > micro_before:
                placed = dict(gather_order.traced()[gathers_before:])
                self.telemetry.gauges([
                    ("zero/param_gathers_pinned_per_step", len(placed) * gas, self.global_samples),
                    ("zero/param_gather_bytes_per_step", sum(placed.values()) * gas,
                     self.global_samples)])
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self.micro_steps += gas
        self._last_metrics = metrics
        self.tput_timer.stop(global_step=True)
        self._maybe_profile_flops(stacked)
        self._report(metrics)
        if self.lr_scheduler is not None:
            self.lr_scheduler.last_batch_iteration = self.global_steps
        return metrics["loss"]

    def forward(self, batch):
        """Facade: compute microbatch loss + gradients, buffer them.
        (Forward/backward fuse under XLA; splitting them would double
        compute, so `forward` does both and `backward` is the accumulation
        boundary bookkeeping — semantics match the reference 3-call API.)"""
        if self.mesh.shape[dist.PIPE_AXIS] > 1:
            raise RuntimeError(
                "the forward/backward/step facade is not supported under pipeline parallelism; "
                "use train_batch() (the reference PipelineEngine likewise only supports "
                "train_batch, pipe/engine.py:285)")
        if self.offload_optimizer or self.param_stream is not None:
            raise RuntimeError("the forward/backward/step facade is not supported with "
                               "offload_optimizer/offload_param; use train_batch()")
        if self._onebit:
            raise RuntimeError("the forward/backward/step facade is not supported with 1-bit "
                               "optimizers (the compressed exchange lives inside the fused "
                               "shard_map step); use train_batch()")
        tel = self.telemetry
        if self._trace_spans:
            self.timers(FORWARD_GLOBAL_TIMER).start()
        self._ensure_grad_acc()
        batch = self._shard_batch(batch)
        fn = self._get("micro", self._build_micro_fn)
        if tel.enabled:
            if self._fwd_since_step == 0:
                self._facade_t0 = time.perf_counter()
            self._fwd_since_step += 1
            if self._step_flops is None:
                # one micro-step's cost × gas ≈ the full step (the apply
                # half is negligible next to fwd+bwd)
                self._step_flops = (self._cost_analysis_flops(fn, self.state, batch)
                                    * self.gradient_accumulation_steps())
        with self.mesh:
            self.state, loss = fn(self.state, batch)
        if self._trace_spans:
            t = self.timers(FORWARD_GLOBAL_TIMER)
            # NOT synchronized: a fence here would serialize host and device
            # every micro-step (the facade's whole point is async dispatch);
            # on async backends this span measures dispatch + compile, and
            # the fenced step() span carries the true device time
            t.stop()
            if tel.enabled:
                dur = t.last()
                tel.record_span("fwd", tel.now() - dur, dur)
        # keep the device array: no host sync per micro-step
        self._pending_batches.append(loss)
        return loss

    def backward(self, loss=None, allreduce_gradients=True, retain_graph=False):
        """Facade: gradients were produced in forward(); this marks the
        micro-step boundary (reference engine.py:1765)."""
        if self._trace_spans:
            t = self.timers(BACKWARD_GLOBAL_TIMER)
            t.start()
            t.stop()
            if self.telemetry.enabled:
                # gradients were already produced inside forward() (fwd+bwd
                # fuse under XLA); the span marks the micro-step boundary
                dur = t.last()
                self.telemetry.record_span("bwd", self.telemetry.now() - dur, dur,
                                           attrs={"fused_into": "fwd"})
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self):
        return int(self.state.micro_step) % self.gradient_accumulation_steps() == 0

    def step(self, lr_kwargs=None):
        """Facade: apply the buffered gradients if at a boundary (reference
        engine.py:1961)."""
        if int(self.state.micro_step) < self.gradient_accumulation_steps():
            return  # not at boundary yet
        if self._trace_spans:
            self.timers(STEP_GLOBAL_TIMER).start()
        pending = self._pending_batches[-self.gradient_accumulation_steps():]
        loss_mean = (jnp.mean(jnp.stack([jnp.asarray(p, jnp.float32) for p in pending]))
                     if pending else jnp.zeros((), jnp.float32))
        fn = self._get("apply", self._build_apply_fn)
        with self.mesh:
            self.state, metrics = fn(self.state, loss_mean)
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._pending_batches = []
        self._last_metrics = metrics
        if self._trace_spans:
            t = self.timers(STEP_GLOBAL_TIMER)
            t.stop(synchronize=self.telemetry.enabled)
            if self.telemetry.enabled:
                dur = t.last()
                self.telemetry.record_span("step", self.telemetry.now() - dur, dur,
                                           attrs={"path": "facade"})
        if self.telemetry.enabled:
            if self._facade_t0 is not None:
                # fwd..step wall time of the whole accumulation window — the
                # denominator the MFU gauge uses on the facade path
                self._last_step_dur = time.perf_counter() - self._facade_t0
            self._facade_t0 = None
            self._fwd_since_step = 0
            self._emit_step_counters()
        self._report(metrics)
        if self.lr_scheduler is not None:
            self.lr_scheduler.last_batch_iteration = self.global_steps
        return metrics

    def eval_batch(self, batch):
        if self.param_stream is not None:
            return jnp.asarray(self.param_stream.eval_batch(batch)["loss"])
        batch = self._shard_batch(batch)
        fn = self._get("eval", self._build_eval_fn)
        with self.mesh:
            return fn(self.state, batch)

    def __call__(self, batch):
        return self.eval_batch(batch)

    def allreduce_gradients(self, bucket_size=None):
        """No-op: gradient reduction is inside the compiled step (XLA
        collectives inserted by the partitioner). Kept for API parity."""

    def zero_grad(self):
        zero_fn = self._get(
            "zero_grad",
            lambda: jax.jit(lambda s: s._replace(grad_acc=jax.tree_util.tree_map(jnp.zeros_like, s.grad_acc),
                                                 micro_step=jnp.zeros((), jnp.int32)),
                            donate_argnums=(0, ), out_shardings=self.state_shardings))
        with self.mesh:
            self.state = zero_fn(self.state)

    # ------------------------------------------------------------------ reporting
    def _maybe_update_eigenvalue(self, stacked):
        """MoQ curvature schedule (reference engine.py:1268 eigenvalue hook):
        at ``gas_boundary_resolution`` intervals, power-iterate the loss
        Hessian and scale the compressed model's quantize periods by
        ``1 + floor(ev_norm * 4)`` — high-curvature phases quantize slower.
        Simplification vs the per-layer reference factors, documented: one
        global factor from the max-normalized mean of the subtree values."""
        ev_cfg = dict(self._config.raw_config.get("eigenvalue", {}))
        if not ev_cfg.get("enabled") or not hasattr(self.module, "eigenvalue_factor"):
            return
        if self._eigenvalue is None:
            from .eigenvalue import Eigenvalue
            keys = ("verbose", "max_iter", "tol", "stability", "gas_boundary_resolution",
                    "layer_name", "layer_num")
            self._eigenvalue = Eigenvalue(**{k: ev_cfg[k] for k in keys if k in ev_cfg})
        res = max(1, int(self._eigenvalue.gas_boundary_resolution))
        if self.global_steps == 0 or self.global_steps % res != 0:
            return
        import math
        mb = jax.tree_util.tree_map(lambda x: x[0], stacked)
        try:
            evs = self._eigenvalue.compute_eigenvalue(self.module.loss, self.state.params, mb)
        except Exception as e:
            logger.warning(f"eigenvalue: computation failed ({e}); keeping factor "
                           f"{self.module.eigenvalue_factor}")
            return
        vals = np.asarray([abs(v) for v in evs.values()], np.float64)
        if vals.size and vals.max() > 0:
            ev_norm = float(np.mean(vals / vals.max()))
            self.module.eigenvalue_factor = 1 + math.floor(ev_norm * 4)
            log_dist(f"eigenvalue: factor={self.module.eigenvalue_factor} "
                     f"(normalized mean {ev_norm:.3f})", [0])

    def _maybe_profile_flops(self, stacked):
        """flops_profiler section: at profile_step, read XLA's cost analysis
        of the compiled train step and log achieved vs peak (reference
        engine.py:1636 flops_profiler integration; here the counts come from
        the compiler, not module hooks)."""
        fp = self._config.flops_profiler
        if not fp.enabled or self.global_steps != fp.profile_step:
            return
        from ..profiling.flops_profiler.profiler import profile_compiled, number_to_string
        name = "offload_grads" if self.offload_optimizer else "train_batch"
        fn = self._compiled.get(name)
        if fn is None:
            return
        try:
            stats = profile_compiled(fn, self.state, stacked)
        except Exception as e:
            logger.warning(f"flops_profiler: cost analysis unavailable ({e})")
            return
        self.flops_profile = stats
        peak = get_accelerator().peak_flops()
        msg = (f"flops profile @ step {self.global_steps}: "
               f"{number_to_string(stats['flops'], 'FLOPs')}/step, "
               f"{number_to_string(stats.get('bytes_accessed', 0), 'B')} accessed")
        if peak:
            msg += f", peak {number_to_string(peak, 'FLOP/s')}"
        log_dist(msg, [0])
        if fp.output_file:
            import json as _json
            with open(fp.output_file, "w") as f:
                _json.dump(stats, f, indent=2)

    def _cost_analysis_flops(self, fn, *args):
        """XLA cost-analysis FLOPs of one compiled step, read from the
        lowering (trace-only; see ``profiling/flops_profiler``). 0.0 when
        unavailable — the MFU gauge is then simply not emitted."""
        try:
            from ..profiling.flops_profiler.profiler import profile_compiled
            with self.mesh:
                return float(profile_compiled(fn, *args).get("flops", 0.0))
        except Exception as e:
            logger.warning(f"telemetry: step cost analysis unavailable ({e})")
            return 0.0

    def _emit_step_counters(self):
        """Per-step analytic comms accounting. XLA inserts the gradient
        collectives inside the compiled step (no host-observable per-op
        hook, by design — see comm/comm.py), so DP gradient-sync traffic is
        accounted from the sharding plan: ring all-reduce moves
        2(n-1)/n × fp32 grad bytes per step."""
        tel = self.telemetry
        if not tel.enabled:
            return
        if self._grad_sync_bytes_cached is None:
            n = self.dp_world_size()
            param_bytes = 4 * sum(int(np.prod(x.shape))
                                  for x in jax.tree_util.tree_leaves(self.state.params))
            self._grad_sync_bytes_cached = (int(param_bytes * 2 * (n - 1) / n)
                                            if n > 1 else 0)
        if self._grad_sync_bytes_cached:
            tel.counter("comm/grad_sync/bytes", self._grad_sync_bytes_cached,
                        attrs={"estimate": "ring_all_reduce", "dp": self.dp_world_size()})
        self._emit_comm_overlap()

    def _emit_comm_overlap(self):
        """Drain this step's comm realized/overlap accounting
        (``comm/overlap.py`` — host->device batch placement, control-plane
        collectives) into gauges: ``comm/{op}/realized_ms``,
        ``comm/{op}/dispatch_ms``, ``comm/overlap_efficiency``. Same
        realized-vs-exposed definition as ``offload/overlap_efficiency``
        (PR 5), so the two read on one scale."""
        tel = self.telemetry
        if not tel.enabled:
            return
        stats = dist.get_overlap_tracker().collect(reset=True)
        if not stats["ops"]:
            return
        gauges = []
        for op, s in sorted(stats["ops"].items()):
            gauges.append((f"comm/{op}/realized_ms", s["realized_s"] * 1e3,
                           self.global_samples))
            gauges.append((f"comm/{op}/dispatch_ms", s["dispatch_s"] * 1e3,
                           self.global_samples))
        gauges.append(("comm/overlap_efficiency", stats["overlap_efficiency"],
                       self.global_samples))
        tel.gauges(gauges)

    def _interval_gauges(self):
        """MFU + device/host memory watermark gauges for one logging
        interval, as (name, value, step) tuples. Step axis is
        ``global_samples`` — the same axis the Train/Samples scalars use, so
        monitor backends see one monotonic step stream."""
        out = []
        if self._step_flops and self._last_step_dur:
            peak = get_accelerator().peak_flops()
            if peak:
                mfu = self._step_flops / self._last_step_dur / (peak * jax.device_count())
                out.append(("mfu", mfu, self.global_samples))
        try:
            stats = get_accelerator().memory_stats() or {}
        except Exception:
            stats = {}
        if "bytes_in_use" in stats:
            out.append(("memory/device_bytes_in_use", stats["bytes_in_use"], self.global_samples))
        if "peak_bytes_in_use" in stats:
            out.append(("memory/device_peak_bytes", stats["peak_bytes_in_use"], self.global_samples))
        try:
            import psutil
            out.append(("memory/host_rss_bytes", psutil.Process().memory_info().rss,
                        self.global_samples))
        except Exception:
            pass
        return out

    def _report(self, metrics):
        if self.global_steps % self.steps_per_print() == 0:
            # single host sync per print interval
            loss = float(metrics["loss"])
            lr = float(metrics["lr"])
            scale = float(metrics["loss_scale"])
            norm = float(metrics["grad_norm"])
            msg = (f"step={self.global_steps} loss={loss:.4f} lr={lr:.3e} grad_norm={norm:.3f}")
            if self.fp16_enabled():
                msg += f" loss_scale={scale:g}"
            log_dist(msg, [0])
            # single reporting call site: ONE batched sink call per interval
            # fans these out to the tb/wandb/csv monitor backends (one
            # write_events/flush) and, when telemetry is enabled, into the
            # JSONL/trace as gauges
            tel = self.telemetry
            scalars = [("Train/Samples/train_loss", loss, self.global_samples),
                       ("Train/Samples/lr", lr, self.global_samples)]
            if self.fp16_enabled():
                scalars.append(("Train/Samples/loss_scale", scale, self.global_samples))
            if tel.enabled:
                scalars.append(("Train/Samples/grad_norm", norm, self.global_samples))
                scalars.extend(self._interval_gauges())
            tel.gauges(scalars)
            if self._slo is not None:
                self._slo.maybe_evaluate()
            if self.profiler is not None:
                # report-boundary capture point: starts a pending
                # request_profile() and reaps an overdue capture
                started = self.profiler.maybe_capture(tag="report")
                if started is not None:
                    log_dist(f"xla profile capture started: {started}", [0])

    def request_profile(self, duration_s=1.0):
        """Arm a duration-bounded XLA device-trace capture that begins at
        the next report interval (``steps_per_print`` boundary) — traces
        land under the telemetry output path, one ``xla_trace_*`` directory
        per capture. Raises when telemetry is disabled; raises
        :class:`~deepspeed_tpu.telemetry.profiler.ProfileBusy` when a
        capture is already in flight or pending."""
        if self.profiler is None:
            raise RuntimeError("request_profile requires telemetry.enabled "
                               "(the trace needs an output path)")
        self.profiler.request(duration_s)

    # ------------------------------------------------------------------ data
    def deepspeed_io(self, dataset, batch_size=None, route=None, data_sampler=None, collate_fn=None, num_local_io_workers=None):
        from .dataloader import DeepSpeedDataLoader
        # one JAX process feeds every device it controls (single-controller
        # model), so the loader yields the process-local share of the global
        # microbatch — micro_bs × dp ÷ processes — not the per-device size,
        # and each process reads a disjoint interleaved shard of the dataset
        if batch_size is None:
            global_micro = self.train_micro_batch_size_per_gpu() * self.dp_world_size()
            if global_micro % jax.process_count() != 0:
                raise ValueError(
                    f"global microbatch {global_micro} not divisible by process count "
                    f"{jax.process_count()}; adjust train_micro_batch_size_per_gpu")
            batch_size = global_micro // jax.process_count()
        if (data_sampler is None and self._data_sampling_cfg.get("enabled")
                and route in (None, "train") and self._data_sampler is not None):
            # a later train loader (e.g. per-epoch rebuild) REUSES the live
            # sampler: its curriculum position and checkpoint state carry over
            data_sampler = self._data_sampler
        elif (data_sampler is None and self._data_sampling_cfg.get("enabled")
                and route in (None, "train") and self._data_sampler is None
                and hasattr(dataset, "__len__")):
            # train route only (reference wires ROUTE_TRAIN only): eval
            # loaders must see one ordered pass, and the training sampler's
            # checkpoint state must not be clobbered by later loaders
            # curriculum-clustered sampling wired into the loader (reference
            # builds DeepSpeedDataSampler inside deepspeed_io,
            # data_pipeline/data_sampler.py:36). One feeding process = one
            # "rank" of the sampler; it yields that process's micro-batch
            # index lists.
            from .data_pipeline.data_sampler import DeepSpeedDataSampler
            data_sampler = DeepSpeedDataSampler(
                {"data_sampling": self._data_sampling_cfg,
                 "seed": self._data_sampling_cfg.get("seed", self._seed)},
                one_epoch_total_samples=len(dataset),
                micro_batch_size=batch_size,
                data_parallel_rank=jax.process_index(),
                data_parallel_size=jax.process_count(),
                gradient_accumulation_steps=self.gradient_accumulation_steps(),
                drop_last=self._config.dataloader_drop_last)
            self._data_sampler = data_sampler
            if self._pending_sampler_state is not None:
                # checkpoint loaded before the sampler existed: apply now
                data_sampler.load_state_dict(self._pending_sampler_state)
                self._pending_sampler_state = None
                log_dist("deepspeed_io: restored data-sampler state from the loaded "
                         "checkpoint", [0])
            log_dist(f"deepspeed_io: DeepSpeedDataSampler wired "
                     f"(curriculum={'on' if data_sampler.curriculum_enabled else 'off'}, "
                     f"{len(dataset)} samples/epoch)", [0])
        return DeepSpeedDataLoader(dataset,
                                   batch_size=batch_size,
                                   collate_fn=collate_fn or self.collate_fn,
                                   drop_last=self._config.dataloader_drop_last,
                                   seed=self._seed,
                                   data_sampler=data_sampler,
                                   num_shards=jax.process_count(),
                                   shard_index=jax.process_index())

    # ------------------------------------------------------------------ checkpoint
    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True, exclude_frozen_parameters=False):
        """Sharded, layout-independent checkpoint (reference engine.py:2802;
        the universal-checkpoint property — resumable onto a different mesh —
        comes free because arrays are saved as global logical tensors).

        **Shared-filesystem requirement (param offload)**: on the
        param-offload path only RANK 0 writes the store/client/latest files
        (the host-resident state is replicated, and per-rank writes would
        race on the same paths), so ``save_dir`` MUST be on a filesystem
        visible to every process (NFS/GCS-fuse/Lustre). With per-host local
        dirs, non-zero hosts end up with an empty ``save_dir`` and a later
        ``load_checkpoint`` there returns ``(None, None)``. The non-offload
        path has no such requirement: every host writes (and reads back) its
        own shard files."""
        from .checkpoint_engine.engine import save_checkpoint as _save
        tag = tag or f"global_step{self.global_steps}"
        client_sd = dict(client_state or {})
        client_sd.update({
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": int(self.state.skipped_steps),
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler is not None else None,
            "data_sampler": (self._data_sampler.state_dict()
                             if self._data_sampler is not None else None),
            "ds_config": self._config.raw_config,
            # elastic resume: the restore side compares this against its own
            # world to detect (and validate) a resize across the checkpoint
            "world_size": self._config.world_size,
        })
        if self.param_stream is not None:
            # param offload: every block (master + moments) is host-resident
            # and replicated across processes, so only rank 0 writes the
            # store/client files into a shared checkpoint dir (a per-rank
            # write would race on the same npz/meta/json paths)
            tag_dir = os.path.join(save_dir, str(tag))
            if jax.process_index() == 0:
                self.param_stream.save_checkpoint(tag_dir)
                with open(os.path.join(tag_dir, "client_state.json"), "w") as f:
                    import json as _json
                    _json.dump({k: v for k, v in client_sd.items()
                                if isinstance(v, (int, float, str, bool, dict, list, type(None)))}, f)
                if save_latest:
                    with open(os.path.join(save_dir, "latest"), "w") as f:
                        f.write(str(tag))
            # non-zero ranks must not report success (or start a dependent
            # load/eviction) while rank 0 is still writing
            dist.barrier()
            log_dist(f"saved param-offload checkpoint {save_dir}/{tag}", [0])
            return True
        # grad_acc is in-flight facade scratch, not training state — always
        # checkpoint the canonical (empty) structure so resume works from
        # either API path (the reference likewise never checkpoints IPG
        # buffers, engine.py:3012)
        _save(save_dir, tag, self.state._replace(grad_acc={}), client_sd, save_latest=save_latest,
              use_async=self._config.checkpoint.async_save)
        if self.offload_optimizer:
            # every host saves ITS partition of the offloaded master/moments
            # (streamed block npz, shared by both tiers); the loader
            # reassembles across rank files, so resume survives mesh resize
            self.host_opt.save_to(os.path.join(save_dir, str(tag)))
        log_dist(f"saved checkpoint {save_dir}/{tag}", [0])
        return True

    def wait_checkpoint_saves(self):
        """Block until any in-flight async checkpoint (checkpoint.async_save)
        is committed and its 'latest' pointer written."""
        from .checkpoint_engine.engine import wait_pending_saves
        wait_pending_saves()

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True, load_optimizer_states=True,
                        load_lr_scheduler_states=True, load_module_only=False, custom_load_fn=None):
        """Load a checkpoint saved by :meth:`save_checkpoint`. Param-offload
        checkpoints are written by rank 0 only, so ``load_dir`` must be the
        SHARED directory every process can see (see the save-side
        docstring); a host-local dir on non-zero ranks silently has no
        checkpoint and returns ``(None, None)``."""
        from .checkpoint_engine.engine import load_checkpoint as _load
        if self.param_stream is not None:
            from .checkpoint_engine.engine import get_latest_tag
            tag_used = tag or get_latest_tag(load_dir)
            if tag_used is None:
                return None, None
            tag_dir = os.path.join(os.path.abspath(load_dir), str(tag_used))
            load_opt = load_optimizer_states and not load_module_only
            if not self.param_stream.load_checkpoint(tag_dir, load_optimizer_states=load_opt):
                return None, None
            client_sd = {}
            cs = os.path.join(tag_dir, "client_state.json")
            if os.path.isfile(cs):
                import json as _json
                with open(cs) as f:
                    client_sd = _json.load(f)
            if load_module_only:
                self.loaded_checkpoint_tag = tag_used
                return load_dir, client_sd
            self.global_steps = client_sd.get("global_steps", self.param_stream.global_steps)
            self.param_stream.global_steps = self.global_steps
            self.global_samples = client_sd.get("global_samples", 0)
            self.micro_steps = client_sd.get("micro_steps", 0)
            if load_lr_scheduler_states and self.lr_scheduler is not None and client_sd.get("lr_scheduler"):
                self.lr_scheduler.load_state_dict(client_sd["lr_scheduler"])
            self._elastic_on_restore(client_sd)
            self.loaded_checkpoint_tag = tag_used
            return load_dir, client_sd
        state, client_sd = _load(load_dir, tag, self.state_shardings._replace(grad_acc={}), self.mesh,
                                 template=self.state._replace(grad_acc={}),
                                 load_optimizer_states=load_optimizer_states,
                                 load_module_only=load_module_only)
        if state is None:
            return None, None
        self._drop_grad_acc()
        self.state = state
        if self.offload_optimizer:
            tag_used = tag or client_sd.get("__tag__") or None
            from .checkpoint_engine.engine import get_latest_tag
            tag_dir = os.path.join(os.path.abspath(load_dir),
                                   str(tag_used or get_latest_tag(load_dir)))
            if not (load_optimizer_states and self.host_opt.load_from(tag_dir)):
                logger.warning("offload_optimizer: checkpoint carries no offloaded optimizer "
                               "state (saved without offload?); rebuilding fp32 master from "
                               "loaded params with fresh moments")
                self.host_opt.reset_from_params(self.state.params,
                                                client_sd.get("global_steps", 0))
            # device params re-derive from master so both views agree exactly
            self.state = self.state._replace(params=self.host_opt.compute_params(
                self.compute_dtype, self.state_shardings.params))
        self.global_steps = client_sd.get("global_steps", int(self.state.step))
        self.global_samples = client_sd.get("global_samples", 0)
        self.micro_steps = client_sd.get("micro_steps", 0)
        if load_lr_scheduler_states and self.lr_scheduler is not None and client_sd.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(client_sd["lr_scheduler"])
        if client_sd.get("data_sampler"):
            if self._data_sampler is not None:
                self._data_sampler.load_state_dict(client_sd["data_sampler"])
            else:
                # loader not built yet (load-then-deepspeed_io order): stash
                # and apply when the sampler is created
                self._pending_sampler_state = client_sd["data_sampler"]
        self._elastic_on_restore(client_sd)
        self.loaded_checkpoint_tag = tag
        return load_dir, client_sd

    def _elastic_on_restore(self, client_sd):
        """Elastic resume validation: with the ``elasticity`` section
        enabled and a checkpoint stamped at a DIFFERENT world size, the
        :class:`~deepspeed_tpu.elasticity.ElasticityManager` re-solves the
        batch tiling for this world and asserts the effective train batch
        did not move across the resize (incompatibility raises — resuming
        with a bent loss curve is worse than failing loudly)."""
        from ..elasticity import ElasticityManager, elasticity_enabled
        if not elasticity_enabled(self._config.raw_config):
            return
        ElasticityManager(self._config.raw_config).on_restore(
            self._config.world_size, client_sd, telemetry=self.telemetry)

    def save_16bit_model(self, save_dir, save_filename="pytree_model.msgpack", exclude_frozen_parameters=False):
        """Consolidated compute-dtype export (reference engine.py:3223
        ``save_16bit_model`` / ``_zero3_consolidated_16bit_state_dict``)."""
        import flax.serialization
        os.makedirs(save_dir, exist_ok=True)
        # stream one leaf at a time: gather → host fetch → free, so peak HBM
        # overhead is one tensor, not the whole model replicated per device
        # (the reference's stage-3 consolidation likewise walks params in
        # groups, engine.py:3156)
        replicated = NamedSharding(self.mesh, P())
        cast_one = jax.jit(lambda x: jnp.asarray(x, self.compute_dtype), out_shardings=replicated)
        leaves, treedef = jax.tree_util.tree_flatten(self.state.params)
        host_leaves = []
        with self.mesh:
            for leaf in leaves:
                host_leaves.append(jax.device_get(cast_one(leaf)))
        full = jax.tree_util.tree_unflatten(treedef, host_leaves)
        path = os.path.join(save_dir, save_filename)
        if jax.process_index() == 0:
            with open(path, "wb") as f:
                f.write(flax.serialization.to_bytes(full))
        return path
