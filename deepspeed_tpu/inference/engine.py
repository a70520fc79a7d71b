"""Inference engine.

TPU-native analogue of reference ``inference/engine.py`` (``InferenceEngine``
:89, ``_create_model_parallel_group`` :261, ``forward`` :560) plus the
generation path the reference implements with injected CUDA kernels
(``module_inject/replace_module.py:279`` + ``pt_binding.cpp:1745``). Design
translation:

- Kernel injection -> the model's Pallas attention paths
  (``attention_impl='flash'``: flash prefill + GQA decode kernel); the
  "no-kernel" path is pure XLA. Both share one weight layout — there is no
  module rewriting because models here are functional already.
- CUDA-graph capture -> jit: prefill and the whole decode loop compile to two
  XLA programs per (batch, prompt-bucket) shape.
- AutoTP -> the model's PartitionSpec rules over the ``tensor`` mesh axis
  (``runtime/zero/sharding.py:TensorParallelRules``).
- KV-cache workspace -> a preallocated (L, B, kv_heads, S, head_dim) pair,
  donated through the decode loop.

Batched generation uses left-padding: prompts are right-aligned so every row
shares one cache write head; per-row RoPE/learned positions come from
``position_ids`` and left-pad slots are masked out of attention.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import comm as dist
from ..runtime.zero.sharding import ShardingPlanner
from ..telemetry import TelemetrySink, get_sink, set_sink
from ..utils.logging import logger, log_dist
from .config import DeepSpeedInferenceConfig


def _round_up(x, m):
    return (x + m - 1) // m * m


def _sample_tokens(rng, logits, do_sample, temperature, top_k, top_p):
    """Greedy or filtered sampling. logits: (B, V) fp32."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always >= 1 token)
        keep = jnp.concatenate([jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p], axis=-1)
        threshold = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(rng, logits).astype(jnp.int32)


class FusedDecodeEligibility:
    """Structured result of the fused decode-block gate
    (:meth:`InferenceEngine._fused_decode_eligible`): truthy iff the decode
    loop can use ``ops/pallas/decode_block``; otherwise ``reasons`` names
    EVERY failing condition — surfaced in the ready line, ``/v1/metrics``,
    and ``_shard_desc()`` so an operator never has to guess why the fast
    path didn't activate."""
    __slots__ = ("eligible", "reasons")

    def __init__(self, reasons=()):
        self.reasons = tuple(reasons)
        self.eligible = not self.reasons

    def __bool__(self):
        return self.eligible

    def __repr__(self):
        return (f"FusedDecodeEligibility(eligible={self.eligible}, "
                f"reasons={list(self.reasons)})")


class InferenceEngine:
    """Wraps a zoo model (or preset name) for TP-sharded generation."""

    def __init__(self, model, config=None, params=None):
        self._construct(model, config, params, materialize=True)

    @classmethod
    def from_shared_params(cls, model, config=None, params=None):
        """Supported constructor for engines whose weights are OWNED AND
        PUBLISHED EXTERNALLY (the RLHF hybrid engine's
        :class:`~deepspeed_tpu.rlhf.WeightPublisher`): runs the full
        ``__init__`` path — config validation, dtype/kernel overrides, mesh
        and sharding setup, telemetry wiring — but installs ``params``
        as-is (possibly ``None`` until the first publication) instead of
        loading a checkpoint or initializing random weights.

        This replaces the old ``InferenceEngine.__new__`` + field-poking
        pattern, which silently skipped config validation and every
        invariant later ``__init__`` revisions added."""
        eng = cls.__new__(cls)
        eng._construct(model, config, params, materialize=False)
        return eng

    def _construct(self, model, config, params, materialize):
        self._config = config if isinstance(config, DeepSpeedInferenceConfig) else \
            DeepSpeedInferenceConfig(dict(config or {}))
        cfg = self._config

        if isinstance(model, str):
            from ..models import get_model
            model = get_model(model)
        if not hasattr(model, "cfg") or not hasattr(model, "apply_with_cache"):
            raise ValueError("init_inference expects a deepspeed_tpu model (CausalLMModel or preset "
                             f"name); got {type(model)}")

        # the mesh decides the EFFECTIVE tensor parallelism (a pre-existing
        # mesh with tensor>1 shards serving even when the config left
        # tp_size at 1), so resolve it BEFORE the model-config overrides
        # that depend on it (int8 fused-qkv gating, the bitwise-TP layout)
        tp = cfg.tensor_parallel.tp_size
        if dist.has_mesh():
            self.mesh = dist.get_mesh()
            if self.mesh.shape[dist.TENSOR_AXIS] != tp and tp > 1:
                raise ValueError(f"existing mesh has tensor={self.mesh.shape[dist.TENSOR_AXIS]}, "
                                 f"config asks tp_size={tp}")
        else:
            self.mesh = dist.initialize_mesh(tensor=tp)
        tp_eff = self.mesh.shape[dist.TENSOR_AXIS]

        # dtype + kernel selection are model-config switches. dtype 'int8'
        # means INT8 WEIGHTS + bf16 compute (reference csrc int8
        # dequant-GEMM serving): the memory-bound decode loop reads half
        # the HBM bytes through the Pallas quant matmul.
        self._int8_weights = cfg.dtype == jnp.int8
        if self._int8_weights and not materialize:
            raise ValueError("from_shared_params does not support dtype=int8: the "
                             "int8 tier quantizes at materialization, but shared "
                             "params are published post-hoc in the compute layout")
        compute_dtype = jnp.bfloat16 if self._int8_weights else cfg.dtype
        overrides = {"dtype": compute_dtype, "decode_block_kv": cfg.decode_block_kv}
        # serving bitwise-TP layout (see TransformerConfig.bitwise_tp): only
        # column-parallel shards + activation re-replication before the
        # row-parallel matmuls, so tp>1 logits stay bit-identical to tp=1.
        # Head-divisibility gate: unevenly-sharded head axes make GSPMD pad
        # shards and re-split contractions (measured ulp drift), so when the
        # head counts don't divide the tensor degree serving falls back to
        # FULLY REPLICATED weights — tp>1 either shards bit-identically or
        # replicates loudly, never drifts silently.
        nh = getattr(model.cfg, "num_heads", None)
        nkv = getattr(model.cfg, "kv_heads", nh) or nh
        heads_divide = nh is None or (nh % tp_eff == 0 and nkv % tp_eff == 0)
        self._tp_replicated_fallback = tp_eff > 1 and not heads_divide
        if self._tp_replicated_fallback:
            logger.warning(
                f"init_inference: mesh tensor={tp_eff} but head counts "
                f"(num_heads={nh}, kv_heads={nkv}) don't divide it — serving "
                f"REPLICATED (uneven head shards would cost bit-identity); "
                f"choose a tensor degree dividing the kv head count to shard")
        overrides["bitwise_tp"] = tp_eff > 1 and heads_divide
        # expert parallelism (MoE serving): the `expert` mesh axis shards
        # the expert kernels and the per-expert FFN batch; the combine
        # all-gathers (pure concat) so ep>1 logits stay bit-identical to
        # ep=1. A non-dividing expert count falls back to REPLICATED expert
        # weights — loudly, mirroring the head-divisibility rule above (the
        # MoE layer skips its expert constraints when E % ep != 0, and the
        # planner's divisibility validation relaxes the expert rules).
        ep_eff = self.mesh.shape[dist.EXPERT_AXIS]
        n_experts = getattr(model.cfg, "num_experts", 0)
        self._ep_replicated_fallback = (ep_eff > 1 and n_experts > 0
                                        and n_experts % ep_eff != 0)
        if self._ep_replicated_fallback:
            logger.warning(
                f"init_inference: mesh expert={ep_eff} but num_experts="
                f"{n_experts} doesn't divide it — serving REPLICATED expert "
                f"weights (uneven expert shards would cost bit-identity)")
        self._int8_fused_note = None
        if self._int8_weights and hasattr(model.cfg, "int8_weights"):
            overrides["int8_weights"] = True
            if hasattr(model.cfg, "int8_fused_qkv"):
                # fused [q;k;v] matmul: fewer/larger pallas calls per decode
                # step; tp>1 (by the MESH, not just the config knob) FORCES
                # split projections: the fused N axis concatenates [q;k;v],
                # so a plain column shard would split across component
                # boundaries, and quantize_params' qkv_q matches no tp_rules
                # pattern (it would silently replicate). The split q/k/v
                # kernels shard column-wise per tp_rules instead.
                overrides["int8_fused_qkv"] = tp_eff == 1
                if tp_eff > 1:
                    self._int8_fused_note = (
                        f"tensor={tp_eff} shards split q/k/v projections "
                        f"column-wise; the fused [q;k;v] column axis cannot "
                        f"shard without splitting component boundaries")
                    logger.warning(
                        "init_inference(int8): fused-qkv decode disabled under "
                        f"tensor parallelism (mesh tensor={tp_eff}) — {self._int8_fused_note}")
        elif self._int8_weights:
            raise ValueError(f"dtype=int8 requires a model with int8 weight support "
                             f"(CausalLMModel family); got {type(model)}")
        if getattr(model.cfg, "latent_width", 0) and (self._int8_weights or tp_eff > 1):
            raise ValueError(
                "latent attention is served in its float dtype on an unsharded pool: "
                "int8 weights and a tensor mesh axis > 1 are not supported yet")
        if getattr(model.cfg, "layer_types", ()) and self._int8_weights:
            raise ValueError("a model with layer_types (per-layer mixers) is served in its "
                             "float dtype: int8 weights are not supported yet")
        if cfg.kernel_inject and hasattr(model.cfg, "scan_layers"):
            overrides["attention_impl"] = "flash"
            # unrolled layers: the KV cache becomes per-layer tensors that
            # alias in-place through the decode while-loop carry — a scanned
            # model's stacked cache is rebuilt (full copy, ~2x cache bytes of
            # HBM traffic) every token
            overrides["scan_layers"] = False
        # config families differ (e.g. BertConfig has no decode_block_kv)
        known = {f.name for f in dataclasses.fields(model.cfg)}
        overrides = {k: v for k, v in overrides.items() if k in known}
        self.module = type(model)(dataclasses.replace(model.cfg, **overrides))
        self.model_config = self.module.cfg

        # fused decode-block gating: every failing condition gets a concrete
        # reason (ready line + /v1/metrics + warning) instead of the old
        # silent boolean chain. Only meaningful for int8 configs that asked
        # for the fast path — an fp engine stays quiet.
        self._fused_decode_note = None
        if self._int8_weights and hasattr(self.model_config, "int8_weights"):
            elig = self._fused_decode_eligible()
            if not elig:
                self._fused_decode_note = "; ".join(elig.reasons)
                logger.warning("init_inference(int8): fused decode-block disabled — "
                               + self._fused_decode_note)

        # cold-expert host offload (continuous_batching.expert_offload):
        # expert kernels leave the device tree at materialization and page
        # through moe/expert_store.py; only the scheduler path can serve
        self._expert_offload = (cfg.continuous_batching.expert_offload
                                if cfg.continuous_batching.expert_offload.enabled
                                else None)
        self._expert_host = None
        self._expert_store = None
        if self._expert_offload is not None:
            if getattr(self.model_config, "num_experts", 0) <= 0:
                raise ValueError("continuous_batching.expert_offload requires a "
                                 "MoE model (num_experts > 0)")
            if not getattr(self.model_config, "scan_layers", True):
                raise ValueError(
                    "expert_offload requires scan_layers (stacked expert "
                    "kernels); kernel_inject unrolls the layer stack — "
                    "disable one of the two")
            if ep_eff > 1:
                raise ValueError(
                    f"expert_offload requires expert mesh axis 1 (got {ep_eff}): "
                    f"pages replicate across the mesh — shard experts OR page "
                    f"them, not both")
            if not materialize:
                raise ValueError("expert_offload is unsupported for shared-params "
                                 "engines: expert pages are captured at "
                                 "materialization")

        # the replicated fallback hands the planner NO tensor rules at all:
        # every weight replicates, which trivially preserves bit-identity
        tp_rules = (() if getattr(self, "_tp_replicated_fallback", False)
                    else self.module.tp_rules())
        self.planner = ShardingPlanner(self.mesh, None, tp_rules=tp_rules,
                                       expert_pattern=self.module.expert_pattern())
        # shared-params engines never materialize: the publisher installs
        # (and later swaps) the compute-layout tree
        self.params = self._materialize_params(params) if materialize else params
        self._compiled = {}
        self._cache_pool = {}  # (B, S) -> reusable KV cache buffers
        # telemetry: reuse an already-installed global sink (e.g. the
        # training engine's, so train + serve share one event stream), else
        # build one from this config's 'telemetry' section
        self.telemetry = get_sink()
        if self.telemetry is None or not self.telemetry.enabled:
            if dict(cfg.telemetry or {}).get("enabled"):
                self.telemetry = TelemetrySink(cfg.telemetry)
                set_sink(self.telemetry)
            elif self.telemetry is None:
                self.telemetry = TelemetrySink(None)
        self._inflight = 0  # submitted-not-yet-fetched requests
        self._scheduler = None  # lazily-built continuous-batching scheduler
        self._adapter_store = None  # lazily-built paged LoRA store (multi_lora)
        log_dist(
            f"InferenceEngine ready: model dtype={jnp.dtype(self.model_config.dtype).name} "
            f"{self._shard_desc()} kernel_inject={cfg.kernel_inject} "
            f"max_out_tokens={cfg.max_out_tokens}", [0])

    def _shard_desc(self):
        """The REAL shard configuration, for the ready line and the serving
        metrics surface: the effective mesh tensor size (which may exceed
        the config's tp_size when a training mesh pre-exists), the layout in
        force, whether the KV pool's head axis actually shards (the
        divisibility fallback), and the int8 fused-qkv gating outcome."""
        tp_eff = self.mesh.shape[dist.TENSOR_AXIS]
        if tp_eff <= 1:
            desc = "tp=1"
        elif getattr(self, "_tp_replicated_fallback", False):
            nh = getattr(self.model_config, "num_heads", None)
            nkv = getattr(self.model_config, "kv_heads", None)
            desc = (f"tp={tp_eff} (REPLICATED fallback: num_heads={nh}/"
                    f"kv_heads={nkv} don't divide the tensor degree)")
        else:
            nkv = getattr(self.model_config, "kv_heads", None)
            kv = ("kv_heads sharded /" + str(tp_eff)
                  if nkv is not None and nkv % tp_eff == 0
                  else f"kv replicated ({nkv} kv_heads % tp={tp_eff} != 0)")
            desc = f"tp={tp_eff} (bitwise all-gather layout, {kv})"
        if self._int8_weights:
            fused = getattr(self.model_config, "int8_fused_qkv", False)
            desc += (f" int8_fused_qkv={'on' if fused else 'off'}"
                     + (f" ({self._int8_fused_note})"
                        if getattr(self, "_int8_fused_note", None) else ""))
        n_experts = getattr(self.model_config, "num_experts", 0)
        if n_experts:
            ep_eff = self.mesh.shape[dist.EXPERT_AXIS]
            topk = getattr(self.model_config, "moe_top_k", 0)
            if ep_eff <= 1:
                moe = "ep=1"
            elif getattr(self, "_ep_replicated_fallback", False):
                moe = (f"ep={ep_eff} (REPLICATED experts: num_experts="
                       f"{n_experts} doesn't divide the expert degree)")
            else:
                moe = f"ep={ep_eff} (expert-sharded, all-gather combine)"
            desc += f" moe[{n_experts}e top{topk}] {moe}"
            if getattr(self, "_expert_offload", None) is not None:
                R = int(self._expert_offload.resident_experts) or n_experts
                desc += f" expert_offload=on ({R}/{n_experts} resident)"
        if getattr(self, "_fused_decode_note", None):
            desc += f" fused_decode=off ({self._fused_decode_note})"
        elif self._int8_weights and hasattr(self.model_config, "int8_weights"):
            desc += " fused_decode=on"
        return desc

    # ------------------------------------------------------------------ params
    def _adapt_layout(self, params, host=False):
        """Convert between stacked ('layers', scan form) and per-layer
        ('layer_i', unrolled form) parameter trees so checkpoints/params from
        either model layout serve under the other (kernel_inject runs
        unrolled; training models usually scan). ``host=True`` stays in
        numpy (the int8 quantize path must not touch HBM)."""
        scan = getattr(self.model_config, "scan_layers", None)
        if params is None or scan is None or not isinstance(params, dict):
            return params
        stack = (lambda *xs: np.stack(xs)) if host else (lambda *xs: jnp.stack(xs))
        take = (lambda x, i: np.asarray(x)[i]) if host else (lambda x, i: x[i])
        L = self.model_config.num_layers
        if not scan and "layers" in params:
            params = dict(params)
            stacked = params.pop("layers")
            for i in range(L):
                params[f"layer_{i}"] = jax.tree_util.tree_map(lambda x, i=i: take(x, i), stacked)
        elif scan and "layer_0" in params:
            params = dict(params)
            layers = [params.pop(f"layer_{i}") for i in range(L)]
            params["layers"] = jax.tree_util.tree_map(stack, *layers)
        return params

    def _strip_experts(self, params, cast=True):
        """Pop the (host) experts subtree for the cold-expert pager: the
        expert kernels must never land in HBM — the stripped tree places,
        and the serving MoE path reads pool pages instead of params. With
        ``cast`` the leaves follow the same floating->compute-dtype rule
        placement applies, so paged and in-tree kernels are byte-identical;
        the int8 path passes ``cast=False`` (quantize_params already
        emitted the final dtypes — int8 kernels, fp32 scales)."""
        dtype = np.dtype(jnp.dtype(self.model_config.dtype).name)
        params = dict(params)
        params["layers"] = dict(params["layers"])
        moe = params["layers"]["moe"] = dict(params["layers"]["moe"])
        experts = moe.pop("experts")
        def conv(x):
            x = np.asarray(x)
            if cast and np.issubdtype(x.dtype, np.floating):
                return x.astype(dtype)
            return x
        self._expert_host = {k: conv(v) for k, v in experts.items()}
        return params

    def _materialize_params(self, params):
        if params is None and self._config.checkpoint:
            params = self._load_checkpoint_host(self._config.checkpoint)
        if params is None and self._expert_offload is not None and not self._int8_weights:
            # debug/test path: flax init materializes the FULL tree (experts
            # included) on the default device once before the host pull —
            # models whose experts genuinely exceed HBM must pass
            # params/checkpoint instead
            logger.warning(
                "init_inference(expert_offload): no checkpoint/params given; "
                "random init materializes the full expert tree on device ONCE "
                "before stripping — pass params/checkpoint for models whose "
                "experts exceed HBM")
            params = jax.tree_util.tree_map(np.asarray,
                                            self.module.init_params(jax.random.key(0)))
        if self._int8_weights and params is None:
            logger.warning("init_inference(int8): no checkpoint/params given; quantizing "
                           "random weights")
            import dataclasses as _dc
            bf16_module = type(self.module)(_dc.replace(self.model_config,
                                                        int8_weights=False))
            params = jax.tree_util.tree_map(
                lambda x: np.asarray(x),
                bf16_module.init_params(jax.random.key(0)))
        if self._int8_weights:
            # host-side quantize BEFORE placement: the bf16 tree never
            # reaches HBM (the point of int8 serving is halving those bytes)
            host = jax.tree_util.tree_map(np.asarray, params)
            params = self.module.quantize_params(self._adapt_layout(host, host=True))
            if self._expert_offload is not None:
                # no cast: quantize_params already emitted the final leaf
                # dtypes (int8 kernels, fp32 scales)
                params = self._strip_experts(params, cast=False)
            shardings = self.planner.shardings(self.planner.master_specs(params))
            with self.mesh:
                return jax.device_put(params, shardings)
        params = self._adapt_layout(params)
        if self._expert_offload is not None and params is not None:
            params = self._strip_experts(jax.tree_util.tree_map(np.asarray, params))
        shardings = self.planner.shardings(self.planner.master_specs(
            params if params is not None else jax.eval_shape(self.module.init_params, jax.random.key(0))))
        dtype = self.model_config.dtype
        if params is not None:
            leaves, placed = jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shardings)
            if all(isinstance(x, jax.Array) and x.dtype == dtype
                   and x.sharding.is_equivalent_to(sh, x.ndim)
                   for x, sh in zip(leaves, placed)):
                # already what the cast would return (a model made on the
                # device in its serving dtype): a jitted identity would hold
                # a second copy of the weights while it runs
                return params
            cast = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), p),
                           out_shardings=shardings)
            with self.mesh:
                return cast(params)
        logger.warning("init_inference: no checkpoint/params given; initializing random weights")
        init = jax.jit(lambda rng: jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype),
                                                          self.module.init_params(rng)),
                       out_shardings=shardings)
        with self.mesh:
            return init(jax.random.key(0))

    def _load_checkpoint_host(self, path):
        """Load weights from a ``save_16bit_model`` msgpack export, a
        training checkpoint dir, or a Megatron 'checkpoint json' description
        (reference ``inference/engine.py:419`` -> ``SDLoaderFactory``)."""
        import os
        import flax.serialization
        if isinstance(path, dict) or (isinstance(path, str) and path.endswith(".json")):
            from ..module_inject.policy import MegatronPolicy
            from ..module_inject.replace_module import _check_tree
            from ..runtime.state_dict_factory import SDLoaderFactory
            desc = path if isinstance(path, dict) else None
            if desc is None:
                import json as _json
                with open(path) as f:
                    desc = _json.load(f)
            if str(desc.get("type", "")).lower() not in ("megatron", "ds_model", "bloom"):
                raise ValueError(
                    f"checkpoint description dict has unsupported type {desc.get('type')!r}; "
                    f"expected one of 'Megatron'/'ds_model'/'bloom' with keys "
                    f"{{'type','checkpoints','version'}}, or pass a file/dir path instead")
            version = desc.get("version")
            layout = desc.get("qkv_layout")
            if layout != "blocked" and version not in (0, 0.0):
                raise ValueError(
                    f"Megatron checkpoint version {version!r}: v1.0/2.0 fused QKV is head/"
                    f"rank-interleaved and cannot be split into projections; only version 0 "
                    f"(blocked [q;k;v]) converts — or add 'qkv_layout': 'blocked' to the "
                    f"description if this checkpoint is known-blocked")
            if layout == "blocked":
                # The flag asserts every per-rank tensor is blocked [q;k;v]; the
                # v1+ merge rule (plain rank concat) would interleave ranks, so
                # force the version-0 regrouping merge regardless of the tag
                # (a missing version key defaults to 1.0 in MegatronSDLoader,
                # which would silently scramble Q/K/V the same way).
                desc = {**desc, "version": 0}
            sd = SDLoaderFactory.get_sd_loader_json(desc).load()
            params = MegatronPolicy().convert(sd.__getitem__, self.model_config)
            _check_tree(self.module, params)
            return params
        def module_variants():
            yield self.module
            scan = getattr(self.model_config, "scan_layers", None)
            if scan is not None:  # the file may carry the other layer layout
                yield type(self.module)(dataclasses.replace(self.model_config,
                                                            scan_layers=not scan))

        if os.path.isfile(path):
            with open(path, "rb") as f:
                blob = f.read()
            err = None
            for mod in module_variants():
                template = jax.eval_shape(mod.init_params, jax.random.key(0))
                template = jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), template)
                try:
                    return flax.serialization.from_bytes(template, blob)
                except Exception as e:
                    err = e
            raise ValueError(f"checkpoint {path} matches neither layer layout: {err}")
        from ..runtime.checkpoint_engine.engine import load_params_only
        err = None
        for mod in module_variants():
            abstract = jax.eval_shape(mod.init_params, jax.random.key(0))
            try:
                return load_params_only(path, abstract_params=abstract)
            except Exception as e:
                err = e
        raise ValueError(f"checkpoint {path} matches neither layer layout: {err}")

    # ------------------------------------------------------------------ forward
    def _check_offload_path(self, what):
        if getattr(self, "_expert_host", None) is not None:
            raise ValueError(
                f"{what} reads expert weights from the param tree, which is "
                f"host-resident under continuous_batching.expert_offload — "
                f"serve through the scheduler path (submit() with "
                f"continuous_batching.enabled, or engine.scheduler())")

    def forward(self, input_ids, attention_mask=None):
        """Full-sequence logits (reference ``InferenceEngine.forward`` :560)."""
        self._check_offload_path("forward()")
        if "fwd" not in self._compiled:
            self._compiled["fwd"] = jax.jit(self.module.apply)
        with self.mesh:
            return self._compiled["fwd"](self.params, jnp.asarray(input_ids, jnp.int32),
                                         None if attention_mask is None else jnp.asarray(attention_mask, bool))

    __call__ = forward

    # ------------------------------------------------------------------ generate
    def _fused_decode_eligible(self):
        """Structured gate for the fused per-layer decode kernel
        (``ops/pallas/decode_block.py`` — the reference's fused
        qkv_gemm/softmax_context/mlp_gemm pass, pt_binding.cpp:1745).
        Returns a truthy :class:`FusedDecodeEligibility` for int8 fused-qkv
        serving with unrolled layers at tp=1 and any of: layernorm OR
        rmsnorm, rope (full rotary) / learned / no positions, gated
        (swiglu/geglu) or ungated MLPs, grouped KV heads. Falsy results
        carry a concrete reason per failing condition — the genuinely
        unsupported shapes are alibi, partial rotary, local-attention
        layers, act-quant, attn_scale, parallel residual, and MoE.

        VMEM gate (ADVICE r5): the fused kernels' k-block pickers
        (``pick_block_k``) never split a quantization group, so a coarse
        group (``int8_group_size`` > the 1024 cap, or a dim the group size
        doesn't divide — quantize_params then falls back to ONE group
        spanning the whole contraction dim) forces a weight block covering
        the full K axis, which can exceed VMEM at compile time. Such
        configs fall back to the per-projection path instead."""
        mc = self.model_config
        reasons = []

        if not getattr(mc, "int8_weights", False):
            reasons.append("dtype is not int8 (the fused kernels stream "
                           "int8 weights)")
        elif not getattr(mc, "int8_fused_qkv", False):
            reasons.append("int8_fused_qkv=off"
                           + (f" ({self._int8_fused_note})"
                              if getattr(self, "_int8_fused_note", None)
                              else ""))
        if getattr(mc, "scan_layers", True) is not False:
            reasons.append("scan_layers=True (the fused path needs "
                           "per-layer unrolled caches; enable kernel_inject)")
        if getattr(mc, "num_experts", 0) > 0:
            reasons.append(
                f"num_experts={mc.num_experts}: the fused per-layer decode "
                f"kernel has no expert dispatch; serving the per-projection "
                f"MoE path")
        if getattr(mc, "parallel_residual", False):
            reasons.append("parallel_residual=True (the fused out/mlp kernel "
                           "computes the sequential residual)")
        if getattr(mc, "norm", "") not in ("layernorm", "rmsnorm"):
            reasons.append(f"norm={getattr(mc, 'norm', '?')} (fused kernels "
                           f"support layernorm/rmsnorm)")
        if getattr(mc, "embed_norm", False):
            reasons.append("embed_norm=True (no fused embedding norm)")
        if mc.pos_embedding not in ("learned", "none", "rope"):
            reasons.append(f"pos_embedding={mc.pos_embedding}: no in-kernel "
                           f"alibi bias")
        elif (mc.pos_embedding == "rope"
              and (mc.rotary_dim or 0) not in (0, mc.head_size)):
            reasons.append(
                f"partial rotary (rotary_dim={mc.rotary_dim} < head_size="
                f"{mc.head_size}): the in-kernel rotation is full-head only")
        if mc.activation not in ("gelu", "gelu_exact", "quick_gelu", "relu",
                                 "swiglu", "geglu"):
            reasons.append(f"activation={mc.activation} not in the fused "
                           f"out/mlp kernel's set")
        if getattr(mc, "attn_scale", None) is not None:
            reasons.append(f"attn_scale={mc.attn_scale} (fused attention "
                           f"uses the default 1/sqrt(head_size))")
        if getattr(mc, "local_attention_layers", ()):
            reasons.append("local-attention layers (the fused path has no "
                           "per-layer sliding-window starts)")
        if getattr(mc, "layer_types", ()):
            kinds = sorted(set(mc.layer_types) - {"full_attention"})
            reasons.append(f"layer_types ({', '.join(kinds)}: the fused path has one "
                           f"attention block and no recurrent-state update, ring, "
                           f"differential map or value carried between layers)")
        if getattr(mc, "act_quant_bits", 0):
            reasons.append(f"act_quant_bits={mc.act_quant_bits} (no fused "
                           f"fake-quant of block inputs)")
        gs = getattr(mc, "int8_group_size", 0) or 128
        # effective group per contraction dim: quantize_params uses gs
        # only when it divides K, else the whole dim is one group
        dims = (mc.hidden_size,                      # qkv / up K
                mc.num_heads * mc.head_size,         # o-proj K
                getattr(mc, "ffn_size", 4 * mc.hidden_size))  # down K
        bad = [k for k in dims if (gs if k % gs == 0 else k) > 1024]
        if bad:
            reasons.append(
                f"int8 group spans {max(bad)} > 1024 on a contraction dim "
                f"(group_size={gs}): the weight block would exceed VMEM")
        tp_eff = self.mesh.shape[dist.TENSOR_AXIS]
        if tp_eff != 1:
            reasons.append(f"tensor={tp_eff}: the fused kernels are opaque "
                           f"to GSPMD; tp decodes per-projection")
        return FusedDecodeEligibility(reasons)

    def _fast_tree(self):
        """Per-layer tuples for the fused decode kernel, derived once from
        the quantized param tree. Built EAGERLY (no jit wrapper): the int8
        kernels and embedding pass through by reference — a jit'd rebuild
        would copy every weight into fresh buffers and double resident
        model memory; only the small norm/bias/scale leaves convert.

        Keyed on the param-tree OBJECT (``is``, not ``id()`` — a freed
        tree's address can be reused by the replacement, which would
        false-hit): replacing the param tree (a checkpoint reload onto a
        live engine) invalidates the cache, so the fused decode path can
        never keep serving the OLD weights while the unfused prefill uses
        the new ones (a long-lived serving process reloads in place;
        ADVICE r5). Holding the old tree until rebuild costs nothing extra:
        the cached fast tree references the same weight buffers."""
        cached = getattr(self, "_fast_tree_cache", None)
        if cached is not None and cached[0] is self.params:
            return cached[1]
        with self.mesh:
            self._fast_tree_cache = (
                self.params, self.module.fused_decode_operands(self.params))
        return self._fast_tree_cache[1]

    def _fused_step(self, layers, head, caches, tok, pos_rows, pos, pads):
        """One fused-token decode step: embeds -> L fused layer kernels (+
        XLA cache commits) -> final norm -> int8 logits. Returns
        (logits (B, V) f32, new caches)."""
        from ..models.transformer import rope_table
        from ..ops.pallas.decode_block import fused_decode_block
        from ..ops.pallas.quant_matmul import quant_matmul
        mc = self.model_config
        x = jnp.take(head["embed"], tok, axis=0)  # (B, H) bf16
        if mc.pos_embedding == "learned":
            x = x + jnp.take(head["pos_embed"], pos_rows, axis=0).astype(x.dtype)
        rope = None
        if mc.pos_embedding == "rope":
            sin, cos = rope_table(mc.rotary_dim or mc.head_size,
                                  mc.max_seq_len, mc.rope_theta)
            rope = (sin[pos_rows], cos[pos_rows])
        # the float cache of init_cache: (k leaves, v leaves) or, packed,
        # (kv leaves, ); the block takes a layer's leaves as they are
        new_layers = []
        for i, (norms, qkv, o, up, down, gate) in enumerate(layers):
            x, kv = fused_decode_block(
                x, norms, tuple(comp[i] for comp in caches), qkv, o, up, down,
                pads, pos, activation=mc.activation, eps=mc.layernorm_epsilon,
                block_kv=mc.decode_block_kv, norm=mc.norm, rope=rope,
                gate=gate)
            new_layers.append(kv)
        x32 = x.astype(jnp.float32)
        if "final_bias" in head:  # layernorm head
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
            xn = ((x32 - mu) * jax.lax.rsqrt(var + mc.layernorm_epsilon)
                  * head["final_scale"] + head["final_bias"]).astype(x.dtype)
        else:  # rmsnorm
            ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            xn = (x32 * jax.lax.rsqrt(ms + mc.layernorm_epsilon)
                  * head["final_scale"]).astype(x.dtype)
        logits = quant_matmul(xn, head["logits_q"], head["logits_scale"],
                              block_m=8)[:, :mc.vocab_size].astype(jnp.float32)
        if "logits_bias" in head:
            logits = logits + head["logits_bias"]
        return logits, tuple(tuple(lay[j] for lay in new_layers)
                             for j in range(len(caches)))

    def _build_generate(self, B, P, S, W, max_gen, do_sample, temperature, top_k, top_p, eos, pad,
                        padded):
        """``W``: cache write head after prefill (static). Uniform-length
        batches are right-padded to the P bucket with W = true length — no
        cache masking, which enables the flash prefill kernel; ragged batches
        are left-padded with W = P and per-row mask/positions."""
        model = self.module
        fused = self._fused_decode_eligible()
        fused_step = self._fused_step

        def generate(params, fast, cache, ids, pads, max_new, rng):
            # ids: (B, P); pads: (B,) left-pad counts (zeros when uniform)
            cache_mask = (jnp.arange(S)[None, :] >= pads[:, None]) if padded else None
            pos_prefill = jnp.maximum(jnp.arange(P)[None, :] - pads[:, None], 0) if padded else None
            logits, cache = model.apply_with_cache(params, ids, cache, 0, cache_mask, pos_prefill)
            rng, sub = jax.random.split(rng)
            tok = _sample_tokens(sub, logits[:, W - 1].astype(jnp.float32), do_sample, temperature,
                                 top_k, top_p)
            buf = jnp.full((B, max_gen), pad, jnp.int32)
            buf = buf.at[:, 0].set(tok)
            done = (tok == eos) if eos is not None else jnp.zeros((B, ), bool)

            def cond(c):
                _, _, done, t, _, _ = c
                return (t < max_new - 1) & ~jnp.all(done)

            def body(c):
                cache, buf, done, t, rng, tok = c
                if fused:
                    # one pallas call per LAYER (reference fused decode pass)
                    layers, head = fast
                    logits2d, cache = fused_step(layers, head, cache, tok,
                                                 W + t - pads, W + t, pads)
                else:
                    pos = (W + t - pads)[:, None]  # (B, 1) true positions
                    logits, cache = model.apply_with_cache(params, tok[:, None], cache, W + t,
                                                           cache_mask, pos)
                    logits2d = logits[:, 0].astype(jnp.float32)
                rng, sub = jax.random.split(rng)
                nxt = _sample_tokens(sub, logits2d, do_sample, temperature, top_k, top_p)
                if eos is not None:
                    nxt = jnp.where(done, pad, nxt)
                    new_done = done | (nxt == eos)
                else:
                    new_done = done
                buf = jnp.where(done[:, None] | (jnp.arange(max_gen)[None, :] != t + 1), buf,
                                nxt[:, None])
                return cache, buf, new_done, t + 1, rng, nxt

            cache, buf, done, t, rng, tok = jax.lax.while_loop(
                cond, body, (cache, buf, done, jnp.zeros((), jnp.int32), rng, tok))
            n_tokens = jnp.minimum(max_new, max_gen)
            # return the cache: the donated input then aliases an output
            # (true in-place buffers) and the caller pools it for the next
            # generate() call — no per-call allocation or init
            return buf, n_tokens, cache

        return jax.jit(generate, donate_argnums=(2, ))

    def scheduler(self, **overrides):
        """The engine's continuous-batching :class:`DecodeScheduler`
        (``inference/scheduler.py``), built lazily from the
        ``continuous_batching`` config section. ``overrides`` replace config
        fields (num_slots/max_len/collect_logits/...) on first
        construction."""
        if self._scheduler is None:
            from .scheduler import DecodeScheduler
            cb = self._config.continuous_batching
            kw = {"num_slots": cb.num_slots, "max_len": cb.max_len,
                  "collect_logits": cb.collect_logits,
                  "steps_per_sync": cb.steps_per_sync,
                  "prefill_chunk": cb.prefill_chunk,
                  "prefix_cache": cb.prefix_cache,
                  "spec_tokens": cb.spec_tokens,
                  "spec_ngram_max": cb.spec_ngram_max,
                  "spec_ngram_min": cb.spec_ngram_min,
                  "spec_draft": cb.spec_draft,
                  "kv_cache_dtype": cb.kv_cache_dtype}
            # long-context serving: extent chaining, seq-parallel prefill,
            # and the lossy-window gate ride the config section straight
            # through (scheduler validation owns the compose rules)
            lc = cb.long_context
            kw.update(max_extents=lc.max_extents,
                      seq_parallel_min_tokens=lc.seq_parallel_min_tokens,
                      seq_parallel_degree=lc.seq_parallel_degree,
                      allow_lossy_kv=lc.allow_lossy_kv)
            hk = cb.hierarchical_kv
            dg = cb.disaggregation
            if hk.enabled or dg.enabled:
                # ONE store per engine: the scheduler threads it through
                # _init_kwargs, so every ReplicaSet sibling binds the same
                # fleet-global host tier (the weight-tree sharing model).
                # Disaggregated prefill/decode rides the SAME store as its
                # migration transport, so enabling it without the
                # hierarchical tier still builds one (the hk knobs apply)
                from ..memory.prefix_store import GlobalPrefixStore
                kw["prefix_store"] = GlobalPrefixStore(
                    capacity_bytes=int(hk.host_capacity_mb) << 20,
                    nvme_path=hk.nvme_path, telemetry=self.telemetry)
                kw["restore_min_tokens"] = hk.restore_min_tokens
            # multi-LoRA serving: one paged adapter store per engine, shared
            # across the ReplicaSet the same way (register_adapter() before
            # the first scheduler() call also flips this on)
            if cb.multi_lora.enabled or self._adapter_store is not None:
                kw["adapter_store"] = self.adapter_store()
            # cold-expert offload: ONE paged expert store per engine,
            # ReplicaSet siblings bind it by reference like the weight tree
            if self._expert_offload is not None:
                kw["expert_store"] = self.expert_store()
            kw.update(overrides)
            self._scheduler = DecodeScheduler(self, **kw)
        elif overrides:
            raise ValueError("scheduler already built; overrides must be passed on "
                             "the first scheduler() call")
        return self._scheduler

    def expert_store(self):
        """The engine's :class:`~deepspeed_tpu.moe.expert_store.PagedExpertStore`
        (cold-expert offload), built lazily from the host expert pages
        captured at materialization and the
        ``continuous_batching.expert_offload`` section. One store per
        engine — replica schedulers bind it by reference, so a page loaded
        through any replica is resident for all of them."""
        if self._expert_store is None:
            if self._expert_host is None:
                raise ValueError("expert_offload enabled but no host expert pages "
                                 "were captured at materialization")
            from ..moe.expert_store import PagedExpertStore
            eo = self._expert_offload
            E = self.model_config.num_experts
            self._expert_store = PagedExpertStore(
                self._expert_host, self.model_config.num_layers, E,
                int(eo.resident_experts) or E, telemetry=self.telemetry,
                mesh=self.mesh)
        return self._expert_store

    def adapter_store(self):
        """The engine's :class:`~deepspeed_tpu.adapters.PagedAdapterStore`
        (multi-tenant adapter serving), built lazily from the
        ``continuous_batching.multi_lora`` section. One store per engine —
        every scheduler replica binds it by reference, so an adapter loaded
        through any replica is resident for all of them."""
        if self._adapter_store is None:
            from ..adapters import PagedAdapterStore
            ml = self._config.continuous_batching.multi_lora
            self._adapter_store = PagedAdapterStore(
                self.model_config, pool_slots=ml.pool_slots,
                rank_buckets=tuple(ml.rank_buckets), telemetry=self.telemetry,
                mesh=self.mesh)
        return self._adapter_store

    def register_adapter(self, adapter_id, lora_tree=None, sites=None,
                         alpha=16.0, rank=None):
        """Register (or update) a LoRA adapter for per-request serving
        (``submit(..., adapter_id=...)`` / the gateway's ``adapter_id``
        body field). ``lora_tree`` is a ``runtime/lora.LoRAModel`` adapter
        tree; ``sites`` the pre-flattened ``{site: (a, b)}`` form. Builds
        the paged store on first use (so tests and in-process callers don't
        need the config flag); must precede the first ``scheduler()`` call
        only when the config flag is off. Returns the adapter version."""
        if (self._scheduler is not None
                and getattr(self._scheduler, "adapters", None) is None):
            raise ValueError(
                "scheduler already built without multi-LoRA support; enable "
                "continuous_batching.multi_lora or register adapters before "
                "the first scheduler() call")
        return self.adapter_store().register(adapter_id, lora_tree=lora_tree,
                                             sites=sites, alpha=alpha, rank=rank)

    def submit(self, input_ids, **kwargs):
        """Pipelined generation: dispatch and return a handle WITHOUT
        fetching results — the next ``submit`` (or any host work) overlaps
        this request's device execution. ``handle.result()`` returns what
        ``generate`` would.

        With ``continuous_batching.enabled`` the rows join the shared
        iteration-level decode scheduler: requests from DIFFERENT submit()
        calls batch into one decode step, finished rows evict mid-loop, and
        queued rows take their slots without recompiling (Orca/vLLM
        continuous batching; see benchmarks/SERVING.md). Otherwise the
        static-batch program is dispatched per call and only the fetch
        overlaps (the pre-scheduler behavior)."""
        if self._config.continuous_batching.enabled:
            return self._submit_continuous(input_ids, **kwargs)
        tel = self.telemetry
        t0 = tel.now() if tel.enabled else None
        max_new = kwargs.get("max_new_tokens", 64)
        buf, trim = self._generate_raw(input_ids, **kwargs)
        if t0 is not None:
            self._inflight += 1
            tel.gauge("inference/queue_depth", self._inflight)
        eng = self

        class _Handle:
            _accounted = False

            def _settle(self_h):
                if t0 is not None and not self_h._accounted:
                    self_h._accounted = True
                    eng._inflight -= 1
                    tel.gauge("inference/queue_depth", eng._inflight)
                    return True
                return False

            def result(self_h):
                out = trim(np.asarray(jax.device_get(buf)))
                if self_h._settle():
                    eng._record_decode(t0, out, max_new)
                return out

            def __del__(self_h):
                # an abandoned handle (timeout/cancel without result()) must
                # settle the queue-depth gauge — and NEVER raise: at
                # interpreter teardown the gauge/engine globals may already
                # be torn down, and an exception from __del__ prints an
                # "Exception ignored" traceback over the user's exit
                try:
                    self_h._settle()
                except Exception:
                    pass
        return _Handle()

    def _submit_continuous(self, input_ids, max_new_tokens=64, do_sample=False,
                           temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                           pad_token_id=0, seed=0):
        """submit() on the continuous-batching path: each row becomes one
        scheduler request; the returned handle reassembles ``generate()``'s
        per-row output lists (eos-inclusive, like the static path)."""
        sched = self.scheduler()
        handles = []
        try:
            for i, row in enumerate(input_ids):
                handles.append(sched.submit(row, max_new_tokens=max_new_tokens,
                                            eos_token_id=eos_token_id,
                                            do_sample=do_sample,
                                            temperature=temperature, top_k=top_k,
                                            top_p=top_p, seed=seed + i))
        except Exception:
            for h in handles:  # don't orphan already-queued rows
                h.cancel()
            raise

        class _BatchHandle:
            def result(self_h):
                return [h.result() for h in handles]

            @property
            def done(self_h):
                return all(h.done for h in handles)

            def __del__(self_h):
                try:
                    # flag abandoned requests for eviction so their slots
                    # free at the scheduler's next iteration — NEVER pump
                    # the decode loop from GC (__del__ can fire mid-step)
                    for h in handles:
                        if not h.done:
                            h.cancel()
                except Exception:
                    pass
        return _BatchHandle()

    def _record_decode(self, t0, out, max_new_tokens):
        """Decode telemetry for one finished request: a `generate` span, a
        per-token-step latency histogram, and TTFT. The fused decode loop
        makes every token of a request visible at once, so TTFT here equals
        request completion latency (see benchmarks/OBSERVABILITY.md)."""
        tel = self.telemetry
        dur = tel.now() - t0
        n_steps = max(1, max((len(r) for r in out), default=1))
        tokens = int(sum(len(r) for r in out))
        tel.record_span("generate", t0, dur,
                        attrs={"batch": len(out), "tokens": tokens,
                               "max_new_tokens": int(max_new_tokens)})
        tel.histogram("decode/latency_ms_per_token", dur * 1e3 / n_steps)
        tel.histogram("decode/ttft_ms", dur * 1e3)
        tel.counter("decode/tokens", tokens)

    def generate(self, input_ids, max_new_tokens=64, do_sample=False, temperature=1.0, top_k=0,
                 top_p=1.0, eos_token_id=None, pad_token_id=0, seed=0):
        """Batched generation. ``input_ids``: list of token lists or (B, P)
        array. Returns a list of 1-D np arrays of *new* tokens per row
        (trimmed at ``eos_token_id``)."""
        tel = self.telemetry
        t0 = tel.now() if tel.enabled else None
        buf, trim = self._generate_raw(input_ids, max_new_tokens=max_new_tokens,
                                       do_sample=do_sample, temperature=temperature,
                                       top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                                       pad_token_id=pad_token_id, seed=seed)
        out = trim(np.asarray(jax.device_get(buf)))
        if t0 is not None:
            self._record_decode(t0, out, max_new_tokens)
        return out

    def _generate_raw(self, input_ids, max_new_tokens=64, do_sample=False, temperature=1.0,
                      top_k=0, top_p=1.0, eos_token_id=None, pad_token_id=0, seed=0):
        """Dispatch one generate; returns (device buf, trim(host_buf) ->
        per-row new-token arrays). The KV cache returns to the pool
        immediately (device-side refs; execution order serializes reuse)."""
        self._check_offload_path("the static-batch generate() path")
        if set(getattr(self.model_config, "layer_types", ())) - {"full_attention"}:
            raise ValueError("a model whose slots hold recurrent state or ring rows "
                             "(layer_types) is served through the "
                             "continuous-batching scheduler (submit() / the gateway): the "
                             "static-batch generate() cache has no per-row spans to "
                             "advance a recurrent state or a ring by")
        rows = [np.asarray(r, np.int32).reshape(-1) for r in input_ids]
        B = len(rows)
        lens = np.array([len(r) for r in rows], np.int32)
        if lens.min() < 1:
            raise ValueError("generate() requires at least one prompt token per row")
        P = int(_round_up(lens.max(), 64))
        # cache length: multiple of the decode-kernel KV block (or of 64 when
        # the whole cache fits in one block)
        block = self._config.decode_block_kv
        S = int(_round_up(P + max_new_tokens, 64))
        if S > block:
            S = int(_round_up(S, block))
        if S > self.model_config.max_seq_len:
            raise ValueError(f"prompt+max_new_tokens needs cache of {S} > model max_seq_len "
                             f"{self.model_config.max_seq_len}")
        if S > self._config.max_out_tokens:
            raise ValueError(f"prompt+max_new_tokens needs cache of {S} tokens > max_out_tokens="
                             f"{self._config.max_out_tokens}; raise max_out_tokens")
        padded = bool((lens != lens[0]).any())
        ids = np.full((B, P), pad_token_id, np.int32)
        if padded:  # ragged: left-pad so all rows share one write head
            pads = P - lens
            for i, r in enumerate(rows):
                ids[i, pads[i]:] = r
            W = P
        else:  # uniform: right-pad the bucket; decode starts at the true length
            pads = np.zeros(B, np.int32)
            for i, r in enumerate(rows):
                ids[i, :lens[i]] = r
            W = int(lens[0])

        max_gen = S - W
        key = ("gen", B, P, S, W, max_gen, do_sample, float(temperature), int(top_k), float(top_p),
               eos_token_id, pad_token_id, padded)
        if key not in self._compiled:
            self._compiled[key] = self._build_generate(B, P, S, W, max_gen, do_sample, temperature,
                                                       top_k, top_p, eos_token_id, pad_token_id,
                                                       padded)
        # reuse pooled cache buffers: stale contents are never attended (the
        # causal position bias and per-row cache_mask gate every slot)
        cache = self._cache_pool.pop((B, S), None)
        if cache is None:
            cache = self._init_cache(B, S)
        fast = self._fast_tree() if self._fused_decode_eligible() else ()
        with self.mesh:
            buf, _, cache = self._compiled[key](self.params, fast, cache, jnp.asarray(ids),
                                                jnp.asarray(pads),
                                                jnp.asarray(max_new_tokens, jnp.int32),
                                                jax.random.key(seed))
        self._cache_pool[(B, S)] = cache
        while len(self._cache_pool) > 2:  # bound HBM held by idle cache buckets
            self._cache_pool.pop(next(iter(self._cache_pool)))

        def trim(host_buf):
            host_buf = host_buf[:, :max_new_tokens]
            out = []
            for i in range(B):
                row = host_buf[i]
                if eos_token_id is not None:
                    hits = np.nonzero(row == eos_token_id)[0]
                    if hits.size:
                        row = row[:hits[0] + 1]
                out.append(row)
            return out
        return buf, trim

    def _init_cache(self, B, S, kv_dtype=None):
        """``kv_dtype``: None = the model compute dtype; "int8" = the
        group-quantized paged KV tier (int8 K/V leaves and a leaf of joint
        per-token-row scales; serving ``kv_cache_dtype: int8``); any jnp
        float dtype = an explicit-precision plain cache."""
        quantized = kv_dtype == "int8"
        key = ("init_cache", B, S, str(kv_dtype))
        if key not in self._compiled:
            from jax.sharding import NamedSharding, PartitionSpec as P_
            nkv = self.model_config.kv_heads
            shard_kv = nkv % self.mesh.shape[dist.TENSOR_AXIS] == 0

            def build():
                if quantized:
                    return self.module.init_cache(B, S, quantized=True)
                return self.module.init_cache(B, S, dtype=kv_dtype)

            def spec_for(leaf):
                # stacked (L, B, kv, S, hd) or per-layer (B, kv, S, hd);
                # the int8 tier's scale leaves carry a size-1 head axis —
                # only genuinely kv-sized axes shard over tensor
                axes = [None] * leaf.ndim
                if shard_kv and leaf.shape[leaf.ndim - 3] == nkv:
                    axes[leaf.ndim - 3] = dist.TENSOR_AXIS
                return NamedSharding(self.mesh, P_(*axes))

            abstract = jax.eval_shape(build)
            shardings = jax.tree_util.tree_map(spec_for, abstract)
            # cached: a fresh jit wrapper per call would retrace (+~0.7 s)
            # on EVERY generate
            self._compiled[key] = jax.jit(build, out_shardings=shardings)
        with self.mesh:
            return self._compiled[key]()

    # ------------------------------------------------------------------ misc parity
    @property
    def config(self):
        return self._config

    def eval(self):
        return self

    def train(self, mode=True):
        return self
