"""Compile a cell's programs at real size for a v5e that is described and not
attached (guide ``on-chip-measurement``, section 2), and print each one's
memory analysis. No chip time; nothing runs, so this gives no result and no
time: a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python -m chipbench.rehearse --workload <cell> [--slots 16,24,32] [--micro-batch 4]

- a ``train`` cell: the engine's fused train step on the cell's mesh (for
  four chips: ``v5e:2x2``), with the engine's state as shapes;
- a ``serve`` cell: the scheduler's (K, chunk) and (K, 1) greedy step
  programs at each ``--slots`` count (default: the cell's), which is how the
  cell's ``num_slots`` was chosen: the largest multiple of 8 for which
  pool + weights + the programs' temporaries fit the chip.

This file reaches into the program's private builders (the engine's state
initialisers, ``DecodeScheduler._fused_fn``) because a described device
holds no array; it is a tool for the builder, not part of a run.
"""

import argparse
import dataclasses
import json
import os
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from chipbench import cells  # noqa: E402

GIB = 2**30


def _report(name, compiled, t0, extra=None):
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    line = {"program": name, "compile_s": round(time.perf_counter() - t0, 1),
            "args_gib": ma.argument_size_in_bytes / GIB, "temps_gib": ma.temp_size_in_bytes / GIB,
            "output_gib": ma.output_size_in_bytes / GIB, "alias_gib": ma.alias_size_in_bytes / GIB,
            "code_gib": ma.generated_code_size_in_bytes / GIB,
            "tpu_custom_call": text.count("tpu_custom_call"),
            "collectives": {k: text.count(k) for k in ("all-gather", "reduce-scatter",
                                                       "all-reduce")}}
    # what the device must hold while the program runs: arguments + outputs
    # that are not donated arguments + temporaries
    line["live_gib"] = (line["args_gib"] + line["output_gib"] - line["alias_gib"]
                        + line["temps_gib"] + line["code_gib"])
    line.update(extra or {})
    print(json.dumps(line), flush=True)
    return line


def rehearse_train(workload, config, topo):
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.runtime import engine as eng_mod

    p = workload["train"]
    chips = workload["chips"]
    comm.initialize_mesh(devices=list(topo.devices)[:chips], **p.get("mesh", {}))
    model = cells.build_model(config, **p.get("model_overrides", {}))

    def init_params(self, model_, _params):
        abstract = jax.eval_shape(model_.init_params, self._base_rng)
        shardings = self.planner.shardings(self.planner.master_specs(abstract))
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=s),
            abstract, shardings)

    real_jit = jax.jit

    def init_state(self, params):
        # the engine's own _init_state, with its one execution (the jitted
        # initialiser) replaced by its shapes
        holder = {}

        def fake_jit(fn, **kw):
            holder["out_shardings"] = kw.get("out_shardings")
            return lambda prm: jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                jax.eval_shape(fn, prm), holder["out_shardings"])
        eng_mod.jax.jit = fake_jit
        try:
            return real_init_state(self, params)
        finally:
            eng_mod.jax.jit = real_jit

    real_init_state = eng_mod.DeepSpeedEngine._init_state
    eng_mod.DeepSpeedEngine._init_params = init_params
    eng_mod.DeepSpeedEngine._init_state = init_state
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": p["micro_batch_per_chip"],
        "optimizer": p["optimizer"], "bf16": {"enabled": True},
        "gradient_clipping": p.get("gradient_clipping", 1.0),
        "zero_optimization": {"stage": p["zero_stage"]}, "steps_per_print": 10**9})
    fn = engine._build_train_batch_fn()
    dp = "data" if engine.mesh.shape["data"] > 1 else None
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (1, engine.train_batch_size(), p["seq_len"]), jnp.int32,
        sharding=NamedSharding(engine.mesh, P(None, dp)))}
    t0 = time.perf_counter()
    with engine.mesh:
        compiled = fn.lower(engine.state, batch).compile()
    return _report("train_step", compiled, t0, {"chips": chips,
                                                "micro_batch_per_chip": p["micro_batch_per_chip"],
                                                "seq_len": p["seq_len"]})


def rehearse_serve(workload, config, topo, slot_counts):
    from deepspeed_tpu.inference.scheduler import DecodeScheduler
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    p = workload["serve"]
    one_chip = SingleDeviceSharding(topo.devices[0])
    base = cells.build_model(config)
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, int8_weights=True, int8_fused_qkv=True,
        attention_impl="flash", scan_layers=False))
    cb = DeepSpeedInferenceConfig({}).continuous_batching
    K, C = cb.steps_per_sync, cb.prefill_chunk

    def sds(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=one_chip)

    def as_served(path, a):  # quantize_params: floats to bf16, group scales stay fp32
        name = str(path[-1])
        keep = a.dtype != jnp.float32 or "scale" in name and name != "['scale']"
        return sds(a, None if keep else jnp.bfloat16)

    params = jax.tree_util.tree_map_with_path(
        as_served, jax.eval_shape(model.init_params, jax.random.key(0)))
    out = []
    for n in slot_counts:
        pool = jax.tree_util.tree_map(sds, jax.eval_shape(
            lambda: model.init_cache(n, p["max_len"])))
        mock = types.SimpleNamespace(
            engine=types.SimpleNamespace(module=model), _fused_block=True, _shard_deg=1,
            _moe_stats=False, experts=None, _compiled={}, capacity=None, _pool_sharding=None)
        mock._program = types.MethodType(DecodeScheduler._program, mock)
        mock._jit_step = types.MethodType(DecodeScheduler._jit_step, mock)
        for width in (C, 1):
            fn = DecodeScheduler._fused_fn(mock, False, False, K, width)
            i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
            args = (params, pool, i32(n, width), i32(n), i32(n),
                    jax.ShapeDtypeStruct((n, ), jnp.uint32, sharding=one_chip), i32(n),
                    jax.ShapeDtypeStruct((n, ), jnp.bool_, sharding=one_chip),
                    jax.ShapeDtypeStruct((n, ), jnp.float32, sharding=one_chip), i32(n),
                    jax.ShapeDtypeStruct((n, ), jnp.float32, sharding=one_chip))
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(*args).compile()
            except Exception as e:  # noqa: BLE001 — what the chip's compiler refuses is the finding
                print(json.dumps({"program": f"step(K={K},C={width})", "num_slots": n,
                                  "refused": str(e)[:600]}), flush=True)
                continue
            out.append(_report(f"step(K={K},C={width})", compiled, t0, {"num_slots": n}))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", default=None, help="serve: comma-separated slot counts")
    ap.add_argument("--micro-batch", type=int, default=None,
                    help="train: another per-chip micro-batch than the cell's, to find the largest")
    args = ap.parse_args(argv)
    if jax.default_backend() != "cpu":
        sys.exit("chipbench.rehearse: run with JAX_PLATFORMS=cpu (the chip is described, "
                 "not attached)")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    import deepspeed_tpu.ops.pallas as pallas_pkg
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    pallas_pkg.interpret = lambda: False  # compile the kernels, do not interpret them
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    _, workload, root = cells.load_workload(args.workload)
    config = cells.load_config(workload["config"], root)
    if args.micro_batch and workload["job"] == "train":
        workload["train"]["micro_batch_per_chip"] = args.micro_batch
    if workload["job"] == "train":
        rehearse_train(workload, config, topo)
    elif workload["job"] == "serve":
        slots = ([int(s) for s in args.slots.split(",")] if args.slots
                 else [workload["serve"]["num_slots"]])
        rehearse_serve(workload, config, topo, slots)
    else:
        sys.exit(f"chipbench.rehearse: no rehearsal for job {workload['job']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
