"""The ``train`` job: ``deepspeed_tpu.initialize`` + ``engine.train_batch``
on fresh batches, a fenced window, the loss checked against the reference.

Set-up (all before the window, all in ``setup_s``): build the engine with
weights from ``--seed``; generate the batches on the host; the reference's
float32 loss on the first batch from the engine's own master parameters
(no second copy), before the first step allocates its temporaries; two
warm-up steps (the first compiles). ``correct``: the engine's first loss
(before any update) equals the reference's within
``reference.TRAIN_LOSS_TOL``; every loss of the window is finite; the mean
of the window's last losses is below the first loss; nothing compiles
inside the window.

The window: each step places its batch on the device (``train_batch`` does
it, so the input path is measured) and dispatches; at most ``in_flight``
steps are outstanding, so the host runs ahead of the device without
queueing the whole window. The clock starts after a fence and stops after
the fence behind the last step: every step counted is finished, all of
the window's time is counted.
"""

import math
import statistics
import time

import jax
import numpy as np

from chipbench import flops, reference, traffic
from chipbench.cells import build_model
from chipbench.harness import TracedWindow, finish_trace

IN_FLIGHT = 2


def run(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm

    p = ctx.workload["train"]
    seq, micro = p["seq_len"], p["micro_batch_per_chip"]
    comm.initialize_mesh(devices=list(ctx.devices), **p.get("mesh", {}))
    model = build_model(ctx.config, **p.get("model_overrides", {}))
    cfg = model.cfg
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": p["optimizer"],
        "bf16": {"enabled": bool(p.get("bf16", True))},
        "gradient_clipping": p.get("gradient_clipping", 1.0),
        "zero_optimization": {"stage": p["zero_stage"]},
        "steps_per_print": 10**9,
        "seed": ctx.seed % (2**31 - 1),  # weights from --seed
    })
    jax.block_until_ready(engine.state.params)
    ctx.setup_part("engine_build")

    global_batch = engine.train_batch_size()
    tokens_per_step = global_batch * seq
    batches = traffic.packed_batches(p["data"], ctx.seed, cfg.vocab_size, seq, global_batch,
                                     p["data"]["distinct_batches"])
    ctx.setup_part("data")

    # ---- reference loss on batch 0, from the engine's own fp32 parameters
    ref_kw = reference.kwargs_for(ctx.config, cfg)
    sabotage = ctx.workload.get("force_wrong")  # fixtures only: prove that correct can be false

    def ref_loss(params, ids):
        tree = reference.from_train_tree(params)
        if sabotage:
            tree["lnf_g"] = tree["lnf_g"] * 1.5
        return reference.loss(tree, ids, **ref_kw)

    rows = p["reference_rows"]
    with engine.mesh:
        ref_fn = jax.jit(ref_loss)
        parts = [float(ref_fn(engine.state.params, batches[0][i:i + rows]))
                 for i in range(0, global_batch, rows)]
    ref = statistics.fmean(parts)
    del ref_fn
    ctx.setup_part("reference")

    # ---- warm up: the first step compiles; its loss is the engine's loss
    # on batch 0 before any update
    first = float(engine.train_batch(batch={"input_ids": batches[0]}))
    warm = [first] + [float(engine.train_batch(batch={"input_ids": batches[i % len(batches)]}))
                      for i in range(1, 1 + p["warm_steps"])]
    ctx.setup_part("compile_and_warm")
    ctx.note(reference_loss=ref, engine_first_loss=first, diff=abs(first - ref),
             tol=reference.TRAIN_LOSS_TOL, warm_losses=warm, global_batch=global_batch,
             seq_len=seq, params=cfg.num_params(),
             doc_share_eos=float(np.mean(batches[0] == p["data"]["eos_token_id"])))

    # ---- the window
    programs_before = ctx.compiles["programs"]
    losses, done_at, pending = [], [], []
    step = len(warm)
    traced = TracedWindow(ctx, p["trace_window_s"])
    ctx.mark_window_start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        if traced.due():
            with jax.profiler.TraceAnnotation("chipbench/fence"):
                jax.block_until_ready(pending)
            traced.stop()
        with jax.profiler.TraceAnnotation("chipbench/train_batch"):
            loss = engine.train_batch(batch={"input_ids": batches[step % len(batches)]})
        pending.append(loss)
        step += 1
        if len(pending) > IN_FLIGHT:
            with jax.profiler.TraceAnnotation("chipbench/fence"):
                jax.block_until_ready(pending[0])
            done_at.append(time.perf_counter())
            losses.append(pending.pop(0))
    jax.block_until_ready(pending)
    t1 = time.perf_counter()
    traced.stop()
    losses += pending
    losses = [float(x) for x in losses]
    late_compiles = ctx.compiles["programs"] - programs_before

    steps = len(losses)
    tail = statistics.fmean(losses[-max(1, steps // 10):])
    checks = {
        "loss_matches_reference": abs(first - ref) <= reference.TRAIN_LOSS_TOL,
        "losses_finite": all(math.isfinite(x) for x in losses + warm),
        "loss_fell": tail < first,
        "no_compile_in_window": late_compiles == 0,
    }
    rate = steps * tokens_per_step / (t1 - t0) / ctx.chips
    step_ms = [(b - a) * 1e3 for a, b in zip(done_at, done_at[1:])]
    obs = {
        "correct": all(checks.values()), "checks": checks,
        "attempted": steps, "failed": sum(not math.isfinite(x) for x in losses),
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "values": {# the traced run stops the profiler inside its window, so its
                   # utilization is taken from the median step, not the window
                   "steady_tokens_per_s_per_chip": tokens_per_step / ctx.chips
                   / (statistics.median(step_ms) * 1e-3) if step_ms else None,
                   "train_flops_per_token": flops.train_flops_per_token(cfg, seq),
                   "steps": steps},
        "series": {"train_step_ms": step_ms},
        # one layer's attention, forward and backward (three kernel calls)
        "work": {"flash_fwd_bwd": tuple(map(sum, zip(*(flops.flash_attention_call(
            micro, cfg.num_heads, cfg.kv_heads, seq, cfg.head_size, 2, backward=b)
            for b in (False, True)))))},
        "info": {"window_s": t1 - t0, "steps": steps, "first_loss": first,
                 "last_losses_mean": tail, "late_compiles": late_compiles,
                 "step_ms_median": statistics.median(step_ms) if step_ms else None,
                 "step_ms_max": max(step_ms) if step_ms else None},
    }
    finish_trace(ctx, traced, obs)
    return obs
