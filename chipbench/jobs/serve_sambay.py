"""The ``serve_sambay`` job: ``jobs/serve_hybrid.py``'s flow for a
configuration whose slots hold an SSM state, RING rows of windowed attention
layers and the rows of one full-attention layer that other layers share
(Phi-4-mini-flash-reasoning), served in its float dtype through the
per-projection path: ``init_inference`` + ``Gateway(engine, port=0)`` +
``start_background()`` in this process, load from a child over localhost
HTTP with SSE. The end-to-end arithmetic is ``jobs/serve.py``'s
(``reduce_records``), the traffic ``traffic.py``'s, the load ``loadgen.py``'s.
``serve_hybrid`` itself cannot run the configuration: its draw of the weights
redraws ``A_log`` by the gated-delta rule's start and scales the block's
norms for a post-normed stack, its reference takes no windows, and it knows
two kinds of leaf.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (``serve_ref.seeded_params``: normal(0, 0.02)
   kernels, norm scales 1, biases 0), with each Mamba layer's ``A_log``,
   ``dt_bias`` and ``D`` and each attention layer's four lambda vectors drawn
   from the seed by the layers' published starts instead (the program's own
   initialisers: ``A_log = log(1..16)`` a channel, ``dt`` log-uniform in
   [0.001, 0.1], ``D`` 1, lambdas normal(0, 0.1)), so that channels forget at
   different rates and lambda is not a constant, and each Mamba layer's
   ``W_x`` at deviation 0.1, so that the state's read-out is as large as the
   skip term beside it (``sambay_params``);
2. ``correct``, part 1, through the scheduler directly: two seeded requests
   (one prompt inside the window, one over twice the window, so the ring
   wraps), prefill then 16 decode steps with ``collect_logits``, a long
   filler prefilling behind them and neighbours live in other slots; every
   position's logits against the reference's full forward on the same
   weights (``references/<module>.py``: one causal forward, no cache, a mask
   for the window). Two controls have to come out NOT ok: the reference with
   its weight matrices at int8 (the precision below the configuration's),
   compared with itself; the PROGRAM with its state leaves zeroed between
   syncs (a slot that loses its state). Two more are reported and decide
   nothing: the PROGRAM with its state leaves, and with its ring and shared
   rows, rounded to int8 between syncs. They read what bf16 reads: a
   64-sublayer bf16 stack rounds by 0.04 a position, a dozen int8 roundings
   of a state or of rows that are averaged by 0.015 beside it (my chip runs,
   PR 32; PERF.md section 4);
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks and than the window, is sent twice: same
   tokens both times, both served cold (such a pool takes no prefix hit: the
   scheduler's bypass counter moved). This also warms the window's programs;
4. the load generator ramps; then the window opens.

A traced run profiles the LAST ``trace_window_s`` of the window and snapshots
the gateway's metrics before it stops the profiler
(``harness.measured_window``; every serving job since PR 38, this one since
PR 32, where the need showed first). This cell's chunk scans put over a million
device operations into five seconds of trace and ``stop_trace`` takes
37-44 s to write them (my chip runs, PR 32). Stopped five seconds into the
window it held this thread until long after the load generator had hung up
(the snapshot showed 62 disconnects), and the sink's 30 s histograms had
retired most or all of their observations by then: ``gateway_queue_wait_ms``
and the three ``sched_*`` quantiles dropped out of the line (the driver's
run of seed 1870952450).
"""

import importlib
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp

from chipbench import traffic
from chipbench.cells import HERE, CellError, build_model
from chipbench.harness import finish_trace, measured_window
from chipbench.jobs.serve import _metrics, _post, reduce_records
from chipbench.jobs.serve_ref import _collect, seeded_params


# the deviation of a Mamba layer's W_x (Delta's input, B and C), as the
# configuration's ``assumed.weights`` states it
X_PROJ_STD = 0.1


def sambay_params(model, seed, dtype):
    """``seeded_params``' tree (normal(0, 0.02) kernels, norm scales 1,
    biases 0) with what the layers publish another start for drawn by it,
    from (seed, leaf path): every ``A_log`` and ``dt_bias`` by the program's
    own initialisers (normal(0, 0.02) there would give every channel one
    rate of forgetting and hide state faults), ``D`` 1, the lambda vectors
    normal(0, 0.1); and each Mamba layer's ``W_x`` at deviation 0.1: at 0.02
    B and C are so small that the state's read-out ``h C`` is a twentieth of
    the skip term ``D x`` (0.016 against 0.32, one layer at the published
    sizes, float32) and a slot whose state is lost reads what a right one
    reads; at 0.1 it is 0.51 against 0.32."""
    from deepspeed_tpu.models import transformer
    root = jax.random.key(seed % (2**31 - 1))
    lam = lambda key, shape, dt: jax.random.normal(key, shape, dt) * dt.type(0.1)
    redrawn = {"['A_log']": transformer.mamba_a_log_init,
               "['dt_bias']": transformer.gdn_dt_bias_init,
               "['D']": lambda key, shape, dt: jnp.ones(shape, dt),
               "['lambda_q1']": lam, "['lambda_k1']": lam, "['lambda_q2']": lam,
               "['lambda_k2']": lam,
               "['x_proj']['kernel']": lambda key, shape, dt: (
                   jax.random.normal(key, shape, dt) * dt.type(X_PROJ_STD))}

    def redraw(path, leaf):
        name = jax.tree_util.keystr(path)
        for tail, init in redrawn.items():
            if name.endswith(tail):
                key = jax.random.fold_in(root, traffic.seed_stream(seed, name).getrandbits(31))
                return init(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(redraw, seeded_params(model, seed, dtype))


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns ``ref.compare``'s
    dict over both requests' positions, with the controls' verdicts under
    ``lower_precision`` (the reference with its weights at int8, against
    itself), ``lost_state_program`` (the PROGRAM reading a pool whose state
    leaves were zeroed after every sync), ``int8_state_program`` and
    ``int8_rows_program`` (the same with the state leaves / the ring and
    shared rows rounded to int8 after every sync)."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    tree = ref.from_tree(eng.params, cfg.layer_types, cfg.layer_windows)
    if ctx.workload.get("force_wrong"):
        tree["final_norm"] = tuple(x * 1.5 for x in tree["final_norm"])

    def against_reference(prompts, handles, lower=False):
        got, want, low = [], [], []
        for pr, h in zip(prompts, handles):
            toks = [int(t) for t in h.result()]
            got.append(h.result_logits())  # (17, V): the row that chose each token
            ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
            with eng.mesh:
                want.append(ref.forward(tree, ids, hp, first=len(pr) - 1)[0])
                if lower:
                    low.append(ref.forward(tree, ids, hp, levels=127.0, first=len(pr) - 1)[0])
        want = jnp.concatenate(want)
        res = ref.compare(jnp.concatenate(got), want, tol=tol)
        if lower:
            res["lower_precision"] = ref.compare(jnp.concatenate(low), want, tol=tol)
        return res

    def collect(after_step=None):
        prompts = draw()
        return prompts, _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size,
                                 after_step)

    res = against_reference(*collect(), lower=True)
    # the program itself over a pool that is altered between syncs: the
    # leaves of some kinds zeroed, or rounded to int8 (the precision below
    # the pool's); fresh prompts of the same lengths
    kinds = sched.cache.leaf_kinds

    def altering(change, *altered):
        def fn(pool):
            leaves, treedef = jax.tree_util.tree_flatten(pool)
            return jax.tree_util.tree_unflatten(treedef, [
                change(leaf) if k in altered else leaf for leaf, k in zip(leaves, kinds)])
        alter = jax.jit(fn, donate_argnums=0)

        def after_step():
            sched.cache.pool = alter(sched.cache.pool)
        return after_step

    res["lost_state_program"] = against_reference(*collect(altering(jnp.zeros_like, "state")))
    res["int8_state_program"] = against_reference(*collect(altering(ref.int8_rows, "state")))
    res["int8_rows_program"] = against_reference(*collect(altering(ref.int8_rows, "rows", "ring")))
    return res


def run(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.serving import Gateway

    p = ctx.workload["serve"]
    tr = p["traffic"]
    ref = importlib.import_module("chipbench.references." + ctx.config["reference"]["module"])
    comm.initialize_mesh(devices=list(ctx.devices))
    dtype = jnp.dtype(p["dtype"])
    try:
        model = build_model(ctx.config, dtype=dtype)
        cfg = model.cfg
        params = sambay_params(model, ctx.seed, dtype)
    except (ValueError, TypeError, AttributeError) as e:
        raise CellError(f"the program cannot build configuration {ctx.config['name']}: {e}")

    engine_cfg = {"dtype": p["dtype"], "kernel_inject": bool(p["kernel_inject"]),
                  "max_out_tokens": p["max_len"],
                  "continuous_batching": {"enabled": True, "num_slots": p["num_slots"],
                                          "steps_per_sync": p["steps_per_sync"],
                                          "prefill_chunk": p["prefill_chunk"]}}
    if ctx.trace:
        engine_cfg["telemetry"] = {"enabled": True, "hist_window_s": ctx.seconds,
                                   "output_path": os.path.join(ctx.scratch, "telemetry")}
    eng = deepspeed_tpu.init_inference(model, config=engine_cfg, params=params)
    del params
    gw = Gateway(eng, port=0, max_queue_depth=max(64, 2 * tr["clients"]),
                 request_timeout_s=900)
    sched = gw.scheduler
    ctx.setup_part("engine_build")

    compared = _logits_check(ctx, eng, sched, cfg, ref)
    ctx.setup_part("reference_and_collect_programs")

    gw.start_background()
    port = gw.port
    child = None
    try:
        rng = traffic.seed_stream(ctx.seed, "warm")
        primer_prompt = [rng.randrange(cfg.vocab_size) for _ in range(32)]
        repeat_prompt = [rng.randrange(cfg.vocab_size) for _ in range(p["repeat_prompt_len"])]
        primer_out = []
        primer = threading.Thread(target=lambda: primer_out.extend(_post(
            port, {"prompt": primer_prompt, "max_tokens": p["primer_tokens"]}, timeout=900)))
        primer.start()
        while _metrics(port)["scheduler"]["active_slots"] < 1:
            if not primer.is_alive():
                raise CellError("the primer request ended before it held a slot")
            time.sleep(0.05)
        bypass_before = sched.prefix_cache_state_bypass
        first = _post(port, {"prompt": repeat_prompt, "max_tokens": 24}, timeout=900)
        again = _post(port, {"prompt": repeat_prompt, "max_tokens": 24}, timeout=900)
        bypassed = sched.prefix_cache_state_bypass - bypass_before
        ctx.setup_part("warm_programs")

        spec = {"port": port, "seed": ctx.seed, "vocab_size": cfg.vocab_size, "traffic": tr,
                "ramp_timeout_s": p["ramp_timeout_s"],
                "first_token_wait_s": p["first_token_wait_s"]}
        child = subprocess.Popen([sys.executable, "-m", "chipbench.loadgen"],
                                 cwd=os.path.dirname(HERE), stdin=subprocess.PIPE,
                                 stdout=subprocess.PIPE, text=True)
        child.stdin.write(json.dumps(spec) + "\n")
        child.stdin.flush()
        ramped = json.loads(child.stdout.readline() or '{"event": "died"}')
        if ramped["event"] != "ramped":
            raise CellError(f"the load generator did not ramp: {ramped}")
        primer.join(timeout=600)
        if primer.is_alive() or len(primer_out) != p["primer_tokens"]:
            raise CellError(f"the primer request did not finish: {len(primer_out)} tokens")
        ctx.setup_part("ramp")

        programs_before = ctx.compiles["programs"]
        before = _metrics(port)
        # column forwards, and the K/V positions the attention layers had to
        # read, at the trace's start and stop (the scheduler's own counters)
        ROWS = ("serving/attn_rows_window", "serving/attn_rows_shared")
        counted = lambda: (sched.steps_run, ) + tuple(
            sched.telemetry.counter_total(name) or 0 for name in ROWS)
        ctx.mark_window_start()
        t0 = time.monotonic() + 0.05
        t1 = t0 + ctx.seconds
        child.stdin.write(json.dumps({"window": [t0, t1]}) + "\n")
        child.stdin.flush()
        occupancy, live_rows = [], []

        def sample():
            occupancy.append(100.0 * sched.cache.occupancy())
            live_rows.append(sched.cache.live_tokens())

        traced, after, steps_at, after_s, host = measured_window(
            ctx, t0, t1, p["trace_window_s"], sample, snapshot=lambda: _metrics(port),
            counted=counted)
        late_compiles = ctx.compiles["programs"] - programs_before
        out = json.loads(child.stdout.readline() or '{"event": "died"}')
        if out["event"] != "records":
            raise CellError(f"the load generator returned no records: {out}")
        child.wait(timeout=60)
        t_records = time.monotonic()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        drained = gw.close(timeout=120)
        eng.telemetry.close()

    res = reduce_records(out["records"], t0, t1, out["t_stop"], p["tpot_min_tokens"],
                         p.get("stall_gap_ms"))
    sched_m = after["scheduler"]
    want = ctx.config["reference"]
    brief = lambda r: {k: r[k] for k in ("ok", "error", "min_error", "median_error", "errors")}
    checks = {
        "logits_match_reference": compared["ok"],
        "lower_precision_fails": not compared["lower_precision"]["ok"],
        "lost_state_program_fails": not compared["lost_state_program"]["ok"],
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
        "kv_bytes_per_token": sched_m["kv_bytes_per_token"] == want["kv_bytes_per_token"],
        "state_bytes_per_slot": sched_m["state_bytes_per_slot"] == want["state_bytes_per_slot"],
        "window_bytes_per_slot": sched_m["window_bytes_per_slot"] == want["window_bytes_per_slot"],
        "no_compile_in_window": late_compiles == 0,
        "no_deadline_expired": after["gateway"]["deadline_expired"]
        == before["gateway"]["deadline_expired"],
    }
    obs = {
        "correct": all(checks.values()), "checks": checks,
        "attempted": res["attempted"], "failed": res["failed"],
        "end_to_end": {k: res[k] for k in ("serve_tokens_per_s", "tpot_p50_ms")},
        "values": {"client_ttft_p90_ms": res["ttft_p90_ms"],
                   "client_tpot_p50_ms": res["tpot_p50_ms"],
                   "client_tpot_p90_ms": res["tpot_p90_ms"],
                   **({name: stop - start for name, start, stop in zip(
                       ("column_forwards_traced", "attn_rows_window_traced",
                        "attn_rows_shared_traced"), steps_at["start"], steps_at["stop"])}
                      if steps_at else {})},
        "series": {"slot_occupancy_pct": occupancy, "live_kv_rows": live_rows},
        "telemetry": after.get("telemetry"),
        "model_cfg": cfg, "itemsize": dtype.itemsize, "num_slots": sched_m["num_slots"],
        "info": dict(res["info"], tpot_p90_ms=res["tpot_p90_ms"], logits_error=compared["error"],
                     median_error=compared["median_error"], logits_errors=compared["errors"],
                     rows_compared=compared["rows"],
                     lower_precision=brief(compared["lower_precision"]),
                     lost_state_program=brief(compared["lost_state_program"]),
                     int8_state_program=brief(compared["int8_state_program"]),
                     int8_rows_program=brief(compared["int8_rows_program"]),
                     tol=ref.TOL[p["dtype"]], late_compiles=late_compiles, drained=bool(drained),
                     host=host, generator=out.get("generator"),
                     after_window_s=dict(after_s, first_tokens_and_records=t_records - t1,
                                         drain=time.monotonic() - t_records),
                     compiled_programs=sched_m["compiled_programs"],
                     num_slots=sched_m["num_slots"], max_len=sched.max_len,
                     kv_bytes_per_token=sched_m["kv_bytes_per_token"],
                     state_bytes_per_slot=sched_m["state_bytes_per_slot"],
                     window_bytes_per_slot=sched_m["window_bytes_per_slot"],
                     prefix_cache_state_bypass=sched.prefix_cache_state_bypass,
                     state_slots_reset=sched.state_slots_reset,
                     fused_decode_reasons=sched_m.get("fused_decode_reasons"),
                     kv_commit_programs=sched_m.get("kv_commit_programs"),
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    finish_trace(ctx, traced, obs)
    return obs
