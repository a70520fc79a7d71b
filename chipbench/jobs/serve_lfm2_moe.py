"""The ``serve_lfm2_moe`` job: ``jobs/serve_nemotron_h.py``'s flow for a
configuration of gated short convolutions beside grouped-query attention
under gated experts with NO shared expert, every expert held (LiquidAI
LFM2-8B-A1B), served in its float dtype through the per-projection path:
``init_inference`` + ``Gateway(engine, port=0)`` + ``start_background()`` in
this process, load from a child over localhost HTTP with SSE. The end-to-end
arithmetic is ``jobs/serve.py``'s (``reduce_records``), the traffic
``traffic.py``'s, the load ``loadgen.py``'s. None of the six serving jobs
takes the configuration as data: ``serve_nemotron_h`` rounds a state to int8
by a head (this pool's state is a window of inputs with no heads), zeroes
the selection bias of a tree with a shared expert, and reads Mamba-2's
counters where the trace starts and stops, not the short convolution's nor
the layer calls; ``serve_exaone_moe`` compares a drafting module's logits.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (``serve_nemotron_h.nemotron_params``: normal(0,
   0.02) kernels, embedding, router and selection bias, norm scales 1; a
   convolution's taps uniform in (-1, 1), so that the carried rows weigh as
   much as the position's own input: at normal(0, 0.02) the operator would be
   a thousandth of the residual stream and a slot that lost its rows would
   read what a right one reads; every FFN's and every operator's last matrix
   centred, so that positions do not collapse onto one direction and route
   alike);
2. ``correct``, part 1, through the scheduler directly: two seeded requests
   (one prompt inside a chunk, one over three chunks with a partial last),
   prefill then 16 decode steps with ``collect_logits``, a long filler
   prefilling behind them and neighbours live in other slots; every
   position's logits against the reference's full forward on the same
   weights (``references/<module>.py``: one causal forward, no cache), the
   reference following the experts the program chose where they are a near
   tie (``handle.result_choice()``). Two controls have to come out NOT ok,
   both the reference against itself at the check's own prompts: with its
   weight matrices at int8 (the precision below the configuration's); run
   call by call (the prompt's chunks, then a token a call) with the carried
   rows DROPPED at every call boundary, so that a program that loses a
   slot's rows at a boundary could not pass;
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks, is sent twice: same tokens both times,
   both served cold (such a pool takes no prefix hit: the scheduler's bypass
   counter moved). This also warms the window's programs;
4. the load generator ramps (every client has had a first token) and the
   backlog the ramp left on the one prefill lane drains (the gateway's and the
   scheduler's queues have been empty); then the window opens.

A traced run profiles the LAST ``trace_window_s`` of the window and reads the
program's counters of required expert work where the trace
starts and where it stops (``harness.measured_window``).
"""

import importlib
import json
import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp

from chipbench import traffic
from chipbench.cells import HERE, CellError, build_model
from chipbench.harness import finish_trace, measured_window
from chipbench.jobs.serve import _metrics, _post, reduce_records
from chipbench.jobs.serve_nemotron_h import nemotron_params
from chipbench.jobs.serve_ref import _collect

# the program's counters read where the trace starts and stops, under
# ``values`` as ``<name>_traced`` (``serving/<name>``)
TRACED = ("moe_experts_touched", "moe_pairs_here", "moe_layer_calls")


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns ``ref.compare``'s
    dict over both requests' positions, with the controls' verdicts under
    ``lower_precision`` (the reference with its weights at int8, against
    itself) and ``dropped_carry`` (the reference that drops the carried rows
    at every call boundary, against itself)."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    prompts = [[rng.randrange(cfg.vocab_size) for _ in range(n)]
               for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    tree = ref.from_tree(eng.params, cfg.layer_types)
    handles = _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size)
    got, want, low, dropped, followed, refused, reach = [], [], [], [], [], [], 0.0
    for pr, h in zip(prompts, handles):
        toks = [int(t) for t in h.result()]
        got.append(h.result_logits())  # (17, V): the row that chose each token
        ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
        kw = dict(first=len(pr) - 1,
                  choice=h.result_choice()[:, None, :ids.shape[1]])  # (expert layers, 1, T, k)
        calls = ref.serving_calls(len(pr), ids.shape[1], p["prefill_chunk"])
        with eng.mesh:
            logits, routing = ref.forward(tree, ids, hp, **kw)
            low.append(ref.forward(tree, ids, hp, levels=127.0, **kw)[0][0])
            dropped.append(ref.forward(tree, ids, hp, call_starts=calls, **kw)[0][0])
        want.append(logits[0])
        followed.append(routing["followed"].reshape(-1))
        refused.append(routing["refused"].reshape(-1))
        reach = max(reach, float(jnp.max(routing["reach"], initial=0.0)))
    want, dropped = jnp.concatenate(want), jnp.concatenate(dropped)
    # the rehearsal's wrong twin: the program held to the reference that drops the rows
    res = dict(ref.compare(jnp.concatenate(got),
                           dropped if ctx.workload.get("force_wrong") else want,
                           jnp.concatenate(followed), jnp.concatenate(refused), tol=tol),
               routing_reach_max=reach)
    res["lower_precision"] = ref.compare(jnp.concatenate(low), want, tol=tol)
    res["dropped_carry"] = ref.compare(dropped, want, tol=tol)
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
        params = nemotron_params(model, ctx.seed, dtype)
    except (ValueError, TypeError, AttributeError, ImportError) as e:
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
        # the ramp leaves a backlog on the one prefill lane
        # (``jobs/serve_nemotron_h.py``): the window measures the loop once
        # the queues have been empty; a lane that never empties them is
        # saturated (read off the objects: a metrics request every poll holds
        # the gateway's loop)
        deadline = time.monotonic() + p["ramp_timeout_s"]
        while len(gw._fair) or len(sched.queue):
            if time.monotonic() > deadline:
                raise CellError(f"the ramp's backlog did not drain in {p['ramp_timeout_s']} s: "
                                f"{len(sched.queue)} requests wait for the prefill lane, "
                                f"which is saturated")
            time.sleep(0.25)
        ctx.setup_part("ramp")

        programs_before = ctx.compiles["programs"]
        before = _metrics(port)
        counted = lambda: tuple(sched.telemetry.counter_total("serving/" + name) or 0
                                for name in TRACED)
        ctx.mark_window_start()
        t0 = time.monotonic() + 0.05
        t1 = t0 + ctx.seconds
        child.stdin.write(json.dumps({"window": [t0, t1]}) + "\n")
        child.stdin.flush()
        occupancy, live_rows = [], []

        def sample():
            occupancy.append(100.0 * sched.cache.occupancy())
            live_rows.append(sched.cache.live_tokens())

        traced, after, counted_at, after_s, host = measured_window(
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
    dispatch = sched_m.get("moe_dispatch_programs") or {}
    want = ctx.config["reference"]
    brief = lambda r: {k: r[k] for k in ("ok", "error", "min_error", "median_error", "errors",
                                          "routing_margin_rows", "routing_refused_rows",
                                          "routing_rows")}
    checks = {
        "logits_match_reference": compared["ok"],
        "lower_precision_fails": not compared["lower_precision"]["ok"],
        "dropped_carry_fails": not compared["dropped_carry"]["ok"],
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
        "sparse_expert_dispatch": dispatch.get("dense", 1) == 0 and dispatch.get("sparse", 0) > 0,
        "kv_bytes_per_token": sched_m["kv_bytes_per_token"] == want["kv_bytes_per_token"],
        "state_bytes_per_slot": sched_m["state_bytes_per_slot"] == want["state_bytes_per_slot"],
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
                   **({name + "_traced": stop - start for name, start, stop in zip(
                       TRACED, counted_at["start"], counted_at["stop"])} if counted_at else {})},
        "series": {"slot_occupancy_pct": occupancy, "live_kv_rows": live_rows},
        "telemetry": after.get("telemetry"),
        "model_cfg": cfg, "itemsize": dtype.itemsize, "num_slots": sched_m["num_slots"],
        "info": dict(res["info"], tpot_p90_ms=res["tpot_p90_ms"], logits_error=compared["error"],
                     median_error=compared["median_error"], logits_errors=compared["errors"],
                     rows_compared=compared["rows"],
                     routing_margin_rows=compared["routing_margin_rows"],
                     routing_refused_rows=compared["routing_refused_rows"],
                     routing_rows=compared["routing_rows"],
                     routing_reach_max=compared["routing_reach_max"],
                     lower_precision=brief(compared["lower_precision"]),
                     dropped_carry=brief(compared["dropped_carry"]),
                     tol=ref.TOL[p["dtype"]], routing_margin=ref.ROUTING_MARGIN,
                     max_followed_share=ref.MAX_FOLLOWED_SHARE,
                     late_compiles=late_compiles, drained=bool(drained),
                     host=host, generator=out.get("generator"),
                     after_window_s=dict(after_s, first_tokens_and_records=t_records - t1,
                                         drain=time.monotonic() - t_records),
                     compiled_programs=sched_m["compiled_programs"],
                     num_slots=sched_m["num_slots"], max_len=sched.max_len,
                     kv_bytes_per_token=sched_m["kv_bytes_per_token"],
                     state_bytes_per_slot=sched_m["state_bytes_per_slot"],
                     prefix_cache_state_bypass=sched.prefix_cache_state_bypass,
                     state_slots_reset=sched.state_slots_reset,
                     moe_dispatch_programs=dispatch,
                     fused_decode_reasons=sched_m.get("fused_decode_reasons"),
                     kv_commit_programs=sched_m.get("kv_commit_programs"),
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    finish_trace(ctx, traced, obs)
    return obs
