"""The ``serve_exaone_moe`` job: ``jobs/serve_nemotron_h.py``'s flow for a
configuration whose decode steps VERIFY AND DRAFT on the device
(K-EXAONE-236B-A23B: windowed layers' rings beside a full layer's rows, gated
experts under a sigmoid router behind a leading dense layer, a
multi-token-prediction module behind the stack), served in its float dtype
through the per-projection path with ``spec_draft: "module"``:
``init_inference`` + ``Gateway(engine, port=0)`` + ``start_background()`` in
this process, load from a child over localhost HTTP with SSE. The end-to-end
arithmetic is ``jobs/serve.py``'s (``reduce_records``), the traffic
``traffic.py``'s, the load ``loadgen.py``'s. None of the five serving jobs
takes the configuration as data: ``serve_nemotron_h`` redraws Mamba-2's
leaves, hands its reference layer kinds, compares no draft logits and has no
roll-back to switch off.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (``serve_ref.seeded_params``: normal(0, 0.02)
   kernels, embedding, head, router and selection bias, norm scales 1), the
   module's beside the stack's. Seeded random weights cannot agree with their
   own drafter: acceptance reads about 1 / vocabulary, so every step of the
   window is a verify of two columns, a commit of one and a roll-back;
2. ``correct``, part 1, through the scheduler directly and THROUGH THE VERIFY
   PATH: two seeded requests (one prompt inside a chunk, one over three chunks
   with a partial last, both past the window so that the rings wrap), prefill
   then 16 tokens with ``collect_logits``, a long filler prefilling behind
   them and neighbours live in other slots; the stack's logits of every
   collected position AND the module's draft logits beside them against the
   reference's full forward on the same weights (``references/<module>.py``),
   the reference following the experts the program chose where they are a
   near tie (``handle.result_choice()``, the module's expert layer last).
   Tokens are not compared: with random weights the largest logit flips on
   bf16 rounding between a one-column and a two-column program (the CPU
   tests hold stream equality in float32). Two controls have to come out NOT
   ok: the reference with its weight matrices at int8 (the precision below the
   configuration's), compared with itself; the PROGRAM with roll-back off
   (every draft kept whatever the stack sampled: void rows stay visible);
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks, is sent twice: same tokens both times,
   both served cold (the scheduler's bypass counter moved). This also warms
   the window's programs and both widths of the carried-state merge;
4. the load generator ramps (every client has had a first token) and the
   backlog the ramp left on the one prefill lane drains; then the window
   opens.

A traced run profiles the LAST ``trace_window_s`` of the window and reads the
program's counters of expert work where the trace starts and where it stops
(``harness.measured_window``).
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

# the program's counters read where the trace starts and stops, under
# ``values`` as ``<name>_traced`` (``serving/<name>``)
TRACED = ("moe_experts_touched", "moe_pairs_here", "moe_layer_calls")


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns ``ref.compare``'s
    dict over both requests' rows (the stack's logits, then the module's draft
    logits), with the controls' verdicts under ``lower_precision`` (the
    reference with its weights at int8, against itself) and
    ``no_rollback_program`` (the PROGRAM keeping every void column)."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    windows = [cfg.layer_window(i) for i in range(cfg.num_layers)]
    if ctx.workload.get("force_wrong"):
        windows = [0] * cfg.num_layers  # a reference that sees every key and rotates none
    tree = ref.from_tree(eng.params, windows)

    def against_reference(prompts, handles, lower=False):
        got, want, low, followed, refused, reach = [], [], [], [], [], 0.0
        for pr, h in zip(prompts, handles):
            toks = [int(t) for t in h.result()]
            # (17, V) twice: the row that chose each token, the draft behind it
            got += [h.result_logits(), h.result_draft_logits()]
            ids = jnp.asarray([pr + toks], jnp.int32)
            choice = h.result_choice()[:, None]  # (expert layers + 1, 1, T', k)
            first = len(pr) - 1
            with eng.mesh:
                logits, drafts, routing = ref.forward(tree, ids, hp, first=first, choice=choice)
                if lower:
                    low += [x[0] for x in ref.forward(tree, ids, hp, levels=127.0, first=first,
                                                      choice=choice)[:2]]
            want += [logits[0], drafts[0]]
            followed.append(routing["followed"].reshape(-1))
            refused.append(routing["refused"].reshape(-1))
            reach = max(reach, float(jnp.max(routing["reach"], initial=0.0)))
        want = jnp.concatenate(want)
        res = dict(ref.compare(jnp.concatenate(got), want, jnp.concatenate(followed),
                               jnp.concatenate(refused), tol=tol), routing_reach_max=reach)
        if lower:
            res["lower_precision"] = ref.compare(jnp.concatenate(low), want, tol=tol)
        return res

    def collect():
        prompts = draw()
        return prompts, _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size)

    res = against_reference(*collect(), lower=True)
    # the program itself with roll-back off: every draft committed whatever the
    # stack sampled, so the void column's rows stay in rings and row caches
    sched._draft_keeps_void = True
    try:
        res["no_rollback_program"] = against_reference(*collect())
    finally:
        sched._draft_keeps_void = False
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
        params = seeded_params(model, ctx.seed, dtype)
    except (ValueError, TypeError, AttributeError, ImportError) as e:
        raise CellError(f"the program cannot build configuration {ctx.config['name']}: {e}")

    engine_cfg = {"dtype": p["dtype"], "kernel_inject": bool(p["kernel_inject"]),
                  "max_out_tokens": p["max_len"],
                  "continuous_batching": {"enabled": True, "num_slots": p["num_slots"],
                                          "steps_per_sync": p["steps_per_sync"],
                                          "prefill_chunk": p["prefill_chunk"],
                                          "spec_tokens": p["spec_tokens"],
                                          "spec_draft": p["spec_draft"]}}
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
        # the ramp leaves a backlog on the one prefill lane (the clients start
        # together and every prompt takes a sync): the window measures the
        # loop once the queues have been empty; a lane that never empties them
        # is saturated (read off the objects, as jobs/serve_nemotron_h.py does)
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
        "no_rollback_program_fails": not compared["no_rollback_program"]["ok"],
        "drafts_verified_on_device": sched.spec_drafted > 0 and sched.drafter is None,
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
        "sparse_expert_dispatch": dispatch.get("dense", 1) == 0 and dispatch.get("sparse", 0) > 0,
        "kv_bytes_per_token": sched_m["kv_bytes_per_token"] == want["kv_bytes_per_token"],
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
                     no_rollback_program=dict(
                         brief(compared["no_rollback_program"]),
                         routing_reach_max=compared["no_rollback_program"]["routing_reach_max"]),
                     tol=ref.TOL[p["dtype"]], routing_margin=ref.ROUTING_MARGIN,
                     max_followed_share=ref.MAX_FOLLOWED_SHARE,
                     late_compiles=late_compiles, drained=bool(drained),
                     host=host, generator=out.get("generator"),
                     after_window_s=dict(after_s, first_tokens_and_records=t_records - t1,
                                         drain=time.monotonic() - t_records),
                     compiled_programs=sched_m["compiled_programs"],
                     num_slots=sched_m["num_slots"], max_len=sched.max_len,
                     kv_bytes_per_token=sched_m["kv_bytes_per_token"],
                     window_bytes_per_slot=sched_m["window_bytes_per_slot"],
                     spec={k: getattr(sched, k) for k in (
                         "spec_steps", "spec_drafted", "spec_accepted", "spec_rows_void",
                         "syncs_ahead", "syncs_serial", "ahead_rows_discarded")},
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
