"""The ``serve_ling_hybrid`` job: ``jobs/serve_lfm2_moe.py``'s flow for a
configuration of Kimi-delta linear-attention layers (a decay a key channel)
beside latent attention with a head-wise gate, under gated experts chosen
inside a few groups with a shared expert, a share of the experts held
(inclusionAI Ling-3.0-flash), served in its float dtype through the
per-projection path: ``init_inference`` + ``Gateway(engine, port=0)`` +
``start_background()`` in this process, load from a child over localhost HTTP
with SSE. The end-to-end arithmetic is ``jobs/serve.py``'s
(``reduce_records``), the traffic ``traffic.py``'s, the load ``loadgen.py``'s.
None of the seven serving jobs takes the configuration as data:
``serve_hybrid`` draws a post-normed stack's weights and has no experts to
follow, ``serve_lfm2_moe`` drops carried rows that this pool's state is not,
and neither runs this model's controls.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (:func:`ling_params`);
2. ``correct``, part 1, through the scheduler directly: two seeded requests
   (one prompt inside a chunk, one over three chunks with a partial last),
   prefill then 16 decode steps with ``collect_logits``, a long filler
   prefilling behind them and neighbours live in other slots; every
   position's logits against the reference's full forward on the same
   weights (``references/<module>.py``: one causal forward, no cache, the
   recurrence a token at a time), the reference following the groups and the
   experts the program chose where they are a near tie
   (``handle.result_choice()``). Four controls have to come out NOT ok. Three
   are the reference against itself at the check's own prompts: with its
   weight matrices at int8 (the precision below the configuration's); with
   every channel's decay replaced by its head's mean (a gated delta rule, not
   Kimi delta attention); with the router's group limit off. One is the
   PROGRAM, fresh prompts of the same lengths, with the pool's state leaves
   zeroed after every sync, against the reference;
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks, is sent twice: same tokens both times,
   both served cold (such a pool takes no prefix hit: the scheduler's bypass
   counter moved). This also warms the window's programs;
4. the load generator ramps (every client has had a first token) and the
   backlog the ramp left on the one prefill lane drains (the gateway's and the
   scheduler's queues have been empty); then the window opens.

A traced run profiles the LAST ``trace_window_s`` of the window and reads,
where the trace starts and where it stops, the column forwards the scheduler
ran (``column_forwards_traced``) and the program's counters of required
expert work (``harness.measured_window``).
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
from chipbench.jobs.serve_ref import _collect, seeded_params

# the program's counters read where the trace starts and stops, under
# ``values`` as ``<name>_traced`` (``serving/<name>``)
TRACED = ("moe_experts_touched", "moe_pairs_here", "moe_layer_calls")


def ling_params(model, seed, dtype):
    """``seeded_params``' tree (normal(0, 0.02) kernels, embedding, router and
    selection bias; norm scales 1) with, from (seed, leaf path): ``A_log`` and
    ``dt_bias`` by the program's own initialisers (normal(0, 0.02) there would
    give every head and channel one rate of forgetting and hide state
    faults: with A ~ U(0, 16] and the bounded gate the channels forget at
    rates from none to e^-5 a token); the convolution's taps uniform in (-1,
    1) (``serve_nemotron_h.nemotron_params``' argument: at 0.02 the state's
    read-out is nothing beside the residual stream); and every sublayer's LAST
    matrix (both mixers' ``W_o``, a dense FFN's, the routed experts' and the
    shared expert's down-projection) CENTRED, its sum over the contraction
    zero, so that positions do not collapse onto one direction and route
    alike: all 128 held experts are touched."""
    import jax
    from deepspeed_tpu.models import transformer
    root = jax.random.key(seed % (2**31 - 1))
    centred = jax.jit(lambda leaf, axis: (leaf - jnp.mean(leaf.astype(jnp.float32), axis=axis,
                                                          keepdims=True)).astype(leaf.dtype),
                      static_argnums=1, donate_argnums=0)
    redrawn = {"['A_log']": transformer.gdn_a_log_init,
               "['dt_bias']": transformer.gdn_dt_bias_init,
               "['conv']": lambda key, shape, dt: jax.random.uniform(key, shape, dt, -1.0, 1.0)}

    def redraw(path, leaf):
        name = jax.tree_util.keystr(path)
        for tail, init in redrawn.items():
            if name.endswith(tail):
                key = jax.random.fold_in(root, traffic.seed_stream(seed, name).getrandbits(31))
                return init(key, leaf.shape, leaf.dtype)
        if name.endswith("['experts']['down_proj']"):
            return centred(leaf, 1)
        if name.endswith("['down_proj']['kernel']"):
            return centred(leaf, 0)
        if name.endswith("['o_proj']['kernel']"):  # (heads, head size, hidden)
            return centred(leaf, (0, 1))
        return leaf

    return jax.tree_util.tree_map_with_path(redraw, seeded_params(model, seed, dtype))


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns ``ref.compare``'s
    dict over both requests' positions, with the controls' verdicts under
    ``lower_precision``, ``head_decay``, ``no_group_limit`` (the reference
    against itself) and ``zeroed_state_program`` (the PROGRAM with its state
    leaves zeroed after every sync, against the reference)."""
    import jax
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    tree = ref.from_tree(eng.params, cfg.layer_types)
    if ctx.workload.get("force_wrong"):
        tree["final_norm"] = tree["final_norm"] * 1.5
    controls = {"lower_precision": dict(levels=127.0), "head_decay": dict(head_decay=True),
                "no_group_limit": dict(group_limit=False)}

    def against_reference(prompts, handles, with_controls=False):
        got, want, followed, refused, reach, beyond = [], [], [], [], 0.0, 0.0
        other = {name: [] for name in controls} if with_controls else {}
        for pr, h in zip(prompts, handles):
            toks = [int(t) for t in h.result()]
            got.append(h.result_logits())  # (17, V): the row that chose each token
            ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
            first = len(pr) - 1
            choice = h.result_choice()[:, None, :ids.shape[1]]  # (expert layers, 1, T, k)
            with eng.mesh:
                logits, routing = ref.forward(tree, ids, hp, first=first, choice=choice)
                for name, kw in other.items():
                    # (the router without its group limit chooses for itself)
                    follow = None if name == "no_group_limit" else choice
                    other[name].append(ref.forward(tree, ids, hp, first=first, choice=follow,
                                                   **controls[name])[0][0])
            want.append(logits[0])
            followed.append(routing["followed"].reshape(-1))
            refused.append(routing["refused"].reshape(-1))
            farthest = lambda taken: float(jnp.max(jnp.where(routing[taken], routing["reach"],
                                                             0.0), initial=0.0))
            reach, beyond = max(reach, farthest("followed")), max(beyond, farthest("refused"))
        want = jnp.concatenate(want)
        res = dict(ref.compare(jnp.concatenate(got), want, jnp.concatenate(followed),
                               jnp.concatenate(refused), tol=tol), routing_reach_max=reach,
                   routing_refused_reach_max=beyond)
        for name, rows in other.items():
            res[name] = ref.compare(jnp.concatenate(rows), want, tol=tol)
        return res

    def collect(after_step=None):
        prompts = draw()
        return prompts, _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size,
                                 after_step)

    res = against_reference(*collect(), with_controls=True)
    # the program itself with what a slot carries from sync to sync lost: the
    # state leaves (the recurrent state and the window) zeroed between syncs
    kinds = sched.cache.leaf_kinds

    def zero_state(pool):
        leaves, treedef = jax.tree_util.tree_flatten(pool)
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.zeros_like(leaf) if k == "state" else leaf for leaf, k in zip(leaves, kinds)])
    zeroed = jax.jit(zero_state, donate_argnums=0)

    def after_step():
        sched.cache.pool = zeroed(sched.cache.pool)

    res["zeroed_state_program"] = against_reference(*collect(after_step))
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
        params = ling_params(model, ctx.seed, dtype)
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
        counted = lambda: (sched.steps_run, ) + tuple(
            sched.telemetry.counter_total("serving/" + name) or 0 for name in TRACED)
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
    gdn_programs = dict(sched.gdn_step_programs)
    want = ctx.config["reference"]
    brief = lambda r: {k: r[k] for k in ("ok", "error", "min_error", "median_error", "errors",
                                          "routing_margin_rows", "routing_refused_rows",
                                          "routing_rows")}
    checks = {
        "logits_match_reference": compared["ok"],
        "lower_precision_fails": not compared["lower_precision"]["ok"],
        "head_decay_fails": not compared["head_decay"]["ok"],
        "no_group_limit_fails": not compared["no_group_limit"]["ok"],
        "zeroed_state_program_fails": not compared["zeroed_state_program"]["ok"],
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
        # the decode column's state update in place on the chip (a rehearsal's
        # heads do not tile: the definition serves them)
        "state_update_in_place": ctx.rehearsal or (gdn_programs.get("xla", 1) == 0
                                                   < gdn_programs.get("kernel", 0)),
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
                       ("column_forwards", ) + TRACED, counted_at["start"], counted_at["stop"])}
                      if counted_at else {})},
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
                     routing_refused_reach_max=compared["routing_refused_reach_max"],
                     lower_precision=brief(compared["lower_precision"]),
                     head_decay=brief(compared["head_decay"]),
                     no_group_limit=brief(compared["no_group_limit"]),
                     zeroed_state_program=brief(compared["zeroed_state_program"]),
                     tol=ref.TOL[p["dtype"]], routing_margin=ref.ROUTING_MARGIN,
                     group_routing_margin=ref.GROUP_ROUTING_MARGIN,
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
                     moe_dispatch_programs=dispatch, gdn_step_programs=gdn_programs,
                     fused_decode_reasons=sched_m.get("fused_decode_reasons"),
                     kv_commit_programs=sched_m.get("kv_commit_programs"),
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    # (a traced run's counts of forwards and of required expert work, for whoever reads the note)
    obs["info"]["traced_counters"] = {k: v for k, v in obs["values"].items()
                                      if k.endswith("_traced")}
    finish_trace(ctx, traced, obs)
    return obs
