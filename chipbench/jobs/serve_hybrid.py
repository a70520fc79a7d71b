"""The ``serve_hybrid`` job: ``jobs/serve_ref.py``'s flow for a configuration
whose slots hold recurrent state beside K/V rows (linear-attention layers
between full-attention ones), served in its float dtype through the
per-projection path: ``init_inference`` + ``Gateway(engine, port=0)`` +
``start_background()`` in this process, load from a child over localhost
HTTP with SSE. The end-to-end arithmetic is ``jobs/serve.py``'s
(``reduce_records``), the traffic ``traffic.py``'s, the load ``loadgen.py``'s.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (``serve_ref.seeded_params``: normal(0, 0.02)
   kernels, norm scales 1), with each linear-attention layer's ``A_log`` and
   ``dt_bias`` drawn from the seed by the layer's published start instead
   (the program's own initialisers), so that heads forget at different
   rates and a state carries hundreds of positions, and the embedding and
   the blocks' output norms scaled so that the post-normed forward does not
   amplify a rounding (``hybrid_params``);
2. ``correct``, part 1, through the scheduler directly: two seeded requests
   (one prompt inside a prefill chunk, one over three with a partial last),
   prefill then 16 decode steps with ``collect_logits``, a long filler
   prefilling behind them and neighbours live in other slots; every
   position's logits against the reference's full forward on the same
   weights (``references/<module>.py``: token by token, no cache). Two
   controls at the precision below the configuration's have to come out NOT
   ok: the reference with its weight matrices at int8, compared with itself;
   the PROGRAM with its state leaves rounded to int8 between syncs. A third
   is reported and decides nothing: the PROGRAM with its K/V rows rounded to
   int8 between syncs reads what bf16 itself reads (one layer in four holds
   rows, and attention over a few hundred of them averages a row's rounding
   away: 0.0224-0.0229 against 0.0219-0.0223, my chip runs, PR 30);
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks, is sent twice: same tokens both times,
   both served cold (a pool with state takes no prefix hit: the scheduler's
   bypass counter moved). This also warms the window's programs;
4. the load generator ramps; then the window opens.
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


# the scale of a block's two output norms and the embedding's deviation, as
# the configuration's ``assumed.weights`` states them
OUTPUT_NORM_SCALE = 0.3
EMBEDDING_STD = 1.0


def hybrid_params(model, seed, dtype):
    """``seeded_params``' tree (normal(0, 0.02) kernels, norm scales 1) with
    what a post-normed hybrid needs drawn otherwise, from (seed, leaf path):

    - every ``A_log`` and ``dt_bias`` by the program's own initialisers (the
      layer's published start): normal(0, 0.02) there would give every head
      a half-life of one token and hide state faults;
    - the embedding at unit deviation and each block's two OUTPUT norms
      (``attn_norm``, ``mlp_norm``) at scale 0.3: a residual stream of RMS 1
      to 2 into which each sublayer writes a third. At scale 1 over a 0.02
      embedding every sublayer rewrites the stream, the forward amplifies a
      rounding twenty-fold (the first chip run read 0.18 against the
      float32 reference where bf16 rounds by 0.004) and no limit can tell
      int8 state or rows from bf16."""
    from deepspeed_tpu.models import transformer
    root = jax.random.key(seed % (2**31 - 1))
    redrawn = {
        "['A_log']": transformer.gdn_a_log_init,
        "['dt_bias']": transformer.gdn_dt_bias_init,
        "['embed']['embedding']": lambda key, shape, dt: (
            jax.random.normal(key, shape, dt) * dt.type(EMBEDDING_STD)),
        "['attn_norm']['scale']": lambda key, shape, dt: jnp.full(shape, OUTPUT_NORM_SCALE, dt),
        "['mlp_norm']['scale']": lambda key, shape, dt: jnp.full(shape, OUTPUT_NORM_SCALE, dt),
    }

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
    itself), ``int8_state_program`` and ``int8_rows_program`` (the PROGRAM
    reading a pool whose state leaves / K/V rows were rounded to int8 after
    every sync)."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    tree = ref.from_tree(eng.params, cfg.layer_types)
    if ctx.workload.get("force_wrong"):
        tree["final_norm"] = tree["final_norm"] * 1.5

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
    # the program itself at the precision below its pool's: the leaves of one
    # kind rounded to int8 between syncs, fresh prompts of the same lengths
    kinds = sched.cache.leaf_kinds

    def rounding(kind):
        def fn(pool):
            leaves, treedef = jax.tree_util.tree_flatten(pool)
            return jax.tree_util.tree_unflatten(treedef, [
                ref.int8_rows(leaf) if k == kind else leaf for leaf, k in zip(leaves, kinds)])
        to_int8 = jax.jit(fn, donate_argnums=0)

        def after_step():
            sched.cache.pool = to_int8(sched.cache.pool)
        return after_step

    res["int8_state_program"] = against_reference(*collect(rounding("state")))
    res["int8_rows_program"] = against_reference(*collect(rounding("rows")))
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
        params = hybrid_params(model, ctx.seed, dtype)
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
        ctx.mark_window_start()
        t0 = time.monotonic() + 0.05
        t1 = t0 + ctx.seconds
        child.stdin.write(json.dumps({"window": [t0, t1]}) + "\n")
        child.stdin.flush()
        occupancy, live_rows = [], []

        def sample():
            occupancy.append(100.0 * sched.cache.occupancy())
            live_rows.append(sched.cache.live_tokens())

        # steps_at: column forwards at the traced part's start and stop
        traced, after, steps_at, after_s, host = measured_window(
            ctx, t0, t1, p["trace_window_s"], sample, snapshot=lambda: _metrics(port),
            counted=lambda: sched.steps_run)
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
        "int8_state_program_fails": not compared["int8_state_program"]["ok"],
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
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
                   "column_forwards_traced": (steps_at["stop"] - steps_at["start"]
                                              if steps_at else None)},
        "series": {"slot_occupancy_pct": occupancy, "live_kv_rows": live_rows},
        "telemetry": after.get("telemetry"),
        "model_cfg": cfg, "itemsize": dtype.itemsize, "num_slots": sched_m["num_slots"],
        "info": dict(res["info"], tpot_p90_ms=res["tpot_p90_ms"], logits_error=compared["error"],
                     median_error=compared["median_error"], logits_errors=compared["errors"],
                     rows_compared=compared["rows"],
                     lower_precision=brief(compared["lower_precision"]),
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
