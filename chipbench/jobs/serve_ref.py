"""The ``serve_ref`` job: ``jobs/serve.py``'s flow for a configuration that
brings its own reference (``configs/<config>.json``: ``reference.module``
names a file under ``chipbench/references/``) and is served in its float
dtype through the per-projection path: ``init_inference`` + ``Gateway(engine,
port=0)`` + ``start_background()`` in this process, load from a child over
localhost HTTP with SSE. The end-to-end arithmetic is ``jobs/serve.py``'s
(``reduce_records``), the traffic ``traffic.py``'s, the load ``loadgen.py``'s.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed``, made on the device leaf by leaf in the serving
   dtype (normal(0, 0.02) kernels, norm scales 1): a float32 tree of the
   chip's share does not fit beside it. ``init_inference`` takes them as
   they are;
2. ``correct``, part 1, through the scheduler directly: two seeded requests,
   prefill then 16 decode steps with ``collect_logits``, a long filler
   prefilling behind them (as ``jobs/serve.py`` does, so that only the
   (K, chunk) program's ``collect_logits`` variant compiles); their logits
   against the reference's full forward on the same weights, the reference
   following the experts the program chose where they are a near tie
   (``handle.result_choice()``), every position by the reference's own
   ``compare``. Three controls at the precision below the configuration's:
   the reference with its weights and latent rows rounded to int8, compared
   with itself by the same rule, has to come out as not ok
   (``lower_precision_fails``); the reference with its latent rows alone at
   int8 is reported; and the PROGRAM runs two more requests while every row
   of its pool is rounded to int8 between syncs, which has to come out as
   not ok too (``int8_pool_program_fails``);
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than a prefill chunk, is sent twice: same tokens both times, the
   second time through the radix copy of latent rows. This also warms the
   window's programs;
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
from chipbench.jobs.serve import COLLECT_STEPS, _metrics, _post, reduce_records


def seeded_params(model, seed, dtype):
    """The model's parameter tree, leaf by leaf on the device in ``dtype``:
    RMSNorm/LayerNorm scales 1, biases 0, everything else normal(0, 0.02)
    from (seed, leaf path)."""
    abstract = jax.eval_shape(model.init_params, jax.random.key(0))
    root = jax.random.key(seed % (2**31 - 1))
    normal = jax.jit(lambda key, shape: jax.random.normal(key, shape, dtype) * dtype.type(0.02),
                     static_argnums=1)

    def make(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return jnp.ones(leaf.shape, dtype)
        if name.endswith("['bias']"):
            return jnp.zeros(leaf.shape, dtype)
        key = jax.random.fold_in(root, traffic.seed_stream(seed, name).getrandbits(31))
        return normal(key, leaf.shape)

    return jax.tree_util.tree_map_with_path(make, abstract)


def _collect(sched, prompts, filler_len, rng, vocab, after_step=None):
    """The prompts through the scheduler, prefill then COLLECT_STEPS decode
    steps with ``collect_logits``, a long filler prefilling behind them (as
    ``jobs/serve.py`` does, so that only the (K, chunk) program's collecting
    variant compiles). ``after_step`` runs between syncs."""
    handles = [sched.submit(pr, max_new_tokens=COLLECT_STEPS, collect_logits=True)
               for pr in prompts]
    filler = sched.submit([rng.randrange(vocab) for _ in range(filler_len)], max_new_tokens=4)
    while not all(h.done for h in handles):
        sched.step()
        if after_step is not None:
            after_step()
    filler.cancel()
    sched.drain()
    return handles


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns
    ``ref.compare``'s dict over both requests' positions, with the verdicts
    of the lower-precision controls under ``lower_precision`` (the reference
    with weights and latent rows at int8), ``lower_precision_pool`` (latent
    rows alone, reported) and ``int8_pool_program`` (the PROGRAM reading a
    pool whose rows were rounded to int8 after every sync)."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config, cfg)
    tree = ref.from_tree(eng.params, cfg.num_layers)
    if ctx.workload.get("force_wrong"):
        tree["final_norm"] = tree["final_norm"] * 1.5

    def against_reference(prompts, handles, controls):
        """The program's logits against the reference that follows its
        routing; ``controls``: name -> ``forward`` keywords of a
        lower-precision reference, compared with the reference itself."""
        got, want, followed, refused, reach = [], [], [], [], 0.0
        lower = {name: [] for name in controls}
        for pr, h in zip(prompts, handles):
            toks = [int(t) for t in h.result()]
            got.append(h.result_logits())  # (17, V): the row that chose each token
            ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
            choice = h.result_choice()[:, None, :ids.shape[1]]  # (L, 1, T, k)
            with eng.mesh:
                logits, routing = ref.forward(tree, ids, hp, choice=choice)
                for name, kw in controls.items():
                    lower[name].append(
                        ref.forward(tree, ids, hp, choice=choice, **kw)[0][0, len(pr) - 1:])
            want.append(logits[0, len(pr) - 1:])
            followed.append(routing["followed"].reshape(-1))
            refused.append(routing["refused"].reshape(-1))
            reach = max(reach, float(jnp.max(routing["reach"])))
        got, want, followed, refused = (jnp.concatenate(x) for x in (got, want, followed, refused))
        res = dict(ref.compare(got, want, followed, refused, tol=tol), routing_reach_max=reach)
        for name in controls:
            res[name] = ref.compare(jnp.concatenate(lower[name]), want, tol=tol)
        return res

    prompts = draw()
    res = against_reference(prompts, _collect(sched, prompts, p["filler_prompt_len"], rng,
                                              cfg.vocab_size),
                            {"lower_precision": {"levels": 127.0},
                             "lower_precision_pool": {"levels": 0.0, "pool_levels": 127.0}})
    # the program itself at the precision below its pool's: every row the
    # pool holds rounded to int8 between syncs, fresh prompts of the same lengths
    to_int8 = jax.jit(lambda pool: jax.tree_util.tree_map(ref.int8_rows, pool), donate_argnums=0)

    def round_pool():
        sched.cache.pool = to_int8(sched.cache.pool)

    prompts = draw()
    res["int8_pool_program"] = against_reference(
        prompts, _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size, round_pool), {})
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
        # unrolled from the start: the tree is made in the layout the engine serves
        model = build_model(ctx.config, dtype=dtype, scan_layers=False)
    except (ValueError, TypeError) as e:
        raise CellError(f"the program cannot build configuration {ctx.config['name']}: {e}")
    cfg = model.cfg

    params = seeded_params(model, ctx.seed, dtype)
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
        hits_before = sched.radix.hits
        first = _post(port, {"prompt": repeat_prompt, "max_tokens": 24}, timeout=900)
        again = _post(port, {"prompt": repeat_prompt, "max_tokens": 24}, timeout=900)
        radix_hits = sched.radix.hits - hits_before
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

        traced, after, _, after_s, host = measured_window(
            ctx, t0, t1, p["trace_window_s"], sample, snapshot=lambda: _metrics(port))
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
    kv_bytes = sched.cache.bytes_per_token()
    brief = lambda r: {k: r[k] for k in ("ok", "error", "median_error", "errors", "max_errors")}
    checks = {
        "logits_match_reference": compared["ok"],
        "lower_precision_fails": not compared["lower_precision"]["ok"],
        "int8_pool_program_fails": not compared["int8_pool_program"]["ok"],
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_through_radix_copy": radix_hits >= 1,
        "sparse_expert_dispatch": dispatch.get("dense", 1) == 0 and dispatch.get("sparse", 0) > 0,
        "latent_kv_bytes_per_token": kv_bytes == ctx.config["reference"]["kv_bytes_per_token"],
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
                   "client_tpot_p90_ms": res["tpot_p90_ms"]},
        "series": {"slot_occupancy_pct": occupancy, "live_kv_rows": live_rows},
        "telemetry": after.get("telemetry"),
        "model_cfg": cfg, "itemsize": dtype.itemsize,
        "info": dict(res["info"], tpot_p90_ms=res["tpot_p90_ms"], logits_error=compared["error"],
                     median_error=compared["median_error"],
                     logits_errors=compared["errors"], logits_max_errors=compared["max_errors"],
                     rows_compared=compared["rows"],
                     routing_margin_rows=compared["routing_margin_rows"],
                     routing_refused_rows=compared["routing_refused_rows"],
                     routing_rows=compared["routing_rows"],
                     routing_reach_max=compared["routing_reach_max"],
                     lower_precision=brief(compared["lower_precision"]),
                     lower_precision_pool=brief(compared["lower_precision_pool"]),
                     int8_pool_program=dict(
                         brief(compared["int8_pool_program"]),
                         **{k: compared["int8_pool_program"][k] for k in (
                             "routing_margin_rows", "routing_refused_rows", "routing_reach_max")}),
                     tol=ref.TOL[p["dtype"]], routing_margin=ref.ROUTING_MARGIN,
                     max_followed_share=ref.MAX_FOLLOWED_SHARE,
                     late_compiles=late_compiles, drained=bool(drained),
                     host=host, generator=out.get("generator"),
                     after_window_s=dict(after_s, first_tokens_and_records=t_records - t1,
                                         drain=time.monotonic() - t_records),
                     compiled_programs=sched_m["compiled_programs"],
                     num_slots=sched_m["num_slots"], max_len=sched.max_len,
                     kv_bytes_per_token=kv_bytes, radix_hits=radix_hits,
                     moe_dispatch_programs=dispatch,
                     kv_commit_programs=sched_m.get("kv_commit_programs"),
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    finish_trace(ctx, traced, obs)
    return obs
