"""The ``serve_falcon_h1`` job: ``jobs/serve_nemotron_h.py``'s flow for a
configuration whose every block runs TWO mixers, grouped-query attention and
a Mamba-2 mixer on one normed input, each scaled by a published constant
(TII Falcon-H1-34B-Instruct), served in its float dtype through the
per-projection path: ``init_inference`` + ``Gateway(engine, port=0)`` +
``start_background()`` in this process, load from a child over localhost
HTTP with SSE. The end-to-end arithmetic is ``jobs/serve.py``'s
(``reduce_records``), the traffic ``traffic.py``'s, the load ``loadgen.py``'s.
No other serving job takes the configuration as data: ``serve_nemotron_h``
follows a router's choices and draws no matrix by a multiplier.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (``falcon_params``: ``serve_nemotron_h.
   nemotron_params``' draw, with every matrix whose output a published
   multiplier scales drawn at 0.02 OVER that multiplier);
2. ``correct``, part 1, through the scheduler directly: two seeded requests
   (one prompt inside a chunk, one over three chunks with a partial last),
   prefill then 16 decode steps with ``collect_logits``, a long filler
   prefilling behind them and neighbours live in other slots; every
   position's logits against the reference's full forward on the same
   weights (``references/<module>.py``: one causal forward, no cache, the
   recurrence token by token). Four controls have to come out NOT ok: the
   reference with its weight matrices at int8 (the precision below the
   configuration's), compared with itself; the PROGRAM with its attention
   branch's output at zero (``o_proj`` zeroed); the PROGRAM computing what
   one that leaves ``mu`` out computes (the in-projection's column blocks
   divided by ``mu``); the PROGRAM with its state leaves zeroed between syncs,
   rows kept;
3. the gateway starts; a primer keeps one row decoding while one prompt,
   longer than two prefill chunks, is sent twice: same tokens both times,
   both served cold (every layer's slot holds state beside its rows, and
   such a pool takes no prefix hit: the scheduler's bypass counter moved).
   This also warms the window's programs;
4. the load generator ramps (every client has had a first token) and the
   backlog the ramp left on the one prefill lane drains; then the window
   opens.

A traced run profiles the LAST ``trace_window_s`` of the window and reads the
program's counters of required state work where the trace starts and where
it stops (``harness.measured_window``).
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
from chipbench.jobs.serve_nemotron_h import nemotron_params
from chipbench.jobs.serve_ref import _collect

# the program's counters read where the trace starts and stops, under
# ``values`` as ``<name>_traced`` (``serving/<name>``)
TRACED = ("ssd_state_updates", "ssd_chunk_tokens")


def draw_scales(cfg):
    """{leaf path's tail: what the 0.02 draw of that leaf is multiplied by}:
    one over the published constant(s) that scale the matrix's output (for
    the in-projection a vector, a column block each), so that the SCALED
    activation is what a 0.02 draw gives an unscaled one. Read from the
    configuration the program built: the reference takes the same weights
    and the constants from the published keys."""
    from deepspeed_tpu.models.mamba2 import in_projection_multipliers
    mu = in_projection_multipliers(cfg)
    a_in = cfg.attention_in_multiplier
    return {"['embed']['embedding']": 1.0 / cfg.embedding_multiplier,
            "['lm_head']['kernel']": 1.0 / cfg.lm_head_multiplier,
            "['attn']['q_proj']['kernel']": 1.0 / a_in,
            "['attn']['k_proj']['kernel']": 1.0 / (a_in * cfg.key_multiplier),
            "['attn']['v_proj']['kernel']": 1.0 / a_in,
            "['attn']['o_proj']['kernel']": 1.0 / cfg.attention_out_multiplier,
            "['mamba2']['in_proj']['kernel']": 1.0 / (cfg.ssm_in_multiplier * mu),
            "['mamba2']['out_proj']['kernel']": 1.0 / cfg.ssm_out_multiplier,
            "['mlp']['gate_proj']['kernel']": 1.0 / cfg.mlp_gate_multiplier,
            "['mlp']['down_proj']['kernel']": 1.0 / cfg.mlp_down_multiplier}


def falcon_params(model, seed, dtype):
    """``nemotron_params``' tree (normal(0, 0.02) kernels, norm scales 1;
    each Mamba-2 mixer's ``A_log``, ``dt_bias`` and ``D`` by the published
    start, its taps uniform in (-1, 1), every down- and out-projection
    centred: the reasons are there) with every matrix whose OUTPUT a
    published multiplier scales multiplied by one over it (``draw_scales``).

    Why: the multipliers are the other half of a parametrisation whose
    trained weights are NOT at 0.02. Drawn at 0.02 beside them, the keys are
    0.011 of the queries and every score is 0.02: the softmax is flat, the
    attention branch an average of the values that no rotation, key or
    position moves, 0.0375 of it 1.5% of the stream; x, B and C reach the
    convolution at 0.06-0.18 and the state's read-out is a five-hundredth of
    the skip term beside it: a program with no attention branch, no rotary
    positions or no state would pass. Drawn over the multipliers, the scaled
    activations are what cells 5-10's are (scores of deviation 2, a state
    that carries hundreds of positions), each constant is needed to get
    there, and one left out or misplaced moves a branch by its whole
    factor."""
    params = nemotron_params(model, seed, dtype)
    scale = jax.jit(lambda leaf, by: (leaf.astype(jnp.float32) * by).astype(leaf.dtype),
                    donate_argnums=0)
    scales = draw_scales(model.cfg)

    def rescale(path, leaf):
        name = jax.tree_util.keystr(path)
        for tail, by in scales.items():
            if name.endswith(tail):
                return scale(leaf, by)
        return leaf

    return jax.tree_util.tree_map_with_path(rescale, params)


def without_attention(params):
    """The tree with every attention branch's output projection at zero."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.zeros_like(leaf) if jax.tree_util.keystr(path).endswith(
            "['attn']['o_proj']['kernel']") else leaf), params)


def _is_in_proj(path):
    return jax.tree_util.keystr(path).endswith("['mamba2']['in_proj']['kernel']")


def without_mu(params, cfg):
    """``(tree, restore)``. ``tree``: the one on which the program, which
    applies ``mu``, computes what a program that leaves ``mu`` out computes on
    the served tree: every in-projection's column blocks divided by ``mu``, IN
    the served leaves' place (six of them are 0.57 GB, and the chip has no
    room for both beside the collecting program's temporaries); the served
    values wait on the host. ``restore()`` gives the served tree back, value
    for value, in new device leaves."""
    import numpy as np
    from deepspeed_tpu.models.mamba2 import in_projection_multipliers
    mu = in_projection_multipliers(cfg)
    divide = jax.jit(lambda leaf: (leaf.astype(jnp.float32) / mu).astype(leaf.dtype),
                     donate_argnums=0)
    kept = {}

    def change(path, leaf):
        if not _is_in_proj(path):
            return leaf
        kept[jax.tree_util.keystr(path)] = (np.asarray(leaf), leaf.sharding)
        return divide(leaf)

    tree = jax.tree_util.tree_map_with_path(change, params)

    def put_back(path, leaf):
        if not _is_in_proj(path):
            return leaf
        leaf.delete()  # a leaf at a time: the changed one goes before the served one comes
        return jax.device_put(*kept.pop(jax.tree_util.keystr(path)))

    def restore():
        return jax.tree_util.tree_map_with_path(put_back, tree)

    return tree, restore


def _logits_check(ctx, eng, sched, cfg, ref):
    """``correct``, part 1; see the module docstring. Returns ``ref.compare``'s
    dict over both requests' positions, with the controls' verdicts under
    ``lower_precision`` (the reference with its weights at int8, against
    itself), ``no_attention_program``, ``no_mu_program`` and
    ``zero_state_program`` (the PROGRAM, against the reference), and
    ``branch_rms``: per layer the RMS of ``m_s SSM``, ``m_a Attn``, the MLP's
    term and the stream, over the longer request's positions."""
    p = ctx.workload["serve"]
    tol = ref.TOL[p["dtype"]]
    rng = traffic.seed_stream(ctx.seed, "correct")
    draw = lambda: [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                    for n in p["collect_prompt_lens"]]
    hp = ref.kwargs_for(ctx.config)
    if ctx.workload.get("force_wrong"):
        hp = dict(hp, attention_out_multiplier=1.0)  # a reference that leaves one constant out
    tree = ref.from_tree(eng.params, cfg.num_layers)

    def against_reference(prompts, handles, lower=False):
        got, want, low, sizes = [], [], [], None
        for pr, h in zip(prompts, handles):
            toks = [int(t) for t in h.result()]
            got.append(h.result_logits())  # (17, V): the row that chose each token
            ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
            first = len(pr) - 1
            with eng.mesh:
                logits, sizes = ref.forward(tree, ids, hp, first=first, branches=True)
                if lower:
                    low.append(ref.forward(tree, ids, hp, levels=127.0, first=first)[0])
            want.append(logits[0])
        want = jnp.concatenate(want)
        res = dict(ref.compare(jnp.concatenate(got), want, tol=tol),
                   branch_rms=[[float(v) for v in row] for row in sizes])
        if lower:
            res["lower_precision"] = ref.compare(jnp.concatenate(low), want, tol=tol)
        return res

    def collect(after_step=None):
        prompts = draw()
        return prompts, _collect(sched, prompts, p["filler_prompt_len"], rng, cfg.vocab_size,
                                 after_step)

    peaks = {}

    def done(phase):
        peaks[phase] = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                           for d in ctx.devices)

    res = against_reference(*collect(), lower=True)
    done("reference")
    # the PROGRAM on a tree that takes one part of the block away, against
    # the reference on the served tree; the engine's tree is put back behind
    # each, before the reference's blocks come
    served = eng.params
    eng.params = without_attention(served)
    try:
        collected = collect()
    finally:
        eng.params = served
    res["no_attention_program"] = against_reference(*collected)
    done("no_attention_program")
    del tree, served  # the in-projections are about to be changed in place
    eng.params, restore = without_mu(eng.params, cfg)
    try:
        collected = collect()
    finally:
        eng.params = restore()
    tree = ref.from_tree(eng.params, cfg.num_layers)
    res["no_mu_program"] = against_reference(*collected)
    done("no_mu_program")
    # ... and with every STATE leaf of the pool (the Mamba-2 states and
    # windows) zeroed between syncs, the rows kept
    kinds = sched.cache.leaf_kinds
    # a leaf at a time and IN its place (a product, which the donated buffer
    # takes; zeros that do not depend on it would be a second buffer)
    zeroed = jax.jit(lambda leaf: leaf * jnp.zeros((), leaf.dtype), donate_argnums=0)

    def after_step():
        leaves, treedef = jax.tree_util.tree_flatten(sched.cache.pool)
        sched.cache.pool = jax.tree_util.tree_unflatten(treedef, [
            zeroed(leaf) if k == "state" else leaf for leaf, k in zip(leaves, kinds)])

    res["zero_state_program"] = against_reference(*collect(after_step))
    done("zero_state_program")
    res["memory_peak_after"] = peaks
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
        params = falcon_params(model, ctx.seed, dtype)
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
        # the window measures the loop once the ramp's backlog on the one
        # prefill lane has drained (``jobs/serve_nemotron_h.py``)
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
    want = ctx.config["reference"]
    commits = sched_m.get("kv_commit_programs") or {}
    controls = ("lower_precision", "no_attention_program", "no_mu_program",
                "zero_state_program")
    brief = lambda r: {k: r[k] for k in ("ok", "error", "min_error", "median_error", "errors")}
    checks = {
        "logits_match_reference": compared["ok"],
        **{name + "_fails": not compared[name]["ok"] for name in controls},
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "repeat_served_cold_twice": bypassed >= 2,
        "kv_commit_in_place": (commits.get("inplace", 0) > 0) is bool(p["kernel_inject"]),
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
                     rows_compared=compared["rows"], branch_rms=compared["branch_rms"],
                     memory_peak_after=compared["memory_peak_after"],
                     **{name: brief(compared[name]) for name in controls},
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
                     kv_commit_programs=commits,
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    finish_trace(ctx, traced, obs)
    return obs
