"""The ``serve`` job: the engine and the gateway in this process
(``init_inference`` + ``Gateway(engine, port=0)`` + ``start_background()``),
load from a child process over localhost HTTP with SSE.

Set-up, all before the window and all in ``setup_s``:

1. weights from ``--seed`` (one jitted call on the device), handed to
   ``init_inference``, which quantises them to int8 its own way;
2. ``correct``, part 1, through the scheduler directly (the pump is not
   running yet): two seeded requests, prefill then 16 decode steps with
   ``collect_logits``; their logits against the reference's full forward
   on the dequantised weights (``reference.SERVE_LOGITS_TOL``). This costs
   one extra program (see ``_logits_check``);
3. the gateway starts; a *primer* request keeps one row decoding while
   ``correct``, part 2, sends one prompt twice over HTTP (same tokens both
   times; the second finds the first in the radix cache). This is also the
   warm-up: with the primer live, those requests dispatch exactly the
   programs the window's traffic uses: the (K, chunk) span program, the
   (K, 1) decode program and the radix copy;
4. the load generator starts and ramps until every client has had a first
   token and the primer has ended; then the window opens in steady state.

What is measured, from the child's raw records: see ``reduce_records``.
The window itself (sampling, the traced part, the snapshot before the
profiler stops) is ``harness.measured_window``'s, as in every serving job.
"""

import http.client
import json
import os
import statistics
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp

from chipbench import reference, traffic
from chipbench.cells import HERE, CellError, build_model
from chipbench.harness import finish_trace, measured_window

COLLECT_STEPS = 17  # the prefill's token and 16 decode steps


def quantile(xs, q):
    """Linear-interpolated quantile of a list (inclusive method)."""
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def delivery_stalls(records, t0, t1, gap_ms):
    """The stretches of [t0, t1) of ``gap_ms`` or more in which NO client
    received a token: the arrival instants of all clients merged and sorted,
    a stall a gap between two consecutive ones, as far as it lies inside the
    window. In a closed loop with a client a slot some row receives tokens
    every period of the pump, so a gap of several periods is the server
    standing still, whatever the cause; one client's pause while the others
    receive is none. Returns [(seconds after t0, length in ms)], by start."""
    instants = sorted(t for r in records for t, _ in r["events"])
    out = []
    for a, b in zip(instants, instants[1:]):
        a, b = max(a, t0), min(b, t1)
        if (b - a) * 1e3 >= gap_ms:
            out.append((a - t0, (b - a) * 1e3))
    return out


def reduce_records(records, t0, t1, t_stop, min_tokens, stall_gap_ms=None):
    """End-to-end numbers from the load generator's records.

    - ``serve_tokens_per_s``: output tokens whose SSE event reached a client
      in [t0, t1), over t1 - t0. Every token counts, whole request or not.
    - ``ttft_p90_ms``: send to first streamed token, over EVERY request sent
      in [t0, t1); the run waits past t1 for their first tokens.
    - ``tpot_p90_ms``: per request, the mean gap between its tokens that
      arrived in [t0, t1): (last arrival - first arrival) / (tokens after
      the first arrival's), for requests with ``min_tokens`` or more tokens
      in the window; the 90th percentile over requests. ``tpot_p50_ms`` is
      the median of the same per-request numbers.
    - ``delivery_stall_ms_per_s``, ``delivery_stalls_per_min`` (where the
      cell states a ``stall_gap_ms``): see ``delivery_stalls``; their summed
      length a second of window, and their count a minute.
    - failed: a request sent in the window that was refused, errored, got
      no first token, or ended short of ``max_tokens``. A stream this run
      cut itself at the end (after ``t_stop``) did not fail."""
    tokens = 0
    ttft, tpot, failed, sent = [], [], 0, 0
    for r in records:
        in_win = [(t, n) for t, n in r["events"] if t0 <= t < t1]
        tokens += sum(n for _, n in in_win)
        n_win = sum(n for _, n in in_win)
        if n_win >= min_tokens and len(in_win) >= 2:
            tpot.append((in_win[-1][0] - in_win[0][0]) / (n_win - in_win[0][1]) * 1e3)
        if not t0 <= r.get("t_send", -1.0) < t1:
            continue
        sent += 1
        got = sum(n for _, n in r["events"])
        cut_by_us = r.get("t_end", t_stop) >= t_stop and not r["done"]
        if r["status"] != 200 or r["t_first"] is None:
            failed += 1
        elif r["done"] and got != r["max_tokens"]:
            failed += 1
        elif not r["done"] and not cut_by_us:
            failed += 1
        if r["t_first"] is not None:
            ttft.append((r["t_first"] - r["t_send"]) * 1e3)
    turnaround = [(r["t_send"] - r["t_ready"]) * 1e3 for r in records if "t_send" in r]
    stalls = delivery_stalls(records, t0, t1, stall_gap_ms) if stall_gap_ms else None
    stall_ms_per_s = stalls_per_min = None
    if stalls is not None:
        stall_ms_per_s = sum(ms for _, ms in stalls) / (t1 - t0)
        stalls_per_min = len(stalls) * 60.0 / (t1 - t0)
    tpot_p50 = quantile(tpot, 0.5) if tpot else None
    tpot_p90 = quantile(tpot, 0.9) if tpot else None
    return {"serve_tokens_per_s": tokens / (t1 - t0),
            "ttft_p90_ms": quantile(ttft, 0.9) if ttft else None,
            "tpot_p90_ms": tpot_p90, "tpot_p50_ms": tpot_p50,
            "delivery_stall_ms_per_s": stall_ms_per_s, "delivery_stalls_per_min": stalls_per_min,
            "attempted": sent, "failed": failed,
            "info": {"tokens_in_window": tokens, "requests_sent_in_window": sent,
                     "ttft_samples": len(ttft), "tpot_samples": len(tpot),
                     "ttft_p50_ms": quantile(ttft, 0.5) if ttft else None,
                     "tpot_p50_ms": tpot_p50, "tpot_p90_ms": tpot_p90,
                     "tpot_max_ms": max(tpot) if tpot else None,
                     "delivery_stall_ms_per_s": stall_ms_per_s,
                     "delivery_stalls_per_min": stalls_per_min,
                     # the longest few, [seconds after t0, ms]: when they came
                     "delivery_stalls_longest": stalls and sorted(
                         sorted(stalls, key=lambda s: -s[1])[:8]),
                     "completed_in_window": sum(1 for r in records if r["done"]
                                                and t0 <= r.get("t_end", -1) < t1),
                     "generator_connect_ms_max": max(turnaround) if turnaround else None,
                     "generator_connect_ms_mean": statistics.fmean(turnaround)
                     if turnaround else None}}


def _post(port, body, timeout=600):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise CellError(f"/v1/completions answered {resp.status}: {raw[:200]!r}")
        return json.loads(raw)["choices"][0]["token_ids"]
    finally:
        conn.close()


def _metrics(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", "/v1/metrics")
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _logits_check(ctx, eng, sched, cfg):
    """Prefill + 16 decode steps of two seeded requests through the
    scheduler (paged pool, fused kernels), logits against the reference.

    One extra program only: a long filler prompt prefills behind the two
    requests, so every sync they decode in is a (K, chunk) span sync and
    only that program's ``collect_logits`` variant compiles (a program costs
    tens of seconds at 36 unrolled layers). The filler is cancelled the
    moment both are done, before a chunk could run with no row decoding
    (that would dispatch the (1, chunk) program the window never uses)."""
    p = ctx.workload["serve"]
    rng = traffic.seed_stream(ctx.seed, "correct")
    lens = p["collect_prompt_lens"]
    prompts = [[rng.randrange(cfg.vocab_size) for _ in range(n)] for n in lens]
    handles = [sched.submit(pr, max_new_tokens=COLLECT_STEPS, collect_logits=True)
               for pr in prompts]
    filler = sched.submit([rng.randrange(cfg.vocab_size)
                           for _ in range(p["filler_prompt_len"])], max_new_tokens=4)
    while not all(h.done for h in handles):
        sched.step()
    filler.cancel()
    sched.drain()
    ref_kw = reference.kwargs_for(ctx.config, cfg)
    sabotage = ctx.workload.get("force_wrong")

    def ref_logits(params, ids):
        tree = reference.from_int8_tree(params, cfg.vocab_size)
        if sabotage:
            tree["lnf_g"] = tree["lnf_g"] * 1.5
        return reference.forward(tree, ids, **ref_kw)[0]

    ref_fn = jax.jit(ref_logits)
    errs = []
    for pr, h in zip(prompts, handles):
        toks = [int(t) for t in h.result()]
        got = h.result_logits()  # (17, V): the row that chose each token
        ids = jnp.asarray([pr + toks[:-1]], jnp.int32)
        with eng.mesh:
            want = ref_fn(eng.params, ids)[len(pr) - 1:]
        errs.append(float(reference.logits_error(got, want)))
    return errs


def run(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.serving import Gateway

    p = ctx.workload["serve"]
    tr = p["traffic"]
    comm.initialize_mesh(devices=list(ctx.devices))
    model = build_model(ctx.config)
    cfg = model.cfg

    # ---- engine: weights from --seed, on the device, in one jitted call
    init = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init_params(key)))
    params = init(jax.random.key(ctx.seed % (2**31 - 1)))
    engine_cfg = {"dtype": p["dtype"], "kernel_inject": bool(p["kernel_inject"]),
                  "max_out_tokens": p["max_len"],
                  "continuous_batching": {"enabled": True, "num_slots": p["num_slots"]}}
    if ctx.trace:
        # per-layer run only: the sink's histogram window is the measured
        # window, so a snapshot at its end describes the window alone
        engine_cfg["telemetry"] = {"enabled": True, "hist_window_s": ctx.seconds,
                                   "output_path": os.path.join(ctx.scratch, "telemetry")}
    eng = deepspeed_tpu.init_inference(model, config=engine_cfg, params=params)
    del params
    # the default deadline (120 s) is shorter than the compile of a cold
    # run's first two step programs; a deployment setting
    gw = Gateway(eng, port=0, max_queue_depth=max(64, 2 * tr["clients"]),
                 request_timeout_s=600)
    sched = gw.scheduler
    ctx.setup_part("engine_build")

    errs = _logits_check(ctx, eng, sched, cfg)
    ctx.setup_part("reference_and_collect_programs")

    gw.start_background()
    port = gw.port
    child = None
    try:
        # ---- primer + repeat check (warms the window's programs)
        rng = traffic.seed_stream(ctx.seed, "warm")
        primer_prompt = [rng.randrange(cfg.vocab_size) for _ in range(32)]
        repeat_prompt = [rng.randrange(cfg.vocab_size) for _ in range(p["repeat_prompt_len"])]
        primer_out = []
        primer = threading.Thread(target=lambda: primer_out.extend(_post(
            port, {"prompt": primer_prompt, "max_tokens": p["primer_tokens"]})))
        primer.start()
        while _metrics(port)["scheduler"]["active_slots"] < 1:
            time.sleep(0.05)
        first = _post(port, {"prompt": repeat_prompt, "max_tokens": 24})
        again = _post(port, {"prompt": repeat_prompt, "max_tokens": 24})
        ctx.setup_part("warm_programs")

        # ---- the load generator: a child that imports no JAX
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
        primer.join(timeout=300)
        if primer.is_alive() or len(primer_out) != p["primer_tokens"]:
            raise CellError(f"the primer request did not finish: {len(primer_out)} tokens")
        ctx.setup_part("ramp")

        # ---- the window
        programs_before = ctx.compiles["programs"]
        before = _metrics(port)
        ctx.mark_window_start()
        t0 = time.monotonic() + 0.05
        t1 = t0 + ctx.seconds
        child.stdin.write(json.dumps({"window": [t0, t1]}) + "\n")
        child.stdin.flush()
        occupancy = []
        traced, after, _, after_s, host = measured_window(
            ctx, t0, t1, p["trace_window_s"],
            sample=lambda: occupancy.append(100.0 * sched.cache.occupancy()),
            snapshot=lambda: _metrics(port))
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
        eng.telemetry.close()  # its files live in the run's scratch directory

    res = reduce_records(out["records"], t0, t1, out["t_stop"], p["tpot_min_tokens"],
                         p.get("stall_gap_ms"))
    sched_m = after["scheduler"]
    checks = {
        "logits_match_reference": max(errs) <= reference.SERVE_LOGITS_TOL,
        "repeat_prompt_same_tokens": first == again and len(first) == 24,
        "fused_decode_path": bool(sched_m["fused_decode_block"])
        and not sched_m["fused_decode_reasons"],
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
                   "delivery_stall_ms_per_s": res["delivery_stall_ms_per_s"],
                   "delivery_stalls_per_min": res["delivery_stalls_per_min"]},
        "series": {"slot_occupancy_pct": occupancy},
        "telemetry": after.get("telemetry"),
        "info": dict(res["info"], logits_errors=errs, tol=reference.SERVE_LOGITS_TOL,
                     late_compiles=late_compiles, drained=bool(drained),
                     host=host, generator=out.get("generator"),
                     after_window_s=dict(after_s, first_tokens_and_records=t_records - t1,
                                         drain=time.monotonic() - t_records),
                     compiled_programs=sched_m["compiled_programs"],
                     num_slots=sched_m["num_slots"], max_len=sched.max_len,
                     kv_bytes_per_token=sched.cache.bytes_per_token(),
                     gateway=after["gateway"] and {k: after["gateway"][k] for k in (
                         "requests", "completed", "shed_429", "shed_503", "deadline_expired",
                         "disconnects", "rejected")}),
    }
    finish_trace(ctx, traced, obs)
    return obs
