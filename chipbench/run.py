"""One cell, once, in this process:

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files, claims the chips, hands over to the cell's job
(``jobs/<job>.py``), which sets up, checks correctness OUTSIDE the window,
measures for ``--seconds`` and returns its observations; then prints the one
result line last. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (the profiler and the telemetry sink are
on only then). Without a TPU the command exits non-zero and prints no result
line; the one exception is a rehearsal workload given by path
(``tests/fixtures/workloads/*.json``), which runs a ``tiny*`` preset on the
CPU and reports counts only: no time, rate or utilization.
"""

import time

T_START = time.perf_counter()  # process start, for setup_s (the interpreter's own ~50 ms are not in it)

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench import cells, harness, reducers  # noqa: E402

# what a rehearsal may report: counts, never a time, a rate or a utilization
REHEARSAL_UNITS = ("count", )


def reduce_per_layer(metrics, obs):
    out = {}
    for name, m in sorted(metrics.items()):
        fn = cells.custom_reducer(m)
        val = fn(obs) if fn is not None else reducers.BUILTIN[m["reducer"]](m.get("args", {}), obs)
        if val is None:
            continue
        val = float(val)
        if not math.isfinite(val):
            raise cells.CellError(f"per-layer metric {name} is not finite: {val}")
        out[name] = {"value": val, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, workload, root = cells.load_workload(args.workload)
    rehearsal = bool(workload.get("rehearsal"))
    config = cells.load_config(workload["config"], root)
    if rehearsal and not config["preset"].startswith("tiny"):
        raise cells.CellError("a rehearsal runs a tiny* preset only")
    metrics = cells.per_layer_metrics(cell, workload, root)
    job = cells.load_job(workload["job"])

    # the program's own rule for the compile cache: JAX_COMPILATION_CACHE_DIR
    # if set, else <checkout>/.jax_cache. Small programs are cached too, so
    # that a second run of a cell compiles nothing.
    import jax
    from deepspeed_tpu.utils import compile_cache
    if not rehearsal:
        compile_cache.configure()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices, peaks, device = cells.claim_devices(workload["chips"], rehearsal)
    compiles = harness.count_compiles()

    # per-run files (trace, telemetry) live inside the checkout, cleared first
    scratch = os.path.join(os.path.dirname(cells.HERE), ".chipbench_run", cell)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)

    ctx = harness.Context(T_START, cell, workload, config, root, devices, peaks, args.seed,
                          args.seconds, bool(args.trace), rehearsal, scratch, compiles)
    obs = job.run(ctx)

    device["memory_peak_bytes"] = cells.memory_peak_bytes(devices)
    obs.update(peaks=peaks, chips=ctx.chips, device=device)
    obs.setdefault("values", {}).update(
        compile_cache_misses=compiles["cache_misses"], compiled_programs=compiles["programs"],
        compile_cache_hits=compiles["cache_hits"])
    if rehearsal:  # counts only: no time of a CPU run is printed anywhere
        ctx.note(compiles=dict(compiles), checks=obs.get("checks"),
                 info={k: v for k, v in (obs.get("info") or {}).items() if isinstance(v, int)})
    else:
        ctx.note(setup_s=ctx.setup_s, setup_parts=ctx.setup_parts, compiles=dict(compiles),
                 checks=obs.get("checks"), info=obs.get("info"))

    result = {"correct": bool(obs["correct"]), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"])}
    if args.trace:
        summary = obs.get("trace_summary")
        if summary is not None:
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            result["breakdown"] = summary["breakdown"]
        result["metrics"] = reduce_per_layer(metrics, obs)
    else:
        e2e = dict(obs["end_to_end"], setup_s=ctx.setup_s)
        want, units = workload["end_to_end"], cells.end_to_end_units()
        missing = [n for n in want if e2e.get(n) is None or n not in units]
        if missing:
            raise cells.CellError(f"{cell}: {missing} not reported by the job, or not "
                                  f"among BENCHMARK.json's end_to_end")
        result["metrics"] = {n: {"value": float(e2e[n]), "unit": units[n]} for n in want}
    if rehearsal:
        # a CPU run gives counts only: nothing timed leaves the process
        result["metrics"] = {n: m for n, m in result["metrics"].items()
                             if m["unit"] in REHEARSAL_UNITS}
        result.pop("breakdown", None)
        device.pop("busy_s", None)
        device.pop("window_s", None)
    result["device"] = device
    shutil.rmtree(scratch, ignore_errors=True)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except cells.CellError as e:
        sys.exit(f"chipbench: {e}")
