"""``pump_wait_trace_pct``: the share of the traced window the serving pump
spent blocked on the device, under ``dstpu/sched/fetch`` (the landing's
``device_get``s) or ``dstpu/sched/fence`` (the sampled sync's
``block_until_ready``s), on the profiler's clock. The program keeps the same
account from the same span boundaries on its own clock
(``telemetry/capacity.py: HostGapTracker``): with no idle turn in the window,
100 - ``pump_host_busy_pct`` is this number."""

from chipbench import trace_reduce, xplane

WAIT_SPANS = ("dstpu/sched/fetch", "dstpu/sched/fence")


def reduce(obs):
    trace = xplane.run_trace(obs)
    if trace is None:
        return None
    t0, t1 = trace["t0"], trace["t1"]
    waits = [(s, s + d) for n, s, d in trace_reduce.clip(trace["host"], t0, t1)
             if n in WAIT_SPANS]
    if not waits:
        return None
    return 100.0 * trace_reduce.total(trace_reduce.union(waits)) / (t1 - t0)
