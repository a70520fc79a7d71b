"""The built-in readers of per-layer metrics. A metric file
(``metrics/<metric>.json``) names one with its arguments:
``{"reducer": "hist_quantile", "args": {"histogram": "serving/step_ms",
"quantile": "p50"}}``. Each takes ``(args, obs)`` and returns a number, or
``None`` when there is nothing to read (the harness then leaves the metric
out of the line). ``obs`` is what a job hands back: ``values`` (named
numbers), ``series`` (named lists), ``telemetry`` (the sink's snapshot),
``trace`` (``trace_reduce.load``), ``work`` (operations and bytes per
kernel call, from ``flops.py``), ``peaks``, ``chips``, ``device``.

A metric that none of these covers brings ``metrics/<metric>.py`` with
``reduce(obs)``.
"""

import statistics

from chipbench import flops, trace_reduce


def value(args, obs):
    """A number the job computed under ``args["key"]``."""
    return obs["values"].get(args["key"])


def series_stat(args, obs):
    """``median`` or ``mean`` of the series the job kept under ``key``."""
    xs = obs["series"].get(args["key"])
    if not xs:
        return None
    return (statistics.median if args.get("stat", "median") == "median"
            else statistics.fmean)(xs) * args.get("scale", 1.0)


def hist_quantile(args, obs):
    """A windowed quantile (``p50``/``p95``/``p99``) of a telemetry
    histogram; the sink's window is set to the measured window."""
    hist = ((obs.get("telemetry") or {}).get("histograms") or {}).get(args["histogram"])
    if not hist or not hist.get("window_count"):
        return None
    return hist[args.get("quantile", "p50")]


def counter_ratio(args, obs):
    """100 x counter ``num`` / (sum of counters ``den``), by ``total``."""
    counters = (obs.get("telemetry") or {}).get("counters") or {}
    den = sum(counters.get(n, {}).get("total", 0) for n in args["den"])
    if not den:
        return None
    return 100.0 * counters.get(args["num"], {}).get("total", 0) / den


def mfu(args, obs):
    """Required operations per token x tokens/s over chips x peak."""
    rate = obs["values"].get(args["rate"])
    if rate is None or not obs.get("peaks"):
        return None
    per_token = obs["values"][args["flops_per_token"]]
    return 100.0 * per_token * rate / obs["peaks"]["bf16_flops"]


def hbm_peak(args, obs):
    """Peak bytes in use on the fullest chip over the chip's HBM."""
    if not obs.get("peaks") or not obs["device"].get("memory_peak_bytes"):
        return None
    return 100.0 * obs["device"]["memory_peak_bytes"] / obs["peaks"]["hbm_bytes"]


def collective_exposed(args, obs):
    """Device trace: time a collective runs and no other operation does,
    over the traced window, mean over devices."""
    tr = obs.get("trace")
    if not tr or len(tr["devices"]) < 2:
        return None
    t0, t1 = trace_reduce.window_of(tr)
    _, exposed = trace_reduce.collective_seconds(tr, t0, t1)
    return 100.0 * exposed / (t1 - t0)


def kernel_roofline(args, obs):
    """Least time the chip could take for a kernel's calls over the time
    they took in the device trace. ``args``: {"pattern": regex on the
    operation's name, "work": name, "calls_per_unit": n}. The trace may not
    tell a kernel's variants apart (flash attention's forward, dq and dk/dv
    kernels are all called ``attn``), so work is counted in units: one unit
    is ``calls_per_unit`` calls, and needs the operations and bytes that
    ``obs["work"][name]`` gives (from ``flops.py``). Returns None (metric
    left out) when the trace names no such kernel."""
    tr = obs.get("trace")
    work = obs.get("work", {}).get(args["work"])
    if not tr or not obs.get("peaks") or work is None:
        return None
    t0, t1 = trace_reduce.window_of(tr)
    took, calls = trace_reduce.kernel_seconds(tr, args["pattern"], t0, t1)
    if not calls:
        return None
    least, _bound = flops.roofline_seconds(work[0], work[1], obs["peaks"])
    return 100.0 * (calls / args.get("calls_per_unit", 1)) * least / took


def span_self_time(args, obs):
    """Self time (ms per second of window) of a benchmark host span."""
    tr = obs.get("trace")
    if not tr:
        return None
    t0, t1 = trace_reduce.window_of(tr)
    secs = trace_reduce.span_self_seconds(tr, args["span"], t0, t1)
    return 1e3 * secs / (t1 - t0) if secs else None


BUILTIN = {fn.__name__: fn for fn in (value, series_stat, hist_quantile, counter_ratio, mfu,
                                      hbm_peak, collective_exposed, kernel_roofline,
                                      span_self_time)}
