"""What a job gets from the harness: its context, the compile counters and
the profiler switch."""

import json
import os
import time


class Context:
    """What a job gets: the cell's files, the chips, the clock and the
    window's length. ``mark_window_start()`` fixes ``setup_s``."""

    def __init__(self, t_start, cell, workload, config, root, devices, peaks, seed, seconds,
                 trace, rehearsal, scratch, compiles):
        self.t_start, self.compiles = t_start, compiles
        self.cell, self.workload, self.config, self.root = cell, workload, config, root
        self.devices, self.peaks, self.chips = devices, peaks, len(devices)
        self.seed, self.seconds, self.trace, self.rehearsal = seed, seconds, trace, rehearsal
        self.scratch = scratch
        self.setup_s = None
        self.setup_parts = {}
        self._last_mark = t_start

    def note(self, **fields):
        """An informational line (anything but the last line is free)."""
        print(json.dumps({"note": fields}), flush=True)

    def setup_part(self, name):
        """Close one part of set-up (engine build, compile, reference...)."""
        now = time.perf_counter()
        self.setup_parts[name] = self.setup_parts.get(name, 0.0) + now - self._last_mark
        self._last_mark = now
        if not self.rehearsal:
            self.note(setup_part=name, seconds=self.setup_parts[name], at_s=now - self.t_start,
                      programs=dict(self.compiles))

    def mark_window_start(self):
        self.setup_s = time.perf_counter() - self.t_start
        return self.setup_s


def count_compiles():
    """jax.monitoring counters (as ``chip_smoke._count_compiles``): programs
    XLA was asked for, and how the persistent cache answered."""
    import jax
    seen = {"programs": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["programs"] += 1

    def on_event(name, *_a, **_k):
        for key in ("cache_hits", "cache_misses"):
            if name == f"/jax/compilation_cache/{key}":
                seen[key] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


class TracedWindow:
    """The profiler over the first ``seconds`` of a job's measured window,
    under the ``chipbench/window`` span that the reduction takes as the
    traced window. Does nothing in an untraced run. Host TraceMe spans are
    on, the Python tracer is off (it would bury the window in events)."""

    def __init__(self, ctx, seconds):
        self.dir = self._span = None
        if not ctx.trace:
            return
        import jax
        from chipbench.trace_reduce import WINDOW_SPAN
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.dir = os.path.join(ctx.scratch, "trace")
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._until = time.perf_counter() + seconds

    def due(self):
        """Whether the traced part has lasted its length and is still open."""
        return self._span is not None and time.perf_counter() >= self._until

    def stop(self):
        """Close the span and write the trace (once; later calls do nothing)."""
        if self._span is not None:
            import jax
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def finish_trace(ctx, traced, obs):
    """Load the run's trace (if it was traced) into ``obs`` and reduce it. A
    real cell whose trace holds no device operation is an error; a rehearsal
    on the CPU has no device plane."""
    from chipbench import trace_reduce
    from chipbench.cells import CellError
    if traced.dir is None:
        return
    obs["trace"] = trace_reduce.load(traced.dir, describe=lambda s: ctx.note(trace=s))
    if obs["trace"]["devices"]:
        obs["trace_summary"] = trace_reduce.summarize(obs["trace"])
    elif not ctx.rehearsal:
        raise CellError("the traced run recorded no operation on the device")
    else:
        obs["trace"] = None
