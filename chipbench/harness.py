"""What a job gets from the harness: its context, the compile counters and
the profiler switch."""

import gc
import json
import os
import resource
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
    """The profiler over ``seconds`` of a job's measured window, from the
    moment it is built, under the ``chipbench/window`` span that the
    reduction takes as the traced window: the window's LAST ``seconds`` in a
    serving job (``measured_window`` builds it then), its first in the
    training job. Does nothing in an untraced run. Host TraceMe spans are
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


def process_usage():
    """This process's CPU seconds and context switches so far."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_user_s": ru.ru_utime, "cpu_system_s": ru.ru_stime,
            "switches_voluntary": ru.ru_nvcsw, "switches_involuntary": ru.ru_nivcsw}


class HostWatch:
    """What this process's host side did between ``start()`` and ``stop()``,
    for the note of a serving run (the engine and the gateway run in this
    process): the collector's runs by generation and how long each held the
    interpreter (``gc.callbacks``: two clock reads a collection, it changes
    nothing), the process's CPU seconds and its context switches, the
    machine's core count (the chip machine's ``/proc/stat`` reads all zeros
    and it has no ``/proc/pressure`` or cgroup ``cpu.stat``: steal time and
    throttling cannot be read there). It only looks: the job freezes,
    disables and renices nothing, because a deployment's gateway would have
    to."""

    def __init__(self, clock=time.monotonic):
        self.clock, self.pauses, self._t = clock, [], None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = self.clock()
        elif self._t is not None:
            self.pauses.append((self._t, (self.clock() - self._t) * 1e3, info["generation"]))
            self._t = None

    def start(self, t0):
        self.t0, self._before, self._gc_before = t0, process_usage(), gc.get_stats()
        gc.callbacks.append(self._on_gc)

    def stop(self):
        gc.callbacks.remove(self._on_gc)
        after, gc_after = process_usage(), gc.get_stats()
        by_gen = lambda g: [ms for _, ms, gen in self.pauses if gen == g]
        return dict({k: after[k] - self._before[k] for k in after},
                    watched_s=self.clock() - self.t0, cpu_count=os.cpu_count(),
                    gc_collections=[a["collections"] - b["collections"]
                                    for a, b in zip(gc_after, self._gc_before)],
                    gc_pause_ms=[sum(by_gen(g)) for g in range(3)],
                    # the longest few, [seconds after t0, ms, generation]
                    gc_pauses_longest=sorted(
                        [t - self.t0, ms, gen] for t, ms, gen in
                        sorted(self.pauses, key=lambda p: -p[1])[:8]))


# what ``jax.profiler.start_trace`` may take before the traced part begins
TRACE_START_S = 2.0


def measured_window(ctx, t0, t1, trace_seconds, sample, snapshot, counted=None, *,
                    open_trace=TracedWindow, clock=time.monotonic, sleep=time.sleep,
                    period=0.25):
    """A serving job's measured window [t0, t1) (``clock``'s), driven on the
    calling thread: ``sample()`` every ``period`` seconds until the window
    closes, then ``snapshot()`` (the gateway's metrics with the sink's
    windowed histograms).

    A traced run profiles the window's LAST ``trace_seconds``: the profiler
    opens ``TRACE_START_S`` before the traced part has to begin, so that the
    part closes inside the window, while the clients still send. When it is
    due (or at ``t1``, whichever comes first) the snapshot is taken FIRST and
    the profiler stopped after it: ``stop_trace`` writes the trace for
    seconds to most of a minute, and a snapshot taken behind it finds the
    sink's histograms retired (PR 32's and PR 37's lost per-layer metrics).
    ``counted()``, if given, reads the program's row counters where the
    traced part starts and where it stops. An untraced run opens no profiler,
    reads no counter and snapshots at the window's end.

    Returns ``(traced, after, counted_at, after_window_s, host)``: the
    ``TracedWindow`` or None, the snapshot, ``{"start", "stop"}`` or None,
    when the snapshot was done and how long ``stop_trace`` took, as seconds
    after ``t1`` (negative: inside the window), and ``HostWatch``'s account of
    this process from ``t0`` to the snapshot."""
    traced = counted_at = None
    watch = HostWatch(clock)
    watch.start(t0)
    trace_from = max(t0, t1 - trace_seconds - TRACE_START_S)
    while clock() < t1 and not (traced is not None and traced.due()):
        if ctx.trace and traced is None and clock() >= trace_from:
            traced = open_trace(ctx, trace_seconds)
            counted_at = {"start": counted()} if counted is not None else None
        sleep(period)
        sample()
    after = snapshot()
    if counted_at is not None:
        counted_at["stop"] = counted()
    t_after = clock()
    host = watch.stop()
    if traced is not None:
        traced.stop()
    return (traced, after, counted_at,
            {"snapshot": t_after - t1, "stop_trace": clock() - t_after}, host)


def finish_trace(ctx, traced, obs):
    """Load the run's trace (if it was traced) into ``obs`` and reduce it. A
    real cell whose trace holds no device operation is an error; a rehearsal
    on the CPU has no device plane."""
    from chipbench import trace_reduce
    from chipbench.cells import CellError
    if traced is None or traced.dir is None:
        return
    obs["trace"] = trace_reduce.load(traced.dir, describe=lambda s: ctx.note(trace=s))
    if obs["trace"]["devices"]:
        obs["trace_summary"] = trace_reduce.summarize(obs["trace"])
    elif not ctx.rehearsal:
        raise CellError("the traced run recorded no operation on the device")
    else:
        obs["trace"] = None
