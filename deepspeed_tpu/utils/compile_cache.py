"""Where compiled programs are kept between runs.

One rule for every process entry point (``chip_smoke.py``,
``python -m deepspeed_tpu.serving``, the launcher's user script): if
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing is set in
code; otherwise the cache is one fixed directory inside the checkout. The
path is part of the cache key, so it never comes from ``tempfile``, a pid
or a clock. Library constructors and the tests configure no cache.

``configure()`` also starts the process's set-up counters (``stats()``):
seconds and counts of the phases every compiled program goes through,
from ``jax.monitoring``. They move on compiles only, never in a steady
window.
"""

import os
import threading

ENV = "JAX_COMPILATION_CACHE_DIR"

# jax.monitoring time-span event -> phase of a program's set-up. "backend" is
# what XLA is asked for; with a warm persistent cache it is retrieval,
# deserialising and loading, and the retrieval alone ("cache_read", a
# duration event) happens inside it.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_lock = threading.Lock()
_stats = {f"{phase}_{kind}": zero for phase in (*_PHASES.values(), "cache_read")
          for kind, zero in (("s", 0.0), ("count", 0))}
_outermost = {}  # thread -> [(start, end, phase)] in _stats and inside no other so far
_listening = False


def cache_dir():
    """The directory in use: the environment's, else ``<checkout>/.jax_cache``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.environ.get(ENV) or os.path.join(root, ".jax_cache")


def configure():
    """Call before the first compile of a process. Returns the directory."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    listen()
    return path


def _on_span(event, start, end, **_kw):
    """A trace / lower / backend interval ended. Intervals nest: a jit traced
    inside another jit, a jnp function traced while a Mosaic kernel is
    lowered. The inner one ends first and the outer one takes it over, so
    seconds and counts are those of the outermost intervals, the three
    phases never overlap, and their sum is wall time."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    with _lock:
        spans = _outermost.setdefault(threading.get_ident(), [])
        while spans and spans[-1][0] >= start:
            inner_start, inner_end, inner_phase = spans.pop()
            _stats[inner_phase + "_s"] -= inner_end - inner_start
            _stats[inner_phase + "_count"] -= 1
        spans.append((start, end, phase))
        _stats[phase + "_s"] += end - start
        _stats[phase + "_count"] += 1


def _on_duration(event, duration, **_kw):
    if event == _CACHE_READ:
        with _lock:
            _stats["cache_read_s"] += duration
            _stats["cache_read_count"] += 1


def listen():
    """Start the set-up counters (once a process; ``configure()`` calls it)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax
    jax.monitoring.register_event_time_span_listener(_on_span)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def stats():
    """{``trace_s``, ``trace_count``, ``lower_s``, ..., ``backend_s``, ...,
    ``cache_read_s``, ``cache_read_count``} of this process so far: Python
    tracing to a jaxpr, lowering to an MLIR module (Mosaic kernels
    included), the backend's compile-or-load, and the persistent cache's
    retrieval inside the latter. What runs inside another interval is that
    interval's (a lowering's own tracing is lowering). All zero until
    ``listen()`` ran (``configure()`` calls it, and so does a scheduler
    built on an enabled sink)."""
    with _lock:
        return dict(_stats)


def programs():
    """How many programs the backend was asked for so far (built, or read
    from the persistent cache): it moves when something compiled. The pump's
    account (``telemetry/capacity.py``) reads it at every span exit, so no
    lock and no copy."""
    return _stats["backend_count"]


def export(env):
    """Hand the directory on to a child process through its environment."""
    env.setdefault(ENV, cache_dir())
    return env
