"""Rehearsal only: how many ``dstpu/sched/step`` spans of the program the run's
profiler trace holds with ``dstpu/sched/dispatch`` inside them (a count; a CPU
trace has no device plane, so ``xplane.run_trace`` gives nothing there and
the file is read directly)."""

from chipbench import xplane


def reduce(obs):
    path = xplane.run_xplane()
    if path is None:
        return None
    host = xplane.read(path)["host"]
    steps = [(s, s + d) for n, s, d in host if n == "dstpu/sched/step"]
    inner = [(s, s + d) for n, s, d in host if n == "dstpu/sched/dispatch"]
    return sum(1 for a, b in steps if any(a <= s and e <= b for s, e in inner))
