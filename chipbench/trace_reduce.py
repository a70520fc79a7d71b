"""From a profiler trace to numbers. ``load`` turns the ``.xplane.pb`` that
``jax.profiler`` wrote into plain lists; everything else works on those
lists, so the arithmetic is tested on a small recorded trace
(``tests/fixtures/trace_small.json``) without a chip.

A loaded trace is ``{"devices": {plane: [(name, start_s, dur_s), ...]},
"host": [(name, start_s, dur_s), ...]}``: per device the operations that
ran on it, and the benchmark's own host spans (``chipbench/...``
``TraceAnnotation``s). Both are on the profiler's one clock.
"""

import glob
import gzip
import os
import re

# an operation is a collective if its HLO name starts with one of these
# (async pairs appear as <name>-start / <name>-done; all match by prefix)
COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast|send|recv)")
# the line of a TPU device plane that holds one event per executed HLO op
OP_LINES = ("XLA Ops", )
HOST_SPAN_PREFIX = "chipbench/"
# the span the harness puts around the traced part of the measured window
WINDOW_SPAN = "chipbench/window"


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir, describe=None):
    """Read the newest trace under ``trace_dir``. ``describe``, if given, is
    called with a one-line account of every plane and line (printed by the
    harness so that a reader sees what the trace held)."""
    import jax
    data = jax.profiler.ProfileData.from_file(newest_xplane(trace_dir))
    devices, host = {}, []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            keep = (is_device and line.name in OP_LINES) or plane.name.startswith("/host:")
            events = [(op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events] if keep else []
            if describe is not None:
                describe(f"plane {plane.name!r} line {line.name!r}: "
                         f"{len(events) if keep else sum(1 for _ in line.events)} events")
                if is_device and line.name in OP_LINES and not devices:
                    _describe_heavy(line, describe)
            if is_device and line.name in OP_LINES:
                devices.setdefault(plane.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host.extend(ev for ev in events if ev[0].startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda ev: ev[1])}


def _describe_heavy(line, describe, limit=6):
    """How the trace names the first device's heaviest operations, in full:
    what a reader needs to write a metric's name pattern."""
    acc = {}
    for e in line.events:
        slot = acc.setdefault(op_name(e.name).partition(".")[0], [0.0, e.name])
        slot[0] += e.duration_ns * 1e-9
    for key, (secs, text) in sorted(acc.items(), key=lambda kv: -kv[1][0])[:limit]:
        describe(f"heavy op {key!r}: {secs:.4f} s, e.g. {text[:700]!r}")


def op_name(text):
    """What the reduction calls a device operation. The TPU trace names an
    operation by its whole HLO instruction, ``%name = shape opcode(...)``;
    kept are the name, the result's first shape and, for a custom call (a
    Pallas kernel among them), the word ``custom-call``. The instruction
    carries no kernel name: a Pallas call is named after the scope or the
    function around it (``attn``, ``shard_map``, ``fused``, ``closed_call``)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    parts = [head.lstrip("%")] + ([shape.group(0)] if shape else [])
    if re.search(r"\bcustom-call\(", rest):
        parts.append("custom-call")
    return " ".join(parts)


def clip(events, t0, t1):
    """Events cut to the window [t0, t1)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(intervals):
    """Merged, sorted (start, end) of possibly overlapping (start, end)."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of merged ``intervals`` not covered by merged ``holes``."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def window_of(trace):
    """(t0, t1) of the traced window: the harness's ``chipbench/window``
    span where the trace has it, else from the first device operation's
    start to the last one's end."""
    marks = [ev for ev in trace["host"] if ev[0] == WINDOW_SPAN]
    if marks:
        return marks[0][1], marks[0][1] + marks[0][2]
    starts = [s for evs in trace["devices"].values() for _, s, _ in evs]
    ends = [s + d for evs in trace["devices"].values() for _, s, d in evs]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(trace, t0, t1):
    """Seconds in which an operation ran on the device (the union of the
    operation intervals inside the window), averaged over the devices."""
    per_dev = [total(union(_spans(clip(evs, t0, t1)))) for evs in trace["devices"].values()]
    return sum(per_dev) / len(per_dev)


def kernel_seconds(trace, pattern, t0, t1):
    """(seconds, calls): device time of the operations whose name matches
    ``pattern`` (a regular expression, searched), mean over devices."""
    rx = re.compile(pattern)
    secs = calls = 0
    for evs in trace["devices"].values():
        hit = [ev for ev in clip(evs, t0, t1) if rx.search(ev[0])]
        secs += sum(d for _, _, d in hit)
        calls += len(hit)
    n = len(trace["devices"])
    return secs / n, calls / n


def collective_seconds(trace, t0, t1):
    """(all, exposed): seconds a collective operation ran, and the part of
    them during which no other operation ran on that device; mean over
    devices."""
    both = [0.0, 0.0]
    for evs in trace["devices"].values():
        evs = clip(evs, t0, t1)
        coll = union(_spans([ev for ev in evs if COLLECTIVE.match(ev[0])]))
        comp = union(_spans([ev for ev in evs if not COLLECTIVE.match(ev[0])]))
        both[0] += total(coll)
        both[1] += total(subtract(coll, comp))
    n = len(trace["devices"])
    return both[0] / n, both[1] / n


def top_ops(trace, t0, t1, limit=10):
    """[[name, seconds], ...]: the operations that took most device time,
    instances folded (``fusion.123 bf16[4,256,1280]`` and ``fusion.7.remat
    bf16[4,256,1280]`` are one row: same kind, same result shape), mean over
    devices."""
    acc = {}
    for evs in trace["devices"].values():
        for name, _, d in clip(evs, t0, t1):
            stem, _, rest = name.partition(" ")
            key = (re.sub(r"(\.remat\d*|\.clone|\.\d+)+$", "", stem) + " " + rest).strip()
            acc[key] = acc.get(key, 0.0) + d
    n = len(trace["devices"])
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / n] for k, v in ranked]


def idle_gaps(trace, t0, t1, limit=10):
    """[[what the host was doing, seconds], ...]: the idle time of the first
    device, attributed to the benchmark's host span that covers most of each
    gap (``no host span`` where none does), summed by span name."""
    dev = sorted(trace["devices"])[0]
    busy = union(_spans(clip(trace["devices"][dev], t0, t1)))
    gaps = subtract([(t0, t1)], busy)
    host = [ev for ev in clip(trace["host"], t0, t1) if ev[0] != WINDOW_SPAN]
    acc = {}
    for a, b in gaps:
        best, best_cover = "no host span", 0.0
        for name, s, d in host:
            cover = min(b, s + d) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
        acc[best] = acc.get(best, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:limit]]


def span_self_seconds(trace, name, t0, t1):
    """Self time of the host spans called ``name``: their duration minus
    the part other benchmark spans inside them cover."""
    host = [ev for ev in clip(trace["host"], t0, t1) if ev[0] != WINDOW_SPAN]
    mine = union(_spans([ev for ev in host if ev[0] == name]))
    inner = []
    for a, b in mine:
        inner += [(max(a, s), min(b, s + d)) for n, s, d in host
                  if n != name and s >= a and s + d <= b]
    return total(subtract(mine, union(inner)))


def summarize(trace):
    """What the harness keeps of a trace: window, busy seconds, breakdown."""
    t0, t1 = window_of(trace)
    return {"t0": t0, "t1": t1, "window_s": t1 - t0, "busy_s": busy_seconds(trace, t0, t1),
            "breakdown": {"device_ops": top_ops(trace, t0, t1),
                          "idle_gaps": idle_gaps(trace, t0, t1)}}
