"""What ``trace_reduce.load`` leaves out of a run's ``.xplane.pb``, for the
per-layer metrics that read the program's own marks:

- the program's host spans (``dstpu/...`` ``TraceAnnotation``s from
  ``TelemetrySink.span``), beside the benchmark's own (``chipbench/...``);
- per device operation, the *scope path* it was traced under
  (``jit(train_step)/.../optimizer/mul``). The TPU trace keeps it in the
  ``tf_op`` stat of the event's metadata, which ``jax.profiler.ProfileData``
  does not expose (its ``event.stats`` holds the three per-occurrence stats
  only), so the file is read as the protocol buffer it is, with the few
  fields needed declared here.

``read`` gives plain lists like ``trace_reduce.load``'s, on the same clock;
the arithmetic on them (``self_times``, ``device_share``, ``idle_by_host``)
is tested on a small recorded fixture without a chip. ``run_trace(obs)``
finds the file of the run being reduced; every reader that cannot find
what it reads (no trace, no such span, scope or kernel: the parent commit
has none) returns ``None`` and the metric is left out of the line.
"""

import glob
import os
import re

from chipbench import trace_reduce

# host spans kept: the program's and the benchmark's own
PROGRAM_SPAN_PREFIX = "dstpu/"
HOST_PREFIXES = (PROGRAM_SPAN_PREFIX, trace_reduce.HOST_SPAN_PREFIX)
SCOPE_STAT = "tf_op"

_XSPACE = None


def _xspace_class():
    """The XSpace message (tsl/profiler/protobuf/xplane.proto), declared with
    the fields read here only; names are bytes (an HLO instruction's text is
    not promised to be UTF-8)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                              package="chipbench_xplane", syntax="proto3")

    def message(name, *fields):
        m = file.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(name=fname, number=number, type=ftype, type_name=type_name,
                        label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)

    def sub(name):
        return ".chipbench_xplane." + name

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("str_value", 5, F.TYPE_BYTES, False, None),
            ("ref_value", 7, F.TYPE_UINT64, False, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("offset_ps", 2, F.TYPE_INT64, False, None),
            ("duration_ps", 3, F.TYPE_INT64, False, None))
    message("XLine", ("name", 2, F.TYPE_BYTES, False, None),
            ("timestamp_ns", 3, F.TYPE_INT64, False, None),
            ("events", 4, F.TYPE_MESSAGE, True, sub("XEvent")))
    message("XEventMetadata", ("name", 2, F.TYPE_BYTES, False, None),
            ("stats", 5, F.TYPE_MESSAGE, True, sub("XStat")))
    message("XStatMetadata", ("name", 2, F.TYPE_BYTES, False, None))
    # a map field on the wire is a repeated {key = 1, value = 2} message
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, F.TYPE_MESSAGE, False, sub("XEventMetadata")))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, F.TYPE_MESSAGE, False, sub("XStatMetadata")))
    message("XPlane", ("name", 2, F.TYPE_BYTES, False, None),
            ("lines", 3, F.TYPE_MESSAGE, True, sub("XLine")),
            ("event_metadata", 4, F.TYPE_MESSAGE, True, sub("EventMetadataEntry")),
            ("stat_metadata", 5, F.TYPE_MESSAGE, True, sub("StatMetadataEntry")))
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, True, sub("XPlane")))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    _XSPACE = message_factory.GetMessageClass(pool.FindMessageTypeByName("chipbench_xplane.XSpace"))
    return _XSPACE


def _text(raw):
    return raw.decode("utf-8", "replace")


def read(path):
    """``{"devices": {plane: [(name, start_s, dur_s, scope), ...]}, "host":
    [(name, start_s, dur_s), ...]}`` of one ``.xplane.pb``: per device the
    operations of its ``XLA Ops`` line (``name`` as ``trace_reduce.op_name``
    gives it, ``scope`` the scope path or ``""``), and the program's and the
    benchmark's host spans, sorted by start."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, host = {}, []
    for plane in space.planes:
        pname = _text(plane.name)
        is_device = pname.startswith("/device:") and "CUSTOM" not in pname.upper()
        if not (is_device or pname.startswith("/host:")):
            continue
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        meta = {}
        for entry in plane.event_metadata:
            name, scope = _text(entry.value.name), ""
            if is_device:
                for st in entry.value.stats:
                    if stat_names.get(st.metadata_id) == SCOPE_STAT:
                        scope = (_text(st.str_value) if st.str_value
                                 else stat_names.get(st.ref_value, ""))
                name = trace_reduce.op_name(name)
            meta[entry.key] = (name, scope)
        for line in plane.lines:
            if is_device and _text(line.name) not in trace_reduce.OP_LINES:
                continue
            base = line.timestamp_ns * 1e-9
            for ev in line.events:
                name, scope = meta.get(ev.metadata_id, ("", ""))
                start, dur = base + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12
                if is_device:
                    devices.setdefault(pname, []).append((name, start, dur, scope))
                elif name.startswith(HOST_PREFIXES):
                    host.append((name, start, dur))
    return {"devices": devices, "host": sorted(host, key=lambda ev: ev[1])}


def run_xplane():
    """The ``.xplane.pb`` of the run being reduced, or ``None``. The harness
    clears ``.chipbench_run/<cell>`` before a run and removes it after the
    reducers, so the newest trace under it is this run's."""
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        ".chipbench_run", "*", "trace", "plugins", "profile", "*", "*.xplane.pb")
    return max(glob.glob(root), key=os.path.getmtime, default=None)


def run_trace(obs):
    """The trace of the run being reduced, read once a process and kept in
    ``obs``; ``None`` for an untraced run or one with no device operation
    (a rehearsal on the CPU)."""
    if "program_trace" not in obs:
        obs["program_trace"] = None
        summary, path = obs.get("trace_summary"), run_xplane()
        if summary is not None and path is not None:
            trace = read(path)
            trace["t0"], trace["t1"] = summary["t0"], summary["t1"]
            if trace["devices"]:
                obs["program_trace"] = trace
    return obs["program_trace"]


def self_times(events, t0, t1):
    """[(name, scope, self seconds)] of one device's operations inside the
    window: an operation's time minus the time of the operations that ran
    inside it (a ``while`` covers its body), so the self times add up to
    the device's busy time and no share of the window can pass 100%."""
    evs = sorted(((max(s, t0), min(s + d, t1), n, sc) for n, s, d, sc in events
                  if s < t1 and s + d > t0), key=lambda e: (e[0], -e[1]))
    out, stack = [], []  # open operations, outermost first: [end, name, scope, self seconds]
    for a, b, n, sc in evs:
        # what ended before this one started is done; so is one this one
        # outlasts (neighbours that touch, not parent and child)
        while stack and (stack[-1][0] <= a or b > stack[-1][0] + _TOUCH_S):
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][3] -= b - a
        stack.append([b, n, sc, b - a])
    out.extend(tuple(e[1:]) for e in stack)
    return out


# two operations whose ends differ by less than this end together
_TOUCH_S = 1e-9


def device_share(trace, pick):
    """100 x (self time of the operations for which ``pick(name, scope)``
    holds) / traced window, mean over the devices; ``None`` when no device
    ran such an operation."""
    if trace is None:
        return None
    t0, t1 = trace["t0"], trace["t1"]
    if "self_times" not in trace:  # several metrics read one trace
        trace["self_times"] = [self_times(evs, t0, t1) for evs in trace["devices"].values()]
    picked = [s for dev in trace["self_times"] for n, sc, s in dev if pick(n, sc)]
    return 100.0 * sum(picked) / len(trace["devices"]) / (t1 - t0) if picked else None


def in_scope(*scopes):
    """``pick`` for ``device_share``: the operation was traced under one of
    the named scopes (a whole component of its scope path)."""
    rx = re.compile(r"(^|/)(" + "|".join(map(re.escape, scopes)) + r")(/|$)")
    return lambda name, scope: bool(rx.search(scope))


def named(*kernels):
    """``pick``: a Pallas call that carries one of these ``name=``s. The
    TPU trace names the instruction after it (``dstpu_decode_attn.3``); the
    scope path ends in it too."""
    rx = re.compile(r"(^|/)(" + "|".join(map(re.escape, kernels)) + r")([./]|$)")
    return lambda name, scope: bool(rx.search(name.partition(" ")[0]) or rx.search(scope))


# which of the pump's three accounts a host span's idle time belongs to
DISPATCH_SPANS = ("dstpu/sched/dispatch", "dstpu/sched/fetch")
SCHED_PREFIX = "dstpu/sched/"


def idle_by_host(trace):
    """{"dispatch", "sched", "gateway"}: the first device's idle seconds in
    the traced window, split by what the serving pump was doing. Under
    ``sched/dispatch`` or ``sched/fetch``: launch latency, and the tail
    between the device finishing and the host holding the tokens. Under
    any other ``sched/...`` span (``admit``, ``assemble``, ``deliver``, the
    rest of ``step``): the scheduler's host work. Everything else
    (``gateway/admit``, ``gateway/idle``, between spans): the gateway's. The
    three add up to the idle time. ``None`` without program spans."""
    if trace is None or not any(n.startswith(PROGRAM_SPAN_PREFIX) for n, _, _ in trace["host"]):
        return None
    t0, t1 = trace["t0"], trace["t1"]
    dev = sorted(trace["devices"])[0]
    busy = trace_reduce.union([(s, s + d) for _, s, d in trace_reduce.clip(
        [ev[:3] for ev in trace["devices"][dev]], t0, t1)])
    idle = trace_reduce.subtract([(t0, t1)], busy)
    host = trace_reduce.clip(trace["host"], t0, t1)
    dispatch = trace_reduce.union([(s, s + d) for n, s, d in host if n in DISPATCH_SPANS])
    sched = trace_reduce.union([(s, s + d) for n, s, d in host if n.startswith(SCHED_PREFIX)])
    not_dispatch = trace_reduce.subtract(idle, dispatch)
    gateway = trace_reduce.subtract(not_dispatch, sched)
    total = trace_reduce.total
    return {"dispatch": total(idle) - total(not_dispatch),
            "sched": total(not_dispatch) - total(gateway),
            "gateway": total(gateway), "window": t1 - t0}


def pump_idle_pct(obs, account):
    split = idle_by_host(run_trace(obs))
    return None if split is None else 100.0 * split[account] / split["window"]


def setup_seconds(*phases):
    """Seconds this process spent in the named phases of program set-up
    (``compile_cache.stats()``: ``trace``, ``lower``, ``backend``,
    ``cache_read``). They move on compiles only, and a run with a compile
    in its window is not ``correct``, so at reduce time they are the
    set-up's. ``None`` where the program has no such counters."""
    from deepspeed_tpu.utils import compile_cache
    stats = getattr(compile_cache, "stats", None)
    if stats is None:
        return None
    got = stats()
    return sum(got[p + "_s"] for p in phases)
