#!/usr/bin/env python3
"""Which product each async all-gather of a compiled TPU step rides.

    python tools/gather_riders.py <compiled step's text[.gz]> [--min-mb 1] [--all]
    python tools/gather_riders.py <text> --ops <device time by operation, JSON>

The TPU compiler runs an all-gather asynchronously as three kinds of fusion
in the entry computation: an ``async-collective-start``, any number of
``async_collective_fusion`` steps, and an ``async-collective-done``. A step
fusion's FIRST output is whatever rode along (a norm's statistic, a bias's
sum), but its called computation holds the gather's own ``all-gather`` step
beside the product the compiler overlaps it with. So this reads the called
computations, not the entry's result shapes: a gather's fusions are found by
the ``channel_id`` of the ``all-gather`` inside them, and a rider is a
``convolution`` (the TPU's matrix product) or a Pallas call inside a step
fusion, named by its ``op_name``.

For every gather: the gathered shape, the ``op_name`` of its consumer,
forward or backward (``transpose(`` in that name), the riders, and how many
entry instructions lie between start and done. A gather with no rider has
nothing beside it but what the scheduler happened to leave between its start
and its done. Blocking ``all-gather`` instructions of the entry (nothing can
run beside them) are listed after the async ones.

With ``--ops`` (a device trace's seconds by operation name, as
``chipbench.xplane.read`` names operations, summed by a builder's script on
the chip) it prints the device's time by what each instruction IS to the
gathers: a step fusion's time beside the plain product's says how much of a
gather its rider hid.

A builder's tool on a text from ``compiled.as_text()`` (``chipbench.rehearse``
compiles a cell's step for a described chip); not a benchmark file.
"""

import argparse
import collections
import gzip
import json
import math
import re
import sys

_SIZES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1,
          "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2}
_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_SHAPE = re.compile(r"^(\w+)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_GATHER = re.compile(r" all-gather\(.*?channel_id=(\d+)")


def _shape_bytes(shape):
    m = _SHAPE.match(shape)
    if not m or m.group(1) not in _SIZES:
        return 0
    dims = [int(d) for d in m.group(2).split(",") if d]
    return math.prod(dims) * _SIZES[m.group(1)]


def _result_and_op(rest):
    """``(result type text, opcode)`` of an instruction's right-hand side."""
    if rest.startswith("("):  # a tuple type: to its matching parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, tail = rest.partition(" ")
    return shape, tail.split("(", 1)[0]


def computations(text):
    """``{name: [(instruction, result type, opcode, line)]}`` and the entry's name."""
    out, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line) if cur is not None else None
        if m:
            shape, op = _result_and_op(m.group(2))
            cur.append((m.group(1), shape, op, line))
    return out, entry


def _plain_shape(shape):
    m = _SHAPE.match(shape)
    return f"{m.group(1)}[{m.group(2)}]" if m else shape


def _inside(comps, name):
    """What a called computation holds: its all-gathers by channel
    ``{channel: (gathered shape, op_name)}``, its products and kernels
    ``[(op_name, shape)]``, and whether it starts or ends an async collective."""
    gathers, riders, role = {}, [], "step"
    for _, shape, op, line in comps.get(name, ()):
        names = _OP_NAME.findall(line)
        if op == "all-gather":
            gathers[int(_GATHER.search(line).group(1))] = (_plain_shape(shape),
                                                           names[0] if names else "")
        elif op == "convolution" or "tpu_custom_call" in line:
            riders.append((names[0] if names else op, _plain_shape(shape)))
        elif op == "fusion":
            # a product nested one call deeper
            called = re.search(r"calls=%([\w.\-]+)", line)
            if called:
                riders += _inside(comps, called.group(1))[1]
        if "AsyncCollectiveStart" in line:
            role = "start"
        elif "AsyncCollectiveDone" in line:
            role = "done"
    return gathers, riders, role


def gathers(text):
    """One dict for every all-gather of the entry computation, in program
    order of its start: ``shape``, ``bytes``, ``op_name`` (its consumer, or
    the explicit gather's own scope), ``backward``, ``blocking`` (a plain
    ``all-gather`` instruction), ``riders`` (``[(op_name, shape)]`` of the
    products and kernels inside the step fusions between start and done),
    ``at`` (the start's place in the entry) and ``between`` (entry
    instructions from start to done)."""
    comps, entry = computations(text)
    open_, out = {}, []
    for at, (name, shape, op, line) in enumerate(comps[entry]):
        if op == "all-gather" or op == "all-gather-start":
            names = _OP_NAME.findall(line)
            if op == "all-gather-start":  # ((operand), result): the last type is the result's
                shape = re.findall(r"\w+\[[\d,]*\]", shape)[-1]
            out.append({"shape": _plain_shape(shape), "bytes": _shape_bytes(shape),
                        "op_name": names[0] if names else "", "blocking": op == "all-gather",
                        "riders": [], "between": 0, "at": at})
            continue
        called = re.search(r"calls=%([\w.\-]+)", line) if op == "fusion" else None
        if not called:
            continue
        inside, riders, role = _inside(comps, called.group(1))
        for channel, (gshape, op_name) in inside.items():
            if role == "start":
                open_[channel] = {"shape": gshape, "bytes": _shape_bytes(gshape),
                                  "op_name": op_name, "blocking": False, "riders": [],
                                  "between": 0, "at": at}
                out.append(open_[channel])
            elif channel in open_ and role == "done":
                g = open_.pop(channel)
                g["between"] = at - g["at"] - 1
            elif channel in open_:
                open_[channel]["riders"] += riders
    for g in out:
        g["backward"] = "transpose(" in g["op_name"]
    return out


def leaf_of(op_name):
    """``layer_3/mlp/up_proj`` from an op_name that holds a layer's path; an
    explicit gather (``runtime/zero/gather_order.py``) names its leaf under
    the scope ``zero3_gather/``."""
    tail = op_name.split("zero3_gather/")[-1]
    m = re.search(r"(layer_\d+)/((?:\w+/)*?\w+_proj)", tail)
    if m:
        return f"{m.group(1)}/{m.group(2)}"
    parts = [p for p in tail.split("/") if not p.endswith(")")]
    return parts[-2] if len(parts) > 1 else tail


def rider_of(op_name):
    """A rider's short name: forward or backward (``transpose(`` in the
    op_name) and the leaf its product belongs to; its shape tells a ``dW``
    (the weight's) from a ``dX`` (the activation's)."""
    return ("bwd " if "transpose(" in op_name else "fwd ") + leaf_of(op_name)


def _no_layer(name):
    """``name`` with the layer numbers taken out."""
    return re.sub(r"layer_\d+/", "", name)


def table(found, min_bytes=1 << 20):
    """Rows ``(kind of gather, direction, kind of rider, count)`` with the
    layer numbers taken out, the table ISSUE 51's Motivation holds."""
    rows = collections.Counter()
    for g in found:
        if g["bytes"] < min_bytes:
            continue
        kind = ("blocking " if g["blocking"] else "") + _no_layer(leaf_of(g["op_name"]))
        big = max(g["riders"], key=lambda r: _shape_bytes(r[1]), default=None)
        rider = "nothing" if big is None else f"{_no_layer(rider_of(big[0]))} {big[1]}"
        if len(g["riders"]) > 1:
            rider += f" (+{len(g['riders']) - 1})"
        rows[kind, g["shape"], "backward" if g["backward"] else "forward", rider] += 1
    return rows


def kinds(text):
    """``{entry instruction: (kind, detail)}``: what each instruction of the
    entry computation is to the gathers. Kinds: ``gather start`` / ``gather
    done`` / ``gather step`` (detail: the gathered leaf, then for a step the
    products that ride), ``product`` (a fusion holding a matrix product and no
    collective), ``kernel`` (a Pallas call), ``reduce-scatter`` (the TPU
    compiler's ``all-reduce-scatter`` fusion), ``all-reduce``, ``all-gather``
    (blocking), ``other``."""
    comps, entry = computations(text)
    side = lambda name: _no_layer(rider_of(name))
    out = {}
    for name, shape, op, line in comps[entry]:
        names = _OP_NAME.findall(line)
        own = side(names[0]) if names else ""
        if op in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
            out[name] = (op, own)
        elif op == "custom-call" and "tpu_custom_call" in line:
            out[name] = ("kernel", own)
        elif op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line).group(1)
            inside, riders, role = _inside(comps, called)
            rode = " + ".join(sorted({side(r) + " " + shape_ for r, shape_ in riders}))
            if inside:
                gathered = " & ".join(sorted(side(op_name) for _, op_name in inside.values()))
                if role == "step":
                    out[name] = ("gather step", f"{gathered} <- {rode or 'nothing'}")
                else:
                    out[name] = (f"gather {role}", gathered)
            elif "all-reduce-scatter" in line:
                out[name] = ("reduce-scatter", own)
            elif riders:
                out[name] = ("product", rode)
            else:
                out[name] = ("other", _plain_shape(re.findall(r"\w+\[[\d,]*\]", shape)[0])
                             if "[" in shape else shape)
        else:
            out[name] = ("other", op)
    return out


def timed(text, ops, steps=1):
    """Device time by kind: ``ops`` maps a trace's operation name (the
    instruction's name, then whatever else) to ``(seconds, occurrences)``;
    returns ``{(kind, detail): [ms a step, occurrences a step]}``."""
    by_name = kinds(text)
    acc = collections.defaultdict(lambda: [0.0, 0.0])
    for name, (seconds, count) in ops.items():
        kind = by_name.get(name.split(" ")[0].lstrip("%"), ("not in the text", name.split(".")[0]))
        acc[kind][0] += seconds * 1e3 / steps
        acc[kind][1] += count / steps
    return dict(acc)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("text", help="a file holding compiled.as_text()")
    ap.add_argument("--min-mb", type=float, default=1.0,
                    help="leave out gathers under this many gathered MB")
    ap.add_argument("--all", action="store_true", help="one line a gather, in program order")
    ap.add_argument("--ops", help="a JSON file {'steps': n, 'ops': {operation name of a device "
                    "trace: [seconds, occurrences]}}: print the device's time by kind instead")
    ap.add_argument("--top", type=int, default=40, help="with --ops: rows printed")
    args = ap.parse_args(argv)
    opener = gzip.open if args.text.endswith(".gz") else open
    with opener(args.text, "rt") as f:
        text = f.read()
    if args.ops:
        with open(args.ops) as f:
            traced = json.load(f)
        rows = timed(text, traced["ops"], traced.get("steps", 1))
        by_kind = collections.Counter()
        for (kind, _), (ms, _) in rows.items():
            by_kind[kind] += ms
        print(f"{sum(by_kind.values()):9.2f} ms a step on the device; by kind:")
        for kind, ms in by_kind.most_common():
            print(f"{ms:9.2f}  {kind}")
        print(f"{'ms/step':>9s} {'calls':>6s} {'ms/call':>8s}  kind: detail")
        for (kind, detail), (ms, calls) in sorted(rows.items(), key=lambda kv: -kv[1][0])[:args.top]:
            print(f"{ms:9.2f} {calls:6.0f} {ms / max(calls, 1):8.3f}  {kind}: {detail[:150]}")
        return 0
    found = gathers(text)
    floor = int(args.min_mb * 2**20)
    large = [g for g in found if g["bytes"] >= floor]
    if args.all:
        for g in large:
            print(f"{g['at']:6d} {'blocking' if g['blocking'] else 'async':8s} {g['shape']:22s} "
                  f"{'bwd' if g['backward'] else 'fwd'} {leaf_of(g['op_name']):32s} between={g['between']:3d} "
                  f"riders={[rider_of(r[0]) + ' ' + r[1] for r in g['riders']]}")
    print(f"{len(large)} all-gathers of {args.min_mb} MB or more: "
          f"{sum(not g['blocking'] for g in large)} async, "
          f"{sum(g['blocking'] for g in large)} blocking, "
          f"{sum(not g['riders'] and not g['blocking'] for g in large)} async with no rider")
    print(f"{'gather':28s} {'shape':20s} {'direction':9s} {'count':>5s}  rides")
    for (kind, shape, direction, rider), n in sorted(table(found, floor).items()):
        print(f"{kind:28s} {shape:20s} {direction:9s} {n:5d}  {rider}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
