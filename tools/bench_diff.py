#!/usr/bin/env python
"""Diff a fresh BENCH_*.json against the prior round's and flag regressions.

Two saved ``python bench.py`` outputs (raw, or driver-wrapped:
``{"n", "rc", "tail", "parsed": {metric, value, unit, vs_baseline, extra}}``)
are otherwise compared by eyeballing two JSON files. No round's JSON is
kept in the repo — the driver's record of a PR is ``PERF_LEDGER.jsonl`` —
so save the outputs you want compared. This tool walks every numeric leaf shared by
two rounds and prints the delta, flagging moves past a threshold in the
metric's BAD direction (lower-is-better names — ms/latency/stall/error —
regress upward; everything else regresses downward).

    python tools/bench_diff.py NEW.json [OLD.json] [--threshold 0.05] [--strict]

``OLD`` defaults to the highest-numbered ``BENCH_r*.json`` lying in the repo
root other than ``NEW`` itself, if there is one. Accepts driver-wrapped files, raw bench JSON
lines (the ``python bench.py`` stdout), and files whose last line is the
JSON (mixed logs). Exit code is 0 unless ``--strict`` is given and a
regression was flagged — the default mode is ADVISORY (ci_check.sh runs it
that way: a slow leg should be seen, not block unrelated work).
"""

import argparse
import glob
import json
import os
import re
import sys

# underscore-tokens marking lower-is-better metrics; everything else is
# higher-is-better. Tokenized (not substring) matching: "_s" as a substring
# would misfile tokens_per_sec_chip. "p95"/"p50" alone are ambiguous
# (ttft_ms_p95 carries "ms" anyway), so direction keys on unit-ish tokens.
# hier_kv leg notes: restore_ms/cold_prefill_ms regress upward via the "ms"
# token; "spills"/"dropped" mark host-tier pressure (a round that spills or
# drops more at the same stream is a capacity regression); tier_hit_rate /
# restores / tokens_per_sec keep the higher-is-better default.
# multi_lora leg notes: adapter swap_ms rides "ms"; "swaps"/"evicts" mark
# load/rotation churn (more swaps at the same round-robin stream = worse
# amortization); speedup_vs_rotation / adapter_hit_rate / tokens_per_sec
# keep the higher-is-better default, and crossover_k is higher-better too
# (rotation needs LONGER per-tenant runs before it catches the paged path).
# disagg leg notes: migration_ms/itl_*_ms ride "ms"; "degradation" marks
# the ITL-p95 load-doubling factors (flat == 1.0 is the goal, growth is
# the regression — "ratio" itself stays direction-neutral: the existing
# ttft_p95_ratio_rotation_over_paged / slot_ratio_at_equal_hbm are
# higher-better); "pending"/"failed" mark handoff backpressure/losses (a
# round that parks or fails more handoffs at the same stream regressed);
# migrations/tokens_per_sec keep the higher-is-better default.
# moe leg notes: "loads"/"replays" mark cold-expert paging churn (more
# hot-loads or replay dispatches at the same stream = worse residency
# amortization; "evicts" already rides the adapter token), and "programs"
# marks mid-stream compile counts (new_programs_mid_stream must stay 0);
# tokens_per_sec / resident_fraction / *_over_* ratios keep the
# higher-is-better default.
# autoscale leg notes: "preempted"/"resize" mark brownout preemptions and
# elastic fleet churn (more preempted in-flight work or more resizes at
# the same stream = a twitchier controller); "shed"/"programs" already
# ride their tokens, and ttft_p95_static_over_autoscaled keeps the
# higher-is-better ratio default.
# multihost leg notes: "sick" marks router health churn (a worker going
# sick during the same fixed stream is a fleet regression) and "retries"
# marks shed-and-retry re-placements; net_bytes_{in,out} read lower-is-
# better via the compound below (more store bytes moved for an identical
# stream = worse placement locality); tokens_per_sec / scaling_efficiency
# / speedup_vs_single_process keep the higher-is-better default.
_LOWER_TOKENS = {"ms", "latency", "stall", "err", "error", "errors", "wait",
                 "shed", "evict", "evictions", "evicts", "miss", "misses",
                 "s", "seconds", "loss", "ppl", "perplexity", "spill",
                 "spills", "dropped", "swaps", "degradation", "pending",
                 "failed", "loads", "replays", "programs", "gap",
                 "ttft", "itl", "preempted", "resize", "resizes",
                 "sick", "retries"}
# long_context leg notes: "ttft"/"itl" read lower-is-better on their own so
# ms-less variants (ttft_p50, itl_p95) resolve too; new_programs_after_first_ctx
# rides "programs" (a length mix that compiles mid-stream is the regression);
# extents_spanned / seq_shards are descriptive, not directional.
# capacity-leg directionality: "gap" (host_gap_total_s — device idle time)
# reads lower-is-better; mfu / hbm_bw_util / goodput_fraction /
# instrumented_ratio stay on the higher-is-better default, so a sampled-
# fencing overhead regression (ratio falling) flags without special-casing


def _lower_better(path):
    leaf = path.split(".")[-1].lower()
    # explicit compounds: bytes_per_token (kv/weight traffic), step_ms (the
    # fused_block leg's per-decode-step wall time), and net_bytes (the
    # multihost leg's cross-process store traffic) read lower-is-better
    # even though their leading token alone wouldn't resolve them
    if "bytes_per_token" in leaf or "step_ms" in leaf or "net_bytes" in leaf:
        return True
    return any(tok in _LOWER_TOKENS for tok in leaf.split("_"))


def _load(path):
    """Driver-wrapped, raw JSON, or last-JSON-line log -> the bench record
    {metric, value, unit, vs_baseline, extra}."""
    with open(path) as f:
        text = f.read().strip()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in reversed(text.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
        if doc is None:
            raise ValueError(f"{path}: no JSON object found")
    if isinstance(doc, dict) and "parsed" in doc:
        # driver wrapper: parsed == null means the round crashed before
        # printing its JSON line — say so instead of diffing wrapper fields
        if not isinstance(doc["parsed"], dict):
            raise ValueError(f"{path}: round recorded no parsed metrics "
                             f"(rc={doc.get('rc')}) — the bench crashed; "
                             f"nothing to compare")
        doc = doc["parsed"]
    return doc


def _numeric_leaves(node, prefix=""):
    """Flatten to {dotted.path: float}; skips bools (flags aren't metrics)
    and non-numeric leaves."""
    out = {}
    if isinstance(node, dict):
        for k, v in node.items():
            out.update(_numeric_leaves(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(node, bool):
        pass
    elif isinstance(node, (int, float)):
        out[prefix] = float(node)
    return out


def _default_old(new_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rounds = []
    for p in glob.glob(os.path.join(root, "BENCH_r*.json")):
        if os.path.abspath(p) == os.path.abspath(new_path):
            continue
        m = re.search(r"BENCH_r(\d+)", os.path.basename(p))
        if m:
            rounds.append((int(m.group(1)), p))
    if not rounds:
        return None
    return max(rounds)[1]


def diff(old, new, threshold=0.05):
    """Compare two bench records; returns (rows, regressions) where rows are
    (path, old, new, rel_delta, flag) over the shared numeric leaves."""
    a = _numeric_leaves(old)
    b = _numeric_leaves(new)
    rows = []
    regressions = []
    for path in sorted(set(a) & set(b)):
        va, vb = a[path], b[path]
        if va == vb:
            continue
        rel = (vb - va) / abs(va) if va else float("inf") * (1 if vb > 0 else -1)
        worse = rel > 0 if _lower_better(path) else rel < 0
        flag = worse and abs(rel) >= threshold
        rows.append((path, va, vb, rel, flag))
        if flag:
            regressions.append((path, va, vb, rel))
    return rows, regressions


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("new", help="fresh bench JSON (driver-wrapped or raw line)")
    ap.add_argument("old", nargs="?", default=None,
                    help="prior round (default: latest BENCH_r*.json in repo root)")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative move flagged as a regression (default 0.05)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any regression is flagged (default: advisory)")
    args = ap.parse_args(argv)

    old_path = args.old or _default_old(args.new)
    if old_path is None:
        print("bench_diff: no prior BENCH_r*.json found; nothing to compare")
        return 0
    try:
        old, new = _load(old_path), _load(args.new)
    except (OSError, ValueError) as e:
        print(f"bench_diff: {e}; skipping comparison")
        return 0
    if old.get("skipped") or new.get("skipped"):
        which = "old" if old.get("skipped") else "new"
        print(f"bench_diff: {which} round was a structured skip "
              f"({(old if which == 'old' else new).get('reason', '?')}); "
              f"no comparable numbers")
        return 0

    if old.get("metric") != new.get("metric"):
        # different headline metrics (e.g. train MFU vs serving tok/s):
        # top-level value/vs_baseline are not comparable — diff extra.* only
        print(f"bench_diff: headline metrics differ ({old.get('metric')!r} vs "
              f"{new.get('metric')!r}); comparing extra.* leaves only")
        old = {"extra": old.get("extra", {})}
        new = {"extra": new.get("extra", {})}
    print(f"bench_diff: {os.path.basename(old_path)} -> "
          f"{os.path.basename(args.new)} (threshold {args.threshold:.0%})")
    rows, regressions = diff(old, new, args.threshold)
    if not rows:
        print("  no shared numeric metrics changed")
        return 0
    for path, va, vb, rel, flag in rows:
        improved = rel < 0 if _lower_better(path) else rel > 0
        mark = "REGRESSION" if flag else ("improved" if improved
                                          else "worse (under threshold)")
        print(f"  {'!! ' if flag else '   '}{path}: {va:g} -> {vb:g} "
              f"({rel:+.1%}) {mark}")
    if regressions:
        print(f"bench_diff: {len(regressions)} metric(s) regressed past "
              f"{args.threshold:.0%}")
        if args.strict:
            return 1
    else:
        print("bench_diff: no regressions past threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
