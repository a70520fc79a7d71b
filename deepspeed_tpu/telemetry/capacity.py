"""Serving capacity accounting: per-program roofline registry, sampled
fenced dispatch timing, host-gap attribution, and goodput.

Training has reported MFU since PR 1 (``runtime/engine``'s interval
gauges), but serving had no utilization accounting at all — an operator
could see tok/s fall without any way to tell *device is slow* apart from
*host is starving the device*. This module closes that gap with three
cooperating pieces, all owned by the scheduler's pump thread and all
no-ops when the telemetry sink is disabled:

- :class:`CapacityModel` — analytic FLOPs and HBM bytes per dispatched
  step program, derived from the model config and the dispatch's batch
  shape (live rows, per-row context, query columns, K substeps). The
  numbers count what the DEVICE executes (the full padded slot block, or
  its column and its chunk where the scheduler splits a chunk sync),
  which is what makes the live MFU/bandwidth gauges roofline-honest and
  lets a test cross-check them against ``jit(...).lower().cost_analysis()``.

- :class:`CapacityMeter` — the per-compiled-program registry. Every
  program the scheduler builds (fused/spec/copy/tier_slice/
  tier_restore, LoRA variants included) registers here at warm/build
  time; a *sampled* fenced-timing window (every ``sample_every``-th sync,
  default 1/32 — the async dispatch pipeline is never fenced on the hot
  path) turns one dispatch's wall time into ``serving/mfu``,
  ``serving/hbm_bw_util``, and a per-program-kind roofline classification
  gauge (``serving/roofline/<kind>``: analytic arithmetic intensity over
  the machine balance — >= 1 means compute-bound, < 1 bandwidth-bound).
  Sampling uses only ``block_until_ready`` on arrays the program already
  produced, so it adds ZERO new XLA programs after warmup. The meter also
  owns goodput: useful vs wasted token-FLOPs (speculative rejected
  columns, MoE miss-replay dispatches, migration/restore traffic
  converted at the machine balance) rolled into the
  ``serving/goodput_fraction`` gauge.

- :class:`HostGapTracker` — device-idle attribution for the pump thread.
  The scheduler's pump is one sync deep: it launches sync N+1 before it
  lands sync N, so where it runs ahead the device has work queued when a
  fetch returns and the gap is 0 (one 0.0 observation a sync, no bucket
  counters). Where the pump is serial (a drafter, expert offload, paged
  extents, a capacity-sampled fence: ``DecodeScheduler._lands_first``) the
  gap between one sync's fence and the next dispatch is pure host
  time; the scheduler's spans (``sched/admit``, ``sched/trie_probe``,
  ``sched/assemble``, ``sched/deliver``, ``sched/tier_transfer``: the same
  boundaries a profiler capture shows) stamp their sections into the open
  gap and the tracker emits a ``serving/host_gap_ms`` histogram plus per-bucket
  ``serving/host_gap/<bucket>_ms`` counters whose sum equals the measured
  gap exactly (residue lands in ``other``; over-attribution from timer
  overlap is scaled back proportionally).

Everything here is stdlib + numpy on the host side; the only device
interaction is the sampled fence.
"""

import numpy as np

# host-gap attribution buckets, in emission order. "other" is the residue
# between the measured gap and the stamped sections — it absorbs pump-loop
# overhead, GIL waits, and anything not explicitly instrumented.
GAP_BUCKETS = ("admission", "trie_probe", "sampling_host", "on_token",
               "tier_transfer", "other")

# the scheduler's spans (``TelemetrySink.span``) that feed the tracker, and
# the bucket each one's time belongs to. ``sched/dispatch`` (its start
# closes the gap) and ``sched/fetch`` (its end opens one) carry no bucket.
SPAN_BUCKETS = {"sched/admit": "admission", "sched/trie_probe": "trie_probe",
                "sched/assemble": "sampling_host", "sched/deliver": "on_token",
                "sched/tier_transfer": "tier_transfer"}

_GATED_ACTS = ("swiglu", "geglu")


def _cfg(model_config, name, default=None):
    return getattr(model_config, name, default)


class CapacityModel:
    """Analytic FLOPs/HBM-bytes for one transformer step dispatch.

    All coefficients are precomputed from the model config at build so the
    per-sample cost is a handful of float multiplies. ``matmul_flops_per_col``
    counts every projection, the ACTIVE expert MLPs (``moe_top_k`` of
    ``num_experts``; dense models count one), and the LM head — per query
    column, full slot block (the program computes padded rows too).
    Attention score/value FLOPs scale with each live row's context and are
    added per dispatch."""

    __slots__ = ("matmul_flops_per_col", "attn_flops_per_ctx_tok",
                 "weight_read_bytes", "kv_bytes_per_token", "num_slots")

    def __init__(self, model_config, kv_bytes_per_token, num_slots,
                 tp_size=1, ep_size=1):
        h = int(_cfg(model_config, "hidden_size", 0) or 0)
        L = int(_cfg(model_config, "num_layers", 0) or 0)
        nh = int(_cfg(model_config, "num_heads", 1) or 1)
        kvh = int(_cfg(model_config, "kv_heads", nh) or nh)
        hd = int(_cfg(model_config, "head_size", max(1, h // max(1, nh))))
        ffn = int(_cfg(model_config, "ffn_size", 4 * h) or 4 * h)
        V = int(_cfg(model_config, "vocab_size", 0) or 0)
        E = int(_cfg(model_config, "num_experts", 0) or 0)
        topk = int(_cfg(model_config, "moe_top_k", 1) or 1)
        act = str(_cfg(model_config, "activation", "gelu"))
        mlp_mats = 3 if act in _GATED_ACTS else 2

        attn_proj = L * (h * hd * (nh + 2 * kvh)  # qkv
                         + nh * hd * h)           # o
        mlp_active = L * mlp_mats * h * ffn * (min(topk, E) if E > 0 else 1)
        mlp_total = L * mlp_mats * h * ffn * (E if E > 0 else 1)
        lm_head = h * V
        active_params = attn_proj + mlp_active + lm_head
        # 2 FLOPs per MAC; per query column the program runs every matmul
        self.matmul_flops_per_col = 2.0 * active_params
        # QK^T + AV: 2 matmuls x 2 FLOPs x (heads*head_dim) per context
        # token per query column, per layer
        self.attn_flops_per_ctx_tok = 4.0 * L * nh * hd
        # active weights read once per on-device step (the K-step loop
        # re-reads them each iteration); router/embeddings are noise
        if _cfg(model_config, "int8_weights", False):
            # int8 serving streams 1 byte/param plus the fp32 per-group
            # scales (4 bytes per group of `int8_group_size` params) —
            # without this the fused decode-block kind would report half
            # its real hbm_bw_util
            gs = int(_cfg(model_config, "int8_group_size", 0) or 128)
            dtype_bytes = 1.0 + 4.0 / max(1, gs)
        else:
            dtype_bytes = 2  # serving compute dtype is bf16 — the honest
            # upper bound for unknown dtypes too
            try:
                dtype_bytes = np.dtype(
                    np.asarray(0, _cfg(model_config, "dtype")).dtype).itemsize
            except Exception:  # noqa: BLE001 — unknown dtype: keep the bound
                pass
        self.weight_read_bytes = float((attn_proj + mlp_active + lm_head)
                                       * dtype_bytes)
        del mlp_total
        self.kv_bytes_per_token = float(kv_bytes_per_token)
        self.num_slots = int(num_slots)

    def dispatch_cost(self, live_ctx, width, ksteps, kv_mult=1.0, split=False):
        """(flops, hbm_bytes) for ONE step dispatch: ``width`` query columns
        over the full slot block plus ``ksteps - 1`` single-column substeps,
        with ``live_ctx`` the live rows' context lengths (attention + KV
        traffic scale with these). ``kv_mult`` scales the KV-read term for
        the multi-extent block walk — the extent kernel DMAs every extent's
        pool column per KV block, so its KV traffic is ``max_extents``× the
        contiguous walk even when most extents sit behind the mask.
        ``split``: the program runs its first forward as one column over
        every slot and ``width`` columns over one (the scheduler's
        ``_splits_chunk``: the ``fused`` and ``fused_block`` chunk programs):
        ``num_slots + width`` rows, two weight streams (int8 ones with their
        group scales where the model's weights are int8)."""
        ksteps = max(1, int(ksteps))
        width = max(1, int(width))
        first_rows = self.num_slots + width if split else self.num_slots * width
        cols_full = first_rows + self.num_slots * (ksteps - 1)
        ctx_sum = float(np.sum(live_ctx)) if len(live_ctx) else 0.0
        cols_per_row = width + (ksteps - 1)
        flops = (cols_full * self.matmul_flops_per_col
                 + cols_per_row * ctx_sum * self.attn_flops_per_ctx_tok)
        bytes_ = ((ksteps + bool(split)) * self.weight_read_bytes
                  + ksteps * ctx_sum * self.kv_bytes_per_token
                  * max(1.0, float(kv_mult)))
        return flops, bytes_

    def flops_per_token(self, ctx):
        """Per useful token at context ``ctx`` — the goodput unit."""
        return (self.matmul_flops_per_col
                + float(ctx) * self.attn_flops_per_ctx_tok)


def program_shape(key):
    """(width, ksteps) batch shape encoded in a compiled-program cache key:
    fused/fused_block keys carry (chunk, ksteps), spec/spec_block keys
    carry the draft width (the verify program scores ``width`` columns in
    one pass); everything else (copy/tier ops) is shape-accounted
    as a single column. The ``*_block`` kinds are the fused decode-block
    retags — same tuple positions, priced separately in the roofline."""
    if (isinstance(key, tuple) and len(key) >= 5
            and key[0] in ("fused", "fused_block", "fused_ext",
                           "fused_seqp")):
        return int(key[3]), int(key[4])
    if (isinstance(key, tuple) and len(key) >= 4
            and key[0] in ("spec", "spec_block")):
        return int(key[3]), 1
    return 1, 1


def _program_kind(key):
    """Registry kind for a compiled-program cache key: the key's leading
    tag (``fused``/``spec``/``copy``/``tier_slice``/...),
    ``+lora`` suffixed for adapter variants."""
    if isinstance(key, tuple):
        kind = str(key[0])
        if key and key[-1] == "lora":
            kind += "+lora"
        return kind
    return str(key)


class CapacityMeter:
    """Per-compiled-program roofline registry + sampled fenced timing +
    goodput accounting. One instance per scheduler; only built when the
    sink is enabled (the disabled path allocates nothing)."""

    def __init__(self, sink, model, *, peak_flops, peak_hbm_bw, n_devices=1,
                 sample_every=32):
        self.sink = sink
        self.model = model
        self.peak_flops = float(peak_flops) * max(1, int(n_devices))
        self.peak_hbm_bw = float(peak_hbm_bw) * max(1, int(n_devices))
        # machine balance: FLOPs/byte at the roofline ridge point
        self.balance = self.peak_flops / max(1.0, self.peak_hbm_bw)
        self.sample_every = max(1, int(sample_every))
        self.programs = {}      # key -> {"kind", "samples", "mfu", "bw", ...}
        self._by_id = {}        # id(fn) -> key
        self.samples = 0
        # goodput accumulators (token-FLOPs)
        self.useful_flops = 0.0
        self.wasted_flops = 0.0

    # ---------------------------------------------------------------- registry
    def register(self, key, fn):
        """Idempotently register a compiled program under its cache key —
        called from the scheduler's program-cache lookup, so shared-cache
        replicas register the same fn once per scheduler at zero cost."""
        if id(fn) in self._by_id:
            return
        self._by_id[id(fn)] = key
        self.programs.setdefault(
            key, {"kind": _program_kind(key), "samples": 0,
                  "mfu": 0.0, "hbm_bw_util": 0.0, "intensity": 0.0})

    def key_for(self, fn):
        return self._by_id.get(id(fn))

    def should_sample(self, sync_seq):
        return sync_seq % self.sample_every == 0

    # ---------------------------------------------------------------- sampling
    def observe_dispatch(self, key, dur_s, live_ctx, width, ksteps,
                         kv_mult=1.0, split=False):
        """Fold one fenced dispatch sample into the live gauges. ``dur_s``
        is the fence-to-fence wall time of the dispatch alone."""
        if dur_s <= 0.0:
            return
        flops, bytes_ = self.model.dispatch_cost(live_ctx, width, ksteps,
                                                 kv_mult, split)
        mfu = flops / dur_s / self.peak_flops
        bw = bytes_ / dur_s / self.peak_hbm_bw
        intensity = flops / max(1.0, bytes_)
        self.samples += 1
        ent = self.programs.get(key)
        if ent is None:
            self.register(key, object())  # unkeyed dispatch: still account
            ent = self.programs[key]
        ent["samples"] += 1
        ent["mfu"] = mfu
        ent["hbm_bw_util"] = bw
        ent["intensity"] = intensity
        sink = self.sink
        if sink is not None and sink.enabled:
            sink.gauge("serving/mfu", mfu)
            sink.gauge("serving/hbm_bw_util", bw)
            # >= 1: compute-bound (intensity past the ridge); < 1: the
            # program is bandwidth-bound at this batch shape
            sink.gauge(f"serving/roofline/{ent['kind']}",
                       intensity / max(1e-9, self.balance))
            sink.counter("serving/capacity_samples")

    # ---------------------------------------------------------------- goodput
    def account(self, useful_tokens, wasted_tokens=0, ctx=0.0,
                wasted_bytes=0.0):
        """Fold one sync's goodput inputs: tokens delivered to requests,
        tokens computed-then-discarded (rejected speculative columns, MoE
        miss replays), and pure-traffic waste (migration demote/restore,
        evicted-then-recomputed prefixes) in bytes — converted to
        FLOP-equivalents at the machine balance so one fraction covers
        both compute and bandwidth waste."""
        ft = self.model.flops_per_token(ctx)
        self.useful_flops += max(0, useful_tokens) * ft
        wasted = max(0, wasted_tokens) * ft
        if wasted_bytes > 0.0:
            wasted += float(wasted_bytes) * self.balance
        self.wasted_flops += wasted
        sink = self.sink
        if sink is not None and sink.enabled:
            if wasted > 0.0:
                sink.counter("serving/goodput/wasted_token_flops", int(wasted))
            total = self.useful_flops + self.wasted_flops
            if total > 0.0:
                sink.gauge("serving/goodput_fraction",
                           self.useful_flops / total)

    @property
    def goodput_fraction(self):
        total = self.useful_flops + self.wasted_flops
        return self.useful_flops / total if total > 0.0 else 1.0

    # ---------------------------------------------------------------- snapshot
    def program_table(self):
        """Registry view for ``/v1/metrics`` extra surfaces / debugging:
        per-program kind, sample count, last MFU/bandwidth/roofline class."""
        out = {}
        for key, ent in self.programs.items():
            out[str(key)] = {
                "kind": ent["kind"], "samples": ent["samples"],
                "mfu": round(ent["mfu"], 5),
                "hbm_bw_util": round(ent["hbm_bw_util"], 5),
                "bound": ("compute" if ent["intensity"] >= self.balance
                          else "bandwidth"),
            }
        return out


class HostGapTracker:
    """Device-idle (host-gap) attribution for one pump thread.

    Lifecycle per sync of a serial pump: :meth:`sync_end` when a dispatch's
    results are fenced on the host (the device goes idle), host sections
    stamped into the open gap via :meth:`add`, and :meth:`dispatch` the
    moment the next program is handed to the device — closing the gap,
    normalizing attribution so the per-bucket counters sum EXACTLY to the
    measured gap, and emitting the histogram. Of a pump that runs ahead:
    ``unlanded()`` says whether a sync was launched and has not landed. A
    ``sched/dispatch`` that opens while one is (before the previous
    ``sched/fetch`` closes) left the device no gap: ONE observation of 0.0,
    no bucket counters; and a ``sched/fetch`` that closes while the next
    sync is out opens none. Span order alone cannot tell the two pumps
    apart (both alternate dispatch and fetch), hence the callable. The
    scheduler calls none of the methods itself: it passes the tracker as
    the ``observer`` of its spans, and :meth:`span_enter` /
    :meth:`span_exit` make the calls from the span boundaries. All methods
    are single-float arithmetic; the tracker is only constructed when the
    sink is enabled."""

    __slots__ = ("sink", "_unlanded", "_open_ts", "_acc", "_open_buckets", "gaps",
                 "total_gap_s")

    def __init__(self, sink, unlanded=None):
        self.sink = sink
        self._unlanded = unlanded if unlanded is not None else (lambda: False)
        self._open_ts = None
        self._acc = {b: 0.0 for b in GAP_BUCKETS if b != "other"}
        self._open_buckets = []  # the bucket spans open now, outermost first
        self.gaps = 0
        self.total_gap_s = 0.0

    def span_enter(self, name, ts):
        """A scheduler span opened at ``ts``."""
        if name == "sched/dispatch":
            self.dispatch(ts)
        elif name in SPAN_BUCKETS:
            self._open_buckets.append(name)

    def span_exit(self, name, t0, t1):
        """The span that opened at ``t0`` closed at ``t1``. A bucket span
        inside another (the trie probe inside admission) takes its time
        out of the enclosing one."""
        if name == "sched/fetch":
            self.sync_end(t1)
        elif name in SPAN_BUCKETS:
            self._open_buckets.pop()
            outer = self._open_buckets[-1] if self._open_buckets else None
            self.add(SPAN_BUCKETS[name], t1 - t0, steal_from=outer and SPAN_BUCKETS[outer])

    def sync_end(self, ts):
        """Device results just landed on the host: the idle gap opens,
        unless the next sync was launched already."""
        self._open_ts = None if self._unlanded() else ts

    def add(self, bucket, dur, steal_from=None):
        """Stamp ``dur`` seconds of host work into ``bucket``.
        ``steal_from`` moves the time out of an ENCLOSING section (e.g. the
        trie probe runs inside the admission region) so nested timers never
        double-count. The debit may land before the enclosing section is
        stamped — the accumulator is allowed to go negative and is floored
        at :meth:`dispatch`, so stamp order doesn't matter."""
        if dur <= 0.0:
            return
        self._acc[bucket] += dur
        if steal_from is not None:
            self._acc[steal_from] -= dur

    def dispatch(self, ts):
        """The next program is being handed to the device: close the gap,
        emit, and reset. A dispatch behind a sync that has not landed found
        the device busy: a gap of 0.0 and nothing to attribute. A dispatch
        before any sync (warmup) just clears the accumulators."""
        open_ts, self._open_ts = self._open_ts, None
        acc = self._acc
        if open_ts is None:
            for b in acc:
                acc[b] = 0.0
            if self._unlanded():
                self.gaps += 1
                if self.sink is not None and self.sink.enabled:
                    self.sink.histogram("serving/host_gap_ms", 0.0)
            return
        gap = max(0.0, ts - open_ts)
        for b in acc:  # floor deferred-steal debits (see :meth:`add`)
            if acc[b] < 0.0:
                acc[b] = 0.0
        attributed = sum(acc.values())
        if attributed > gap > 0.0:
            # timer overlap / clock skew: scale back so the invariant
            # "buckets sum to the measured gap" holds exactly
            scale = gap / attributed
            for b in acc:
                acc[b] *= scale
            attributed = gap
        other = max(0.0, gap - attributed)
        self.gaps += 1
        self.total_gap_s += gap
        sink = self.sink
        if sink is not None and sink.enabled:
            sink.histogram("serving/host_gap_ms", gap * 1e3)
            for b, v in acc.items():
                if v > 0.0:
                    sink.counter(f"serving/host_gap/{b}_ms", v * 1e3)
            if other > 0.0:
                sink.counter("serving/host_gap/other_ms", other * 1e3)
        for b in acc:
            acc[b] = 0.0
