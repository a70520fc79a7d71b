"""Serving capacity accounting: per-program roofline registry, the pump's
account of its time and of the host's two threads, and goodput.

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
  time; a landing whose period the pump's account found DEVICE-BOUND (the
  pump waited for the device for ``DEVICE_BOUND_SHARE`` of it or more, so
  the period is the time the device took for that sync; nothing is ever
  fenced) turns that time into ``serving/mfu``,
  ``serving/hbm_bw_util``, and a per-program-kind roofline classification
  gauge (``serving/roofline/<kind>``: analytic arithmetic intensity over
  the machine balance — >= 1 means compute-bound, < 1 bandwidth-bound).
  A host-bound period says nothing of the device and is no sample. The meter also
  owns goodput: useful vs wasted token-FLOPs (speculative rejected
  columns, MoE miss-replay dispatches, migration/restore traffic
  converted at the machine balance) rolled into the
  ``serving/goodput_fraction`` gauge.

- :class:`HostGapTracker` — the pump thread's account of its own time. It
  is the ``observer`` of the pump's spans (the scheduler's ``sched/*`` and
  the gateway's ``gateway/admit`` and ``gateway/idle``: the same boundaries
  a profiler capture shows) and keeps two things from them. One account a
  LANDED sync: every ``sched/fetch`` exit closes the period that opened at
  the landing before it and splits it into ``wait`` (inside ``sched/fetch``:
  the pump blocked on the device), ``idle`` (inside ``gateway/idle``, or
  under no span with nobody pumping), ``compile`` (a span under which a program was built: set-up, not a sync's
  work) and ``busy``, the host's work for that sync, by the span it was
  under (``BUSY_BUCKETS``). The buckets add up to ``busy`` and the parts to
  the period, exactly, whether the sync ran ahead or serial:
  ``serving/pump_busy_ms`` / ``serving/pump_wait_ms`` histograms and
  ``serving/pump/<part>_ms`` counters. With ``wait`` near 0 the host sets
  the pace, whatever the gap below reads. And the device-idle gap, as
  before: the pump is one sync deep, so where it runs ahead the device has
  work queued when a fetch returns and the gap is 0 (one 0.0 observation a
  sync); where it is serial (a drafter, expert offload, paged extents:
  ``DecodeScheduler._lands_first``) the time from
  one sync's fetch to the next dispatch is pure host time:
  ``serving/host_gap_ms``. Where the gateway has told it the host's two
  interpreter threads (``bind_threads``), the same landing also says what
  each burned of the processor in the period (``serving/pump_cpu_ms``,
  ``serving/loop_cpu_ms``, ``serving/host_threads_cpu_pct``) and what the
  event loop delivered in it (:class:`Delivery`: ``gateway/delivery_lag_ms``,
  ``gateway/delivery_lag_max_ms``, ``gateway/backlog_events``,
  ``gateway/loop_cpu_us_per_event``, the ``gateway/sse_*`` counters).

Everything here is stdlib + numpy on the host side and touches the device
nowhere.
"""

import collections
import time

import numpy as np

# the parts of a period's ``busy`` time, each named after the span the pump
# was under: ``gateway`` is ``gateway/admit`` and whatever else lies between
# two ``sched/step``s, ``other`` what ``sched/step`` spends under none of its
# inner spans (the loop, ``_observe``, waits for the GIL)
BUSY_BUCKETS = ("admit", "trie_probe", "assemble", "dispatch", "deliver",
                "tier_transfer", "gateway", "other")

# where the self time of each span the tracker hears goes, unless a program
# was built under it: that is ``compile``, a part like ``wait`` and ``idle``
PUMP_SPANS = {"sched/step": "other", "sched/fetch": "wait",
              "gateway/admit": "gateway", "gateway/idle": "idle",
              **{"sched/" + b: b for b in BUSY_BUCKETS[:6]}}
PUMP_PARTS = ("wait", "idle", "compile") + BUSY_BUCKETS
GATEWAY_SPANS = ("gateway/admit", "gateway/idle")

# the share of a period the pump has to have waited for the device (under
# ``sched/fetch``) for the period to count as the device's own time for that
# sync, and so as a capacity sample. A fetch that finds its block ready still
# costs the ``device_get`` and the way back to the interpreter lock, 0.1-0.3 ms
# (under 2% of the shortest period any cell runs, 16.6 ms); the most host-paced
# cell there is (cell 2) waits 25% of its period with its device idle 4.7%, a
# device-paced one (cell 4) 81% (ledger, PR 51). A twentieth lies above the
# first and a fifth of the way to the second.
DEVICE_BOUND_SHARE = 0.05

# the landed periods a reading of the two threads' processor time spans. A
# thread's clock need not resolve one period: the chip machine's ticks in
# steps of 10 ms (every reading of PR 52's first chip run was a multiple of
# it) under periods of 16 to 130 ms, so each landing observes the mean a sync
# over the run of periods that ends in it, 16 at most: a tick is then 0.6 ms
# of the shortest period's reading
CPU_PERIODS = 16

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
    one pass), draft keys (the device drafter's verify-and-draft program)
    the first forward's width and the steps; everything else (copy/tier ops) is shape-accounted
    as a single column. The ``*_block`` kinds are the fused decode-block
    retags — same tuple positions, priced separately in the roofline."""
    if (isinstance(key, tuple) and len(key) >= 5
            and key[0] in ("fused", "fused_block", "fused_ext",
                           "fused_seqp", "draft")):
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
    """Per-compiled-program roofline registry + the device-bound periods'
    timing + goodput accounting. One instance per scheduler; only built when
    the sink is enabled (the disabled path allocates nothing)."""

    def __init__(self, sink, model, *, peak_flops, peak_hbm_bw, n_devices=1):
        self.sink = sink
        self.model = model
        self.peak_flops = float(peak_flops) * max(1, int(n_devices))
        self.peak_hbm_bw = float(peak_hbm_bw) * max(1, int(n_devices))
        # machine balance: FLOPs/byte at the roofline ridge point
        self.balance = self.peak_flops / max(1.0, self.peak_hbm_bw)
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

    # ---------------------------------------------------------------- sampling
    def observe_dispatch(self, key, dur_s, live_ctx, width, ksteps,
                         kv_mult=1.0, split=False):
        """Fold one sample into the live gauges. ``dur_s`` is the time the
        device took for the sync: the period that closed at its landing,
        where the pump's account found it device-bound
        (``HostGapTracker.device_s``)."""
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


def thread_cpu_clock(ident):
    """A callable that reads, from ANY thread, the processor seconds the
    LIVE thread ``ident`` (``threading.get_ident()``, ``Thread.ident``) has
    burned; None where the platform keeps no such clock. Reading it after
    the thread has exited raises ``OSError``."""
    try:
        clock = time.pthread_getcpuclockid(ident)
        time.clock_gettime(clock)
    except (AttributeError, OSError):
        return None
    return lambda: time.clock_gettime(clock)


class Delivery:
    """What the gateway's event-loop thread delivered, as totals that only
    that thread adds to and the primary pump's account reads at a landing
    and takes differences of. Nothing is swapped or reset (but the maximum,
    where a lost race costs one event's lag): an addition the reader catches
    half done is whole in the next period, and none is ever lost, so posted =
    written + taken + unread + backlog holds at every reading.

    *The unit is the event*: what the loop writes at once, which is ONE
    row's tokens of ONE landing (1 to ``steps_per_sync`` of them: the gateway
    posts a landing's rows to the loop in one wake-up and a handler writes a
    row's as one SSE document). Everything here but ``tokens`` and ``bytes``
    counts events, never tokens: a landing posts as many as the rows it
    delivered to.

    ``events``, ``writes``, ``bytes``: SSE token events whose bytes were
    handed to the transport, the ``write`` calls and the bytes they took;
    ``tokens``: the tokens inside those events (events a token is what the
    batching buys: 1 at ``steps_per_sync`` 1, about a K-th at K).
    ``lag_s`` / ``lag_max_s``: from the landing an event came from to that
    hand-over, summed, and the largest since the last reading. ``taken``:
    token events a response that does not stream took off its queue, or a
    handler that went away left in it. ``unread``: token events that
    reached the loop for a handler that had gone (the loop counts them
    where it hands a landing's batch on, so this total too is the loop's
    alone). ``pumps``: the trackers whose pumps post (``posted``: one a
    replica, counted by the gateway where a landing's batch is handed over,
    before the post).

    *Which landing an event came from* costs the pump nothing a token (on a
    host-paced server a microsecond a token on either thread is a twentieth
    of the tokens: PR 52's first build stamped every event and opened a span
    for each, and a traced cell-9 run lost a sixth of its tokens). The loop
    hands events on in the order they were posted, so the n-th it handles is
    the n-th posted: every landing leaves ``(token events posted before it,
    its time)`` in ``landings`` and the loop reads the landing of its n-th
    event off the head (:meth:`landing_of`). Exact where one pump posts and
    the handlers run in order; a landing out where they interleave."""

    __slots__ = ("events", "writes", "bytes", "tokens", "lag_s", "lag_max_s", "taken",
                 "unread", "pumps", "landings")

    def __init__(self):
        self.events = self.writes = self.bytes = self.tokens = self.taken = self.unread = 0
        self.lag_s = self.lag_max_s = 0.0
        self.pumps = []
        # bounded: a loop that writes nothing (unary traffic alone) never
        # reads the head off
        self.landings = collections.deque(maxlen=4096)

    def posted(self):
        return sum(p.posted for p in self.pumps)

    def landing_of(self, n):
        """The time of the landing that posted the ``n``-th token event
        (1-based), or None before any landing. Called by the loop alone."""
        ring = self.landings
        while len(ring) > 1 and ring[1][0] < n:
            ring.popleft()
        return ring[0][1] if ring else None


class HostGapTracker:
    """The pump thread's account of its time, from its span boundaries.

    The scheduler and the gateway call none of the methods themselves: they
    pass the tracker as the ``observer`` of the pump's spans, and
    :meth:`span_enter` / :meth:`span_exit` hear the ``time.perf_counter``
    boundaries. All methods are float arithmetic on a short stack; the
    tracker is only constructed when the sink is enabled.

    *Periods.* A landing (a ``sched/fetch`` exit) closes the period that
    opened at the landing before it, or at the first ``sched/step`` entry.
    Every span's SELF time (its own less the spans inside it: the trie probe
    comes out of admission) goes to its part (``PUMP_SPANS``); a span open
    across a landing is cut there, so each period gets its share. A span
    under which ``compiles()`` moved (a program was traced, lowered and
    built: seconds to minutes, once a program) gives its self time to
    ``compile`` whatever its name, so the totals of a process describe its
    syncs and not its set-up. Time under no span is the gateway pump's own
    loop around ``sched/step`` where a sync is in flight and one of the
    gateway's spans is on either side, and is left to ``gateway``; otherwise
    nobody pumped (a caller that steps the scheduler itself, a pump not yet
    started) and it is ``idle``. ``busy`` is the period less ``wait``,
    ``idle`` and ``compile``, and ``gateway`` takes what of it no span
    covered, so the parts add up exactly and nothing is ever scaled. A pump
    that went idle (a ``gateway/idle`` turn with nothing in flight) closes
    the stretch since the last landing at its next ``sched/step`` entry into
    the counters alone: the histograms hold one observation a landed sync,
    of that sync's own host work.

    *The device-idle gap.* ``unlanded()`` says whether a sync was launched
    and has not landed. A ``sched/dispatch`` that opens while one is left
    the device no gap: ONE observation of 0.0; a ``sched/fetch`` that closes
    while the next sync is out opens none; a serial pump's gap is the time
    from the fetch's end to the next dispatch's start. Span order alone
    cannot tell the two pumps apart (both alternate dispatch and fetch),
    hence the callable. Where the host's work a sync passes the device's
    the gap still reads 0 (the host cannot see the device finish): ``wait``
    near 0 is the sign.

    *The device's time.* A landed period in which the pump waited for the
    device for ``device_bound_share`` of it or more, and handed it at most
    one program, was the device's: ``device_s`` is the period less the gap
    measured in it (a serial pump's host work before its dispatch), else
    None. The scheduler reads it behind every fetch and feeds the capacity
    gauges from it (``DecodeScheduler._landed``); nothing is fenced.

    *The two threads.* ``bind_threads`` gives the account the processor
    clocks of the pump's thread and, for the primary replica's, of the event
    loop's, and the loop's :class:`Delivery`. Every close then reads them
    (two ``clock_gettime`` a period, nothing a token): ``serving/pump_cpu_ms``,
    ``serving/loop_cpu_ms``, ``serving/host_threads_cpu_pct`` (100 x the two
    over the wall time), each the mean a sync over the run of landed periods
    that ends in this landing (``CPU_PERIODS`` at most: a thread's clock may
    tick more coarsely than a period lasts). The share CAN pass 100: a thread
    in a system call or in XLA's own code holds no interpreter lock, and the
    loop's ``send`` and the pump's dispatch and ``device_get`` are such. From
    the loop's totals: ``gateway/backlog_events`` (posted less written and
    taken, now), and over the events written since the last close
    ``gateway/delivery_lag_ms`` (their mean), ``gateway/delivery_lag_max_ms``
    and ``gateway/loop_cpu_us_per_event`` (the loop's processor time over the
    events written, both over that run of periods); a period that wrote none
    observes none of the three. Histograms hold one observation a LANDED sync, like
    ``serving/pump_busy_ms``; the counters beside them
    (``serving/pump/cpu_ms``, ``gateway/loop/cpu_ms``, ``gateway/sse_events``
    / ``_writes`` / ``_bytes`` / ``_tokens``) take every close. ``pump_busy_ms``
    less ``pump_cpu_ms`` is the time the pump held a span open without the
    processor: the lock, or the machine. ``posted`` is the pump's side of
    the delivery: the token events it posted, one a row a landing delivered
    to (the gateway adds a landing's at once, where it hands the batch to
    the loop; nothing is counted or stamped a token)."""

    __slots__ = ("sink", "_unlanded", "_compiles", "_compiled", "_open_ts", "gaps",
                 "total_gap_s", "_stack", "_acc", "_period_ts", "_was_idle", "_left_ts",
                 "_left_gateway", "busy_s", "wait_s", "device_bound_share", "device_s",
                 "_period_gap_s", "_period_dispatches", "posted", "_posts",
                 "_pump_cpu", "_loop_cpu", "_delivery", "_cpu_marks", "_sent_mark", "_bound")

    def __init__(self, sink, unlanded=None, compiles=None,
                 device_bound_share=DEVICE_BOUND_SHARE):
        self.sink = sink
        self._unlanded = unlanded if unlanded is not None else (lambda: False)
        self._compiles = compiles if compiles is not None else (lambda: 0)
        self._compiled = self._compiles()
        self._open_ts = None     # where the device-idle gap opened
        self.gaps = 0
        self.total_gap_s = 0.0
        self._stack = []         # open spans, outermost first: [part, since, inside]
        self._acc = dict.fromkeys(PUMP_PARTS, 0.0)
        self._period_ts = None   # where the open period began
        self._was_idle = False
        self._left_ts = None     # where the pump was last under a span, while under none
        self._left_gateway = False   # ... and whether that span was the gateway's
        self.busy_s = 0.0
        self.wait_s = 0.0
        self.device_bound_share = float(device_bound_share)
        self.device_s = None     # the device's time for the sync that just landed, or None
        self._period_gap_s = 0.0     # device-idle gap measured in the open period
        self._period_dispatches = 0  # programs handed to the device in it
        self.posted = 0          # token events this pump posted to the event loop
        self._posts = None       # the loop's Delivery, which every landing tells its time
        self._pump_cpu = self._loop_cpu = self._delivery = None
        self._bound = False      # whether a gateway's pump told it its threads
        # (ts, pump, loop processor seconds, events written) where each of the
        # last CPU_PERIODS landed periods began, and where the open one did
        self._cpu_marks = collections.deque(maxlen=CPU_PERIODS + 1)
        self._sent_mark = (0, 0, 0, 0.0, 0)  # the loop's totals at the last close

    def bind_threads(self, pump_cpu, delivery=None, loop_cpu=None, primary=False):
        """The gateway's pump, as it starts: ``pump_cpu`` reads its thread's
        processor seconds (:func:`thread_cpu_clock`), ``delivery`` is the
        event loop's totals (this pump's ``posted`` is counted into its
        backlog from here on). A fleet has several pumps and one loop: the
        ``primary`` replica's pump also gives ``loop_cpu``, the loop thread's
        clock, and its account emits the loop's numbers; the others emit
        their pump's alone. A clock the platform lacks is None and the
        numbers it feeds are left out, not made up."""
        self._pump_cpu, self._loop_cpu = pump_cpu, loop_cpu if primary else None
        self._cpu_marks.clear()
        self._bound = True
        if delivery is not None:
            delivery.pumps.append(self)
            self._posts = delivery
            if primary:
                self._delivery = delivery
                self._sent_mark = (delivery.events, delivery.writes, delivery.bytes,
                                   delivery.lag_s, delivery.tokens)

    def unbind_threads(self):
        """The gateway's pump, as it exits: a thread's clock cannot be read
        once the thread is gone. What the pump posted stays counted."""
        self._pump_cpu = self._loop_cpu = self._delivery = self._posts = None
        self._bound = False

    def span_enter(self, name, ts):
        """A span of the pump opened at ``ts``."""
        if self._left_ts is not None:
            # under no span: the gateway pump's loop (left to ``gateway``)
            # where one of its spans is on either side and a sync is in
            # flight; else nobody pumped
            if not (self._unlanded() and (self._left_gateway or name in GATEWAY_SPANS)):
                self._acc["idle"] += ts - self._left_ts
            self._left_ts = None
        if name == "sched/step":
            if self._period_ts is None or (self._was_idle and not self._unlanded()):
                self._close(ts, landed=False)
            self._was_idle = False
        elif name == "sched/dispatch":
            self._dispatch(ts)
        self._stack.append([PUMP_SPANS[name], ts, 0.0])

    def span_exit(self, name, t0, t1):
        """The innermost open span, which opened at ``t0``, closed at ``t1``."""
        part, since, inside = self._stack.pop()
        compiled = self._compiles()
        if compiled != self._compiled:
            self._compiled, part = compiled, "compile"
        self._acc[part] += t1 - since - inside
        if self._stack:
            self._stack[-1][2] += t1 - since
        else:
            self._left_ts, self._left_gateway = t1, name in GATEWAY_SPANS
        if name == "sched/fetch":
            # results on the host: the device idles from here unless the
            # next sync was launched already
            self._open_ts = None if self._unlanded() else t1
            if self._posts is not None:
                # what this landing delivers is posted behind everything
                # posted so far
                self._posts.landings.append((self._posts.posted(), t1))
            self._close(t1, landed=True)
        elif name == "gateway/idle" and not self._unlanded():
            self._was_idle = True

    def _dispatch(self, ts):
        """The next program is being handed to the device: the device-idle
        gap closes. Behind a sync that has not landed the device was busy: a
        gap of 0.0. A dispatch before any sync (warm-up) records nothing."""
        open_ts, self._open_ts = self._open_ts, None
        self._period_dispatches += 1
        if open_ts is None and not self._unlanded():
            return
        gap = 0.0 if open_ts is None else max(0.0, ts - open_ts)
        self.gaps += 1
        self.total_gap_s += gap
        self._period_gap_s += gap
        self.sink.histogram("serving/host_gap_ms", gap * 1e3)

    def _close(self, ts, landed):
        """Close the period at ``ts``: book the open spans' self time so far
        (innermost first, each less the open span inside it), emit, and
        open the next period at ``ts``. With no period open (before the
        first ``sched/step``) what the spans booked so far is dropped."""
        acc, covered = self._acc, 0.0
        for span in reversed(self._stack):
            acc[span[0]] += ts - span[1] - span[2] - covered
            covered = ts - span[1]
            span[1], span[2] = ts, 0.0
        start, self._period_ts = self._period_ts, ts
        self.device_s = None
        if start is not None:
            period = ts - start
            wait = acc["wait"]
            busy = period - wait - acc["idle"] - acc["compile"]
            acc["gateway"] = busy - sum(acc[b] for b in BUSY_BUCKETS if b != "gateway")
            self.busy_s += busy
            self.wait_s += wait
            sink = self.sink
            if landed:
                sink.histogram("serving/pump_busy_ms", busy * 1e3)
                sink.histogram("serving/pump_wait_ms", wait * 1e3)
                if (period > 0.0 and self._period_dispatches <= 1
                        and wait >= self.device_bound_share * period):
                    self.device_s = period - self._period_gap_s
            for part, v in (("busy", busy), *acc.items()):
                if v:
                    sink.counter(f"serving/pump/{part}_ms", v * 1e3)
        if self._bound:
            self._close_threads(ts, start is not None, landed)
        self._period_gap_s, self._period_dispatches = 0.0, 0
        for part in acc:
            acc[part] = 0.0

    def _close_threads(self, ts, opened, landed):
        """The two threads' part of a close at ``ts``: their processor time
        and what the loop delivered. ``opened``: a period was open (else the
        clocks are only marked). The processor time is observed as the mean
        a sync over the landed periods that end here, ``CPU_PERIODS`` at
        most and none across an idle stretch; the counters take every
        close's own difference, so their totals are exact."""
        sink = self.sink
        pump_cpu, loop_cpu, d = self._pump_cpu, self._loop_cpu, self._delivery
        # ``events`` before the sums read behind it: the loop adds to it
        # last, so every event counted has its lag and its bytes in them;
        # and ``posted`` last of all (counted before the post, and only
        # growing), so the backlog is never under 0
        events = d.events if d is not None else 0
        marks = self._cpu_marks
        now = (ts, pump_cpu() if pump_cpu is not None else 0.0,
               loop_cpu() if loop_cpu is not None else 0.0, events)
        last = marks[-1] if marks and opened else None
        if not (landed and opened):
            marks.clear()   # an idle stretch, or the first period's opening
        marks.append(now)
        if last is None:
            return
        if pump_cpu is not None:
            sink.counter("serving/pump/cpu_ms", (now[1] - last[1]) * 1e3)
        if loop_cpu is not None:
            sink.counter("gateway/loop/cpu_ms", (now[2] - last[2]) * 1e3)
        if landed:
            base, n = marks[0], len(marks) - 1
            if pump_cpu is not None:
                sink.histogram("serving/pump_cpu_ms", (now[1] - base[1]) / n * 1e3)
            if loop_cpu is not None:
                sink.histogram("serving/loop_cpu_ms", (now[2] - base[2]) / n * 1e3)
                if pump_cpu is not None and ts > base[0]:
                    sink.histogram("serving/host_threads_cpu_pct", 100.0 * (
                        now[1] - base[1] + now[2] - base[2]) / (ts - base[0]))
        if d is None:
            return
        sent = (events, d.writes, d.bytes, d.lag_s, d.tokens)
        backlog = -(events + d.taken + d.unread) + d.posted()
        lag_max, d.lag_max_s = d.lag_max_s, 0.0
        before, self._sent_mark = self._sent_mark, sent
        n = events - before[0]
        if n:
            sink.counter("gateway/sse_events", n)
            sink.counter("gateway/sse_writes", sent[1] - before[1])
            sink.counter("gateway/sse_bytes", sent[2] - before[2])
            sink.counter("gateway/sse_tokens", sent[4] - before[4])
        if landed:
            sink.histogram("gateway/backlog_events", backlog)
            if n:
                sink.histogram("gateway/delivery_lag_ms", (sent[3] - before[3]) / n * 1e3)
                sink.histogram("gateway/delivery_lag_max_ms", lag_max * 1e3)
                if loop_cpu is not None:
                    # the loop's processor time over the events written, both
                    # over the run of periods the thread readings span
                    base = marks[0]
                    sink.histogram("gateway/loop_cpu_us_per_event",
                                   (now[2] - base[2]) * 1e6 / (events - base[3]))
