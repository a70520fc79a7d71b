"""Multi-replica serving: N independent decode schedulers behind one gateway.

The data-parallel half of pod-scale serving (the tensor-parallel half lives
in the scheduler's sharded step programs): a :class:`ReplicaSet` fronts N
:class:`~deepspeed_tpu.inference.scheduler.DecodeScheduler` replicas — each
its own slot pool (tp-sharded over the mesh's ``tensor`` axis when tp>1) —
behind one dispatch policy, in the AlpaServe/"replica groups" sense rather
than N processes: one weight tree, ONE compiled program set (replicas share
the primary scheduler's program cache, so replica count adds ZERO XLA
programs), N independent KV pools and decode loops.

Dispatch policy (the gateway's fair queue pops in DRR order, then this
layer places):

- **Prefix-sticky**: prompts whose leading ``prefill_chunk`` tokens match a
  previously-dispatched prompt route to the replica that served it — that
  replica's radix trie holds the prefix, so admission copies KV instead of
  recomputing prefill. The sticky index is a bounded host-side LRU keyed on
  the leading chunk (NOT a cross-thread read of another replica's trie —
  pump threads own their schedulers), re-pointed whenever placement falls
  elsewhere, so it tracks the most recent owner exactly like the trie's MRU
  donor choice.
- **Least-loaded**: otherwise the replica minimizing expected drain time —
  ``(busy_slots + 1) x service-time EMA`` (the same EMA the gateway's
  Retry-After advertises, tracked per replica) — with a round-robin tie
  break so an idle fleet doesn't pile onto replica 0.

Per-replica lifecycle: ``drain(i)`` stops placement and lets in-flight work
finish (resumable); a replica whose ``step()`` raises is marked **sick** —
its requests fail, its sticky entries purge, and the rest of the fleet keeps
serving (one sick replica sheds instead of sinking the fleet). A sick
replica can be ``resume()``d after operator intervention.

With the hierarchical KV tier enabled (``continuous_batching.
hierarchical_kv``), the fleet additionally shares ONE host-side prefix
store (``memory/prefix_store.GlobalPrefixStore`` — threaded through the
scheduler's ``_init_kwargs`` exactly like the shared compiled-program
cache): a prefix radix-evicted on any replica demotes there, and ANY
replica's admission can restore it, so sticky routing misses stop being
cold prefills.

**Disaggregated prefill/decode** (``continuous_batching.disaggregation``,
DistServe/Splitwise): replicas carry a phase role — ``prefill``,
``decode``, or ``mixed`` (the default; a zero-role fleet behaves exactly
as before). Placement only considers prefill-CAPABLE replicas (``prefill``
or ``mixed``); when a prompt's chunked prefill completes on a ``prefill``
replica, the request's whole KV demotes through the shared prefix store
(``memory/kv_tier.KVTier.demote_request`` — the same two compiled
tier programs the hierarchical tier uses) and parks in the fleet's
migration queue, from which decode-capable replicas PULL as their pumps
find capacity (pull placement self-balances and makes sick-decode
failover free: a parked handoff is bound to no replica, so any healthy
decode replica re-places it). Decode resumes bit-identically — the
sampling seeds fold absolute step indices, the KV rows move byte-exact,
and the request object (tokens, logits, hooks, adapter pin) travels
as-is. ``migrate_min_tokens`` colocates short prompts (the handoff round
trip isn't worth it); a fleet whose decode side vanishes entirely falls
back to colocating on whatever is left rather than stalling.

**Elastic fleet** (``continuous_batching.autoscaler`` —
``serving/controller.py`` drives these): :meth:`ReplicaSet.add_replica`
grows the fleet at runtime over the SAME weight tree and compiled-program
dict (zero new XLA programs; warmup is pool allocation);
:meth:`ReplicaSet.begin_scale_down` / :meth:`ReplicaSet.finish_scale_down`
shrink it two-phase — pending-drain replicas stop counting toward every
advertised-capacity surface immediately, then retire from their own pump
thread once idle, releasing their KV pool's HBM;
:meth:`ReplicaSet.park_out` / :meth:`ReplicaSet.release_parked` implement
brownout preemption-with-resume over the PR 13 migrate-out transport
(held handoff records that decode pumps skip until the brownout lifts).

Why replicas (vs one bigger pool): each replica is its own scheduler loop —
on a pod, its own tensor-sharded device group stepping independently; on
one host, independent pools whose aggregate KV capacity (and radix
residency) scales with N. Compile count stays O(1) because programs are
per-shard-SHAPE, not per-replica.

Telemetry: gauges ``serving/replica/<id>/{slot_occupancy,queue_depth,
tok_s}``; counters ``serving/replica/<id>/{dispatched,tokens}``,
``serving/dispatch/{sticky,least_loaded}``, ``serving/replica_sick``,
``serving/replica_drains``. All reach ``/v1/metrics`` JSON and render as
labeled Prometheus series (``telemetry/prometheus.py``).
"""

import collections
import threading
import time

import numpy as np


# handoff-key sentinel: negative (never a real token), far below the
# adapter-uid namespace sentinels (-(uid)-1); a migration key is
#   adapter_namespace + (_MIG_SENTINEL, unique_counter)
# so adapter invalidation (store.drop_prefix on the uid namespace) reclaims
# parked handoffs too, and no probe of real prompt tokens can ever match one
_MIG_SENTINEL = -(1 << 30)

_PHASE_ROLES = ("prefill", "decode", "mixed")


class _Migration:
    """One prefill→decode handoff in flight: the request object plus where
    its KV is parked. ``entry`` stays None until the demote's async
    device→host fetch lands (``ready`` flips then) — decode pumps only see
    READY records."""

    __slots__ = ("req", "key", "kv_len", "version", "entry", "ready",
                 "src_idx", "t_start", "held")

    def __init__(self, req, key, src_idx, t_start):
        self.req = req
        self.key = key
        self.kv_len = 0
        self.version = 0
        self.entry = None
        self.ready = False
        self.src_idx = src_idx
        self.t_start = t_start
        # brownout parking (serving/controller.py): a held record is NOT
        # claimable by decode pumps — release_parked() flips it back into
        # the normal pull rotation when the brownout lifts
        self.held = False


class _FleetPump:
    """Handle-compatible pump for a migrated-out request: ``result()`` on a
    request whose handoff is parked must drive the WHOLE fleet (the prefill
    scheduler alone would spin forever), so migrate-out re-points the
    handle's scheduler here until a decode replica adopts the request."""

    __slots__ = ("_rs", "engine")

    def __init__(self, rs):
        self._rs = rs
        self.engine = rs.primary.engine

    def step(self):
        return self._rs.pump_once()


class Replica:
    """One scheduler + its fleet bookkeeping (placement load signals,
    health/drain state, phase role, throughput EMA). The scheduler itself
    stays single-threaded: exactly one pump thread calls :meth:`step`."""

    def __init__(self, idx, scheduler, telemetry=None, phase_role="mixed"):
        self.idx = idx
        self.scheduler = scheduler
        # request traces stamp the replica that executed each phase (the
        # migration-aware tools/trace_summary.py --requests view pairs a
        # prefill replica with the decode replica that adopted the handoff)
        scheduler.replica_idx = idx
        self.telemetry = telemetry if telemetry is not None else scheduler.telemetry
        self.draining = False
        self.sick = False
        self.sick_error = None
        # elastic scale-down lifecycle (serving/controller.py): pending_drain
        # = the controller is shrinking the fleet through this replica — it
        # stops counting toward EVERY advertised-capacity surface
        # (total_slots / phase_slots / Retry-After / metrics) immediately,
        # not when the drain completes; retired = drained and released (its
        # pump thread exited, its KV pool freed, its index reusable)
        self.pending_drain = False
        self.retired = False
        self.dispatched = 0
        self.tokens = 0
        # disaggregated serving: "prefill" replicas run prefills and hand
        # finished prompts to the decode side; "decode" replicas receive
        # migrations and never take fresh placements; "mixed" does both
        # (and neither migrates nor changes any pre-disaggregation behavior)
        self.phase_role = phase_role
        self.ema_service_s = None   # per-replica Retry-After-style service EMA
        self.tok_s = 0.0            # EWMA of delivered tokens/sec
        self._last_step_end = None

    # ---------------------------------------------------------------- phase
    def prefill_capable(self):
        """Eligible for fresh prompt placement (gateway/FairQueue pops)."""
        return self.phase_role in ("prefill", "mixed")

    def decode_capable(self):
        """Eligible to adopt migrated-in decode work."""
        return self.phase_role in ("decode", "mixed")

    # ---------------------------------------------------------------- load
    def busy_slots(self):
        s = self.scheduler
        return (s.cache.active_slots + len(s.queue)
                + (1 if s._prefill is not None else 0))

    def has_capacity(self):
        return self.busy_slots() < self.scheduler.num_slots

    def available(self):
        """Placement-eligible: healthy and accepting new work. A
        pending-drain (or retired) replica is never available — the
        controller's scale-down must stop it counting toward advertised
        capacity the moment the decision lands, not when the drain ends."""
        return not (self.sick or self.draining
                    or self.pending_drain or self.retired)

    def idle(self):
        """Nothing queued, prefilling, decoding or launched and not yet
        landed (the scheduler's pump is one sync deep)."""
        s = self.scheduler
        return not (s.active or s.queue or s._prefill is not None or s.in_flight)

    def expected_drain_s(self, fallback_ema):
        """Placement score: expected time for this replica's backlog (+ the
        incoming request) to clear at its measured service rate."""
        ema = self.ema_service_s if self.ema_service_s is not None else fallback_ema
        return (self.busy_slots() + 1) * ema / max(1, self.scheduler.num_slots)

    # ---------------------------------------------------------------- loop
    def step(self):
        """One scheduler iteration plus throughput accounting. Called ONLY
        from this replica's pump thread."""
        t0 = time.monotonic()
        delivered = self.scheduler.step()
        now = time.monotonic()
        self.tokens += delivered
        # inter-step host overhead counts, but an IDLE gap (pump parked
        # waiting for work) must not: a lull would fold a near-zero sample
        # into the EWMA and understate a lightly-loaded replica
        prev = self._last_step_end
        start = prev if (prev is not None and t0 - prev < 1.0) else t0
        dt = now - start
        self._last_step_end = now
        if dt > 0:
            inst = delivered / dt
            self.tok_s = inst if self.tok_s == 0.0 else 0.9 * self.tok_s + 0.1 * inst
        tel = self.telemetry
        if tel.enabled:
            tel.gauges([
                (f"serving/replica/{self.idx}/slot_occupancy",
                 self.scheduler.cache.occupancy(), None),
                (f"serving/replica/{self.idx}/queue_depth",
                 float(len(self.scheduler.queue)), None),
                (f"serving/replica/{self.idx}/tok_s", self.tok_s, None)])
            if delivered:
                tel.counter(f"serving/replica/{self.idx}/tokens", delivered)
        return delivered

    def observe_service(self, service_s):
        """Fold one naturally-completed request's wall time into the
        placement EMA (same exclusion rule as the gateway's Retry-After EMA:
        cancelled/failed requests don't count)."""
        self.ema_service_s = (service_s if self.ema_service_s is None
                              else 0.9 * self.ema_service_s + 0.1 * service_s)

    def state(self):
        if self.retired:
            # the KV pool is released: report the terminal record without
            # touching pool-backed stats
            return {"idx": self.idx, "status": "retired", "error": None,
                    "phase_role": self.phase_role,
                    "dispatched": self.dispatched, "tokens": self.tokens}
        s = self.scheduler
        return {
            "idx": self.idx,
            "status": ("sick" if self.sick else
                       "pending_drain" if self.pending_drain else
                       "draining" if self.draining else "active"),
            "error": self.sick_error,
            # disaggregated serving: this replica's phase role and how many
            # requests it has handed off / adopted (the gateway's
            # /v1/replicas + /v1/metrics surface)
            "phase_role": self.phase_role,
            "migrations_out": s.migrations_out,
            "migrations_in": s.migrations_in,
            "num_slots": s.num_slots,
            "active_slots": s.cache.active_slots,
            "cached_slots": s.cache.cached_slots,
            "queue_depth": len(s.queue),
            "slot_occupancy": round(s.cache.occupancy(), 4),
            "dispatched": self.dispatched,
            "tokens": self.tokens,
            "tok_s": round(self.tok_s, 2),
            # capacity accounting (telemetry/capacity.py): this replica's
            # own pump-thread host-gap totals and goodput — per-replica
            # because each pump attributes independently
            "goodput_fraction": (round(s.capacity.goodput_fraction, 5)
                                 if s.capacity is not None else None),
            "host_gap_total_s": (round(s._gap.total_gap_s, 4)
                                 if s._gap is not None else None),
            "pump_busy_total_s": (round(s._gap.busy_s, 4)
                                  if s._gap is not None else None),
            "pump_wait_total_s": (round(s._gap.wait_s, 4)
                                  if s._gap is not None else None),
            "ema_service_s": self.ema_service_s,
            "tp_size": s.tp_size,
            "ep_size": s.ep_size,
            # cold-expert paging (MoE serving): the fleet-shared store —
            # a page hot-loaded through any replica is resident for all
            "expert_store": s.experts.stats() if s.experts is not None else None,
            "prefix_cache_hit_rate": (round(s.radix.hit_rate(), 4)
                                      if s.radix is not None else None),
            # hierarchical KV tier (fleet-global host store shared by every
            # replica): this replica's demote/restore counts plus the shared
            # store's residency — any replica can restore a prefix any
            # other computed (memory/kv_tier.py)
            "kv_tier": s.kv_tier.stats() if s.kv_tier is not None else None,
            # multi-LoRA: the fleet-shared paged adapter store (one object,
            # same numbers from every replica — an adapter loaded through
            # any replica is resident for all)
            "adapters": s.adapters.stats() if s.adapters is not None else None,
        }


class ReplicaSet:
    """N replicas behind one dispatch policy. Thread-safe: the gateway's
    pump threads race :meth:`dispatch`/:meth:`route` under the internal
    lock; each replica's ``step`` stays exclusive to its own pump."""

    def __init__(self, replicas, sticky_capacity=2048, roles=None,
                 migrate_min_tokens=0):
        if not replicas:
            raise ValueError("ReplicaSet needs at least one replica")
        self.replicas = list(replicas)
        self.telemetry = self.replicas[0].telemetry
        self._lock = threading.RLock()
        self._rr = 0  # round-robin tie break cursor
        # sticky prefix index: leading-chunk key -> replica idx (bounded LRU)
        self._sticky = collections.OrderedDict()
        self._sticky_capacity = int(sticky_capacity)
        self._sticky_chunk = self.primary.prefill_chunk
        # disaggregated prefill/decode: the fleet-wide handoff queue (pull
        # model — decode pumps claim READY records as they find capacity)
        # plus the migrate-time knobs. Hooks install lazily the first time
        # any replica takes a non-mixed role.
        self._migrations = collections.deque()
        self._mig_id = 0
        self.migrate_min_tokens = max(0, int(migrate_min_tokens))
        self.migrations_failed = 0
        self._pump_proxy = _FleetPump(self)
        self._hooks_installed = False
        self._warmup_pending = False
        if roles:
            for idx, role in enumerate(roles):
                if idx < len(self.replicas):
                    self.set_role(idx, role)
            # build time: no pump threads exist yet, so the constructor IS
            # the pump-owned context — warm the tier programs here, before
            # the gateway's recompile watch can arm
            self._run_pending_warmup(self.replicas[0])

    # ---------------------------------------------------------------- build
    @classmethod
    def build(cls, engine, n=None, **scheduler_overrides):
        """N replicas over ONE engine: replica 0 is the engine's singleton
        scheduler (so a single-replica gateway is byte-for-byte the
        pre-replica path), siblings clone its exact configuration and share
        its compiled-program cache — same shapes, same programs, zero new
        XLA compiles per added replica. ``n`` defaults to the engine's
        ``continuous_batching.replicas``; the ``disaggregation`` config
        section seeds per-replica phase roles (all-``mixed`` when absent —
        byte-identical to the pre-disaggregation fleet)."""
        from ..inference.scheduler import DecodeScheduler
        cb = engine._config.continuous_batching
        if n is None:
            n = int(getattr(cb, "replicas", 1) or 1)
        if n < 1:
            raise ValueError(f"replicas must be >= 1, got {n}")
        primary = engine.scheduler(**scheduler_overrides)
        scheds = [primary]
        for _ in range(1, n):
            scheds.append(DecodeScheduler(engine, compiled_cache=primary._compiled,
                                          **primary._init_kwargs))
        dg = getattr(cb, "disaggregation", None)
        roles = list(getattr(dg, "roles", []) or []) if (
            dg is not None and dg.enabled) else []
        mmt = int(getattr(dg, "migrate_min_tokens", 0) or 0) if (
            dg is not None and dg.enabled) else 0
        return cls([Replica(i, s) for i, s in enumerate(scheds)],
                   roles=roles, migrate_min_tokens=mmt)

    @property
    def primary(self):
        return self.replicas[0].scheduler

    def __len__(self):
        return len(self.replicas)

    def __iter__(self):
        return iter(self.replicas)

    # ---------------------------------------------------------------- fleet state
    def total_slots(self):
        """Slots across placement-eligible replicas (the gateway's
        Retry-After backlog math divides by this)."""
        return sum(r.scheduler.num_slots for r in self.replicas
                   if r.available()) or self.replicas[0].scheduler.num_slots

    def phase_slots(self, phase):
        """Available slots on one side of the phase split (``"prefill"`` /
        ``"decode"`` capability — mixed counts for both): the gateway's
        phase-aware Retry-After divides each side's backlog by its own
        capacity instead of the blended fleet total."""
        want = (Replica.prefill_capable if phase == "prefill"
                else Replica.decode_capable)
        return sum(r.scheduler.num_slots for r in self.replicas
                   if r.available() and want(r))

    def disaggregated(self):
        """Any non-mixed role among LIVE replicas (phase-aware paths switch
        on). A retired replica's stale role must not pin the fleet into
        phase-aware math after elastic scale-down removed the split."""
        return any(r.phase_role != "mixed" for r in self.replicas
                   if not r.retired)

    def any_capacity(self):
        """A fresh prompt can be placed right now: an available
        PREFILL-capable replica has a free slot (decode-only replicas are
        not placement targets — that is the disaggregation contract)."""
        return any(r.available() and r.has_capacity() and r.prefill_capable()
                   for r in self.replicas)

    def healthy(self):
        """Replicas that could serve (not sick, not retired) — retired
        slots are index placeholders, not failover capacity."""
        return [r for r in self.replicas if not r.sick and not r.retired]

    def all_sick(self):
        """No live replica left: every non-retired replica is sick (a
        retired slot must not read as a healthy survivor)."""
        return all(r.sick or r.retired for r in self.replicas)

    def compiled_program_count(self):
        """One shared program set — the fleet's compile count IS the
        primary's (the O(1)-in-replicas guard reads this)."""
        return self.primary.compiled_program_count()

    def states(self):
        return [r.state() for r in self.replicas]

    # ---------------------------------------------------------------- lifecycle
    def drain(self, idx):
        """Stop placing onto replica ``idx``; in-flight work finishes (its
        pump keeps stepping). Idempotent; resumable."""
        with self._lock:
            rep = self.replicas[idx]
            rep.draining = True
            self._purge_sticky(idx)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/replica_drains")
        return rep.state()

    def resume(self, idx):
        """Re-admit replica ``idx`` to placement (clears drain AND sick —
        resuming a sick replica is the operator asserting it recovered)."""
        with self._lock:
            rep = self.replicas[idx]
            rep.draining = False
            rep.sick = False
            rep.sick_error = None
        return rep.state()

    def mark_sick(self, idx, error):
        """Health-out replica ``idx`` (its step raised): no further
        placement, sticky entries purge so its prompt families re-home.
        Idempotent — re-marking an already-sick replica neither
        re-increments the health-out counter nor re-scans the sticky map
        (a persistently-raising backend would otherwise spin both)."""
        with self._lock:
            rep = self.replicas[idx]
            if rep.sick:
                return
            rep.sick = True
            rep.sick_error = str(error)[:500]
            self._purge_sticky(idx)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/replica_sick")

    def _purge_sticky(self, idx):
        for key in [k for k, v in self._sticky.items() if v == idx]:
            del self._sticky[key]

    # ---------------------------------------------------------------- elastic fleet
    # (serving/controller.py drives these through cooldown-guarded
    # transitions; the gateway owns pump-thread lifecycle)
    def add_replica(self, phase_role="mixed"):
        """Grow the fleet by one scheduler sharing the primary's weight
        tree AND compiled-program dict — same shapes, same programs, ZERO
        new XLA compiles (the O(1)-programs invariant the gateway's
        recompile watch guards), so scale-up warmup is just pool
        allocation. Reuses a retired replica's index when one exists
        (indices stay dense for /v1/replicas); otherwise appends. The
        caller owns starting a pump thread: ``on_replica_added`` fires
        with the new replica after it is routable."""
        from ..inference.scheduler import DecodeScheduler
        primary = self.primary
        sched = DecodeScheduler(primary.engine, compiled_cache=primary._compiled,
                                **primary._init_kwargs)
        if self._hooks_installed:
            # a disaggregated fleet's migrate hook consults CURRENT roles
            # per prefill completion, so installing it on a mixed newcomer
            # is inert until someone flips its role
            sched.migrate_hook = self._maybe_migrate
        with self._lock:
            slot = next((i for i, r in enumerate(self.replicas) if r.retired),
                        None)
            idx = slot if slot is not None else len(self.replicas)
            rep = Replica(idx, sched, phase_role=phase_role)
            if slot is None:
                self.replicas.append(rep)
            else:
                self.replicas[slot] = rep
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/replica_added")
        cb = self.on_replica_added
        if cb is not None:
            cb(rep)
        return rep

    def begin_scale_down(self, idx):
        """Two-phase scale-down, phase 1 (any thread): mark replica ``idx``
        pending-drain — no further placement, EXCLUDED from every
        advertised-capacity surface immediately (a draining replica that
        still counted toward slots would understate Retry-After for the
        whole drain) — and purge its sticky entries so its prompt families
        re-home. Phase 2 (:meth:`finish_scale_down`) retires it from its
        own pump thread once idle. Replica 0 never scales down: it owns
        the shared compiled-program cache and the fleet-wide pump duties."""
        if idx == 0:
            raise ValueError("replica 0 cannot scale down (it owns the shared "
                             "compiled-program cache and the primary pump)")
        with self._lock:
            rep = self.replicas[idx]
            if rep.retired or rep.pending_drain:
                return rep.state()
            rep.pending_drain = True
            rep.draining = True
            self._purge_sticky(idx)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/replica_drains")
        return rep.state()

    def finish_scale_down(self, rep):
        """Two-phase scale-down, phase 2 (``rep``'s OWN pump thread, once
        its in-flight work finished): retire the replica and drop its KV
        pool tree — the device buffers backing its slots are the HBM the
        scale-down exists to reclaim. Returns True when the replica
        retired (its pump thread should exit)."""
        if not rep.pending_drain or rep.retired or not rep.idle():
            return False
        with self._lock:
            if rep.retired:
                return False
            rep.retired = True
        # the scheduler never steps again: releasing the pool frees the
        # dominant HBM cost of the replica (shared stores — prefix tier,
        # adapters, experts — are fleet-global and stay)
        rep.scheduler.cache.pool = None
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/replica_retired")
            tel.gauge(f"serving/replica/{rep.idx}/slot_occupancy", 0.0)
        return True

    def active_count(self):
        """Fleet size as capacity planning sees it (retired slots are
        index placeholders, not replicas)."""
        return sum(1 for r in self.replicas if not r.retired)

    def park_out(self, rep, req):
        """Brownout preemption WITH resume: demote ``req``'s whole KV
        through the migration transport (PR 13's migrate-out path) and
        HOLD the parked record — decode pumps skip held records — until
        :meth:`release_parked` re-admits it when the brownout lifts. Must
        run on ``rep``'s own pump thread (migrate_out touches its pool).
        Returns the record, or None when the request isn't parkable (no
        transport, not decoding here, mid-prefill, already terminal)."""
        sched = rep.scheduler
        # the handoff moves the request's landed state: land what is in flight
        # BEFORE looking at it (it may end in that very sync)
        sched.land_in_flight()
        if sched.kv_tier is None or req.done or req.cancelled or req.migrating:
            return None
        if req.slot is None or sched.active.get(req.slot) is not req:
            return None
        if sched._prefill is not None and sched._prefill.req is req:
            return None
        with self._lock:
            self._mig_id += 1
            mig_id = self._mig_id
        ns = (sched.adapters.namespace(req.adapter_ref.uid)
              if req.adapter_ref is not None else ())
        key = tuple(ns) + (_MIG_SENTINEL, mig_id)
        record = _Migration(req, key, rep.idx, time.monotonic())
        record.version = int(sched.cache.weights_version)
        record.held = True

        def on_ready(entry):
            record.entry = entry
            record.ready = True
            cb = self.on_migration_ready
            if cb is not None:
                cb()
        record.kv_len = sched.migrate_out(req, key, on_ready)
        if req.handle is not None:
            req.handle._sched = self._pump_proxy
        with self._lock:
            self._migrations.append(record)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/parked")
        return record

    def release_parked(self):
        """Lift the brownout hold: every held record re-enters the normal
        pull rotation, so decode-capable pumps adopt and resume them
        bit-identically (sampling seeds fold absolute step indices; the
        KV rows moved byte-exact). Returns the number released."""
        released = 0
        with self._lock:
            for rec in self._migrations:
                if rec.held:
                    rec.held = False
                    released += 1
        if released:
            cb = self.on_migration_ready
            if cb is not None:
                cb()
        return released

    # ---------------------------------------------------------------- phase roles
    def set_role(self, idx, role):
        """Assign replica ``idx`` a phase role (config seeding and the
        gateway's ``POST /v1/replicas/<i>/role`` runtime override). A
        non-mixed role requires the migration transport (the hierarchical
        prefix store — ``continuous_batching.disaggregation.enabled``
        creates it; ``hierarchical_kv`` also provides it) and a fleet that
        keeps BOTH phases coverable; violating either reverts and raises."""
        if role not in _PHASE_ROLES:
            raise ValueError(f"phase_role must be one of {_PHASE_ROLES}, got {role!r}")
        rep = self.replicas[idx]
        if rep.retired:
            raise ValueError(f"replica {idx} is retired (scaled down); "
                             f"add_replica() reuses its index")
        if role != "mixed" and self.primary.kv_tier is None:
            raise ValueError(
                "phase roles need the hierarchical-KV prefix store as the "
                "migration transport: enable continuous_batching.disaggregation "
                "(or hierarchical_kv) so the fleet shares a GlobalPrefixStore")
        prev, rep.phase_role = rep.phase_role, role
        if not (any(r.prefill_capable() for r in self.replicas if not r.retired)
                and any(r.decode_capable() for r in self.replicas
                        if not r.retired)):
            rep.phase_role = prev
            raise ValueError(
                f"role {role!r} on replica {idx} would leave the fleet with no "
                f"{'prefill' if role == 'decode' else 'decode'}-capable replica "
                f"(roles: {[r.phase_role for r in self.replicas]})")
        if role == "decode":
            with self._lock:
                self._purge_sticky(idx)  # no fresh placements land here
        if role != "mixed" and not self._hooks_installed:
            try:
                self._install_migration_hooks()
            except Exception:
                rep.phase_role = prev  # docstring contract: revert AND raise
                raise
        return rep.state()

    def _install_migration_hooks(self):
        """First non-mixed role: every scheduler gets the migrate hook (it
        consults the CURRENT role at each prefill completion, so runtime
        role flips take effect immediately) and the tier-program warmup is
        FLAGGED for the primary's pump — set_role may run on the gateway's
        admin (event-loop) thread, and warming inline there would race the
        pump's concurrent pool updates. The pump executes it at its next
        ``admit_migrations`` turn, which both pump loops run BEFORE any
        step that could migrate."""
        for rep in self.replicas:
            rep.scheduler.migrate_hook = self._maybe_migrate
        self._warmup_pending = True
        self._hooks_installed = True

    def _run_pending_warmup(self, rep):
        """Compile tier_slice/tier_restore into the SHARED program cache
        (one warmup serves every replica). Runs on a pump-owned turn — for
        build-time roles that is the constructor (no pumps yet); for a
        runtime role flip, the primary's next pump turn. A flip on a warm
        gateway may trip the recompile watch once — an expected compile,
        visible as exactly these two tier programs in the flight dump."""
        if self._warmup_pending and rep is self.replicas[0]:
            self._warmup_pending = False
            self.primary.kv_tier.warmup()

    # ---------------------------------------------------------------- migration
    def _maybe_migrate(self, sched, req):
        """The scheduler-side migrate hook: decide whether the request a
        prefill sync just finished should hand off to the decode side, and
        if so drive ``migrate_out``. Runs on the PREFILL replica's pump
        thread. Returns True when the request was taken."""
        rep = next((r for r in self.replicas if r.scheduler is sched), None)
        if rep is None or rep.phase_role != "prefill":
            return False  # mixed/decode replicas keep their decodes
        if req.prompt.size < self.migrate_min_tokens:
            return False  # colocate: the handoff isn't worth a short prompt
        with self._lock:
            target_exists = any(r.decode_capable() and r.available()
                                for r in self.replicas if r is not rep)
            if not target_exists:
                return False  # degraded fleet: colocate rather than stall
            self._mig_id += 1
            mig_id = self._mig_id
        ns = (sched.adapters.namespace(req.adapter_ref.uid)
              if req.adapter_ref is not None else ())
        key = tuple(ns) + (_MIG_SENTINEL, mig_id)
        record = _Migration(req, key, rep.idx, time.monotonic())
        record.version = int(sched.cache.weights_version)

        def on_ready(entry):
            # transfer-thread callback: the handoff entry is probe-visible
            # (or the fetch failed — entry None settles the request on the
            # next pull). Attribute stores are atomic; ready flips LAST.
            record.entry = entry
            record.ready = True
            cb = self.on_migration_ready
            if cb is not None:
                cb()
        record.kv_len = sched.migrate_out(req, key, on_ready)
        if req.handle is not None:
            # a parked request is owned by NO scheduler; result() must
            # drive the fleet until a decode replica adopts it
            req.handle._sched = self._pump_proxy
        with self._lock:
            self._migrations.append(record)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/migrations")
            tel.counter(f"serving/replica/{rep.idx}/migrations_out")
        return True

    # gateway wakeup for parked decode pumps (set by Gateway; None = polling
    # direct-drive callers)
    on_migration_ready = None
    # gateway hook: a freshly added replica needs a pump thread (set by
    # Gateway; None = direct-drive callers, whose pump_once covers it)
    on_replica_added = None

    def pending_migrations(self):
        return len(self._migrations)

    def admit_migrations(self, rep):
        """Let ``rep``'s pump claim parked handoffs (called from that pump's
        thread, once per turn): cancelled/failed records settle on ANY pump;
        ready records admit onto an available decode-capable replica — or
        onto ANY available replica when the decode side has vanished
        entirely (degraded colocation beats stalling the requests).
        Returns the number of records consumed."""
        self._run_pending_warmup(rep)  # runtime role flip: warm on the pump
        if not self._migrations:
            return 0
        sched = rep.scheduler
        consumed = 0
        while True:
            record = None
            settle = False
            with self._lock:
                no_decode_side = not any(r.decode_capable() and r.available()
                                         for r in self.replicas)
                can_admit = (rep.available() and not rep.sick
                             and (rep.decode_capable() or no_decode_side))
                for i, rec in enumerate(self._migrations):
                    # settle only READY records: a cancel racing the
                    # in-flight demote fetch must wait for the store put to
                    # land — settling early would discard nothing and the
                    # late-landing pinned entry would leak forever
                    if rec.ready and (rec.req.cancelled or rec.entry is None):
                        record, settle = rec, True
                        del self._migrations[i]
                        break
                    # held records (brownout parking) settle above but are
                    # never adopted until release_parked() lifts the hold
                    if (rec.ready and can_admit and not rec.req.cancelled
                            and not rec.held):
                        record = rec
                        del self._migrations[i]
                        break
                if record is None:
                    return consumed
            if settle:
                sched.admit_migration(record)  # settles without a slot
                if not record.req.cancelled:
                    self.migrations_failed += 1
                consumed += 1
                continue
            try:
                outcome = sched.admit_migration(record)
            except Exception:
                # the scheduler settled the request before re-raising;
                # account the fleet-level failure, then let the pump's
                # sick-replica handling see the error
                self.migrations_failed += 1
                raise
            if outcome == "resumed":
                consumed += 1
                rep.dispatched += 1
                tel = self.telemetry
                if tel.enabled:
                    tel.counter(f"serving/replica/{rep.idx}/migrations_in")
                    tel.counter("serving/migration_tokens", record.kv_len)
                    tel.histogram("serving/migration_ms",
                                  (time.monotonic() - record.t_start) * 1e3)
            elif outcome == "settled":
                self.migrations_failed += 1
                consumed += 1
            else:  # no free slot on this replica: park it again
                with self._lock:
                    self._migrations.appendleft(record)
                return consumed

    def _fail_handoffs(self):
        """No replica can ever adopt the parked handoffs (the whole fleet is
        sick/unavailable): settle them as failed instead of leaving their
        clients waiting on a queue nobody drains. In-flight demote fetches
        are joined first so their store entries land and can be discarded
        (a late-landing pinned entry would otherwise leak)."""
        for rep in self.replicas:
            tier = rep.scheduler.kv_tier
            if tier is not None:
                tier.executor.drain_fetches()
        with self._lock:
            records, self._migrations = list(self._migrations), collections.deque()
        for rec in records:
            # the primary's settle helper: shared store/adapter refs, and
            # the same cancel-vs-failure accounting as every other settle
            # site (a client cancel landing here is a cancel, not a failure)
            self.primary._settle_migration(
                rec, error="migration failed: no serving replica available")
            if not rec.req.cancelled:
                self.migrations_failed += 1
        return len(records)

    def inject_resume(self, desc, on_token=None, trace=None,
                      collect_logits=False):
        """Cross-process migration, decode side: rebuild the request a
        PREFILL WORKER handed off (its descriptor carries the prompt,
        sampling params, and where the KV is parked) and park it in this
        fleet's migration queue as a READY record whose entry points at the
        remote shard. ``admit_migrations`` then pulls it through the exact
        in-process adoption path — ``admit_migration`` restores the KV
        (the NetPrefixStore fetches the bytes from the owner over HTTP) and
        decode resumes bit-identically: the rebuilt request carries the
        original seed (sampling keys fold ABSOLUTE step indices), the
        already-decoded tokens, and the original budget rounding. Returns
        the request's :class:`~deepspeed_tpu.inference.scheduler.
        SchedulerHandle` (fleet-pumped until adoption). Raises ValueError
        on a descriptor this fleet cannot honor."""
        from ..inference.scheduler import (SchedulerHandle, _Request,
                                           _round_up)
        from ..memory.net_store import RemoteEntry
        if desc.get("adapter_id") is not None:
            raise ValueError("cross-process resume does not carry adapter "
                             "page pins; route adapter traffic to a worker "
                             "with the adapter resident instead")
        sched = self.primary
        if sched.kv_tier is None:
            raise ValueError("resume requires the hierarchical KV tier as "
                             "the migration transport (continuous_batching."
                             "disaggregation or hierarchical_kv)")
        with self._lock:
            self._mig_id += 1
            rid = -self._mig_id  # never collides with submit()'s own rids
        req = _Request(rid, np.asarray(desc["prompt"], np.int32),
                       int(desc["max_new_tokens"]), desc.get("eos_token_id"),
                       bool(desc.get("do_sample", False)),
                       float(desc.get("temperature", 1.0)),
                       int(desc.get("top_k", 0)),
                       float(desc.get("top_p", 1.0)),
                       int(desc.get("seed", 0)), bool(collect_logits),
                       sched.telemetry.now(), on_token=on_token, trace=trace)
        # tokens the prefill side's final fused sync already decoded (and
        # already streamed): part of the KV rows, and the absolute decode
        # step the sampling keys fold continues from len(out)
        req.out = [int(t) for t in desc.get("done_tokens", ())]
        if len(req.out) >= req.max_new_tokens:
            raise ValueError("resume descriptor is already complete")
        req.migrating = True
        # the same overshoot rounding submit() stamped on the original
        # request: admission sizes extent chains against it
        budget = _round_up(req.max_new_tokens, sched.steps_per_sync)
        if sched.spec_tokens > 0:
            budget = max(budget, req.max_new_tokens + sched._spec_width - 1)
        req.row_budget = int(budget)
        handle = SchedulerHandle(self._pump_proxy, req)
        req.handle = handle
        key = tuple(int(t) for t in desc["key"])
        entry = RemoteEntry(key, int(desc["kv_len"]), int(desc["version"]),
                            int(desc.get("nbytes", 0)), True,
                            desc["owner_url"], desc.get("owner_wid"))
        record = _Migration(req, key, None, time.monotonic())
        record.kv_len = int(desc["kv_len"])
        record.version = int(desc["version"])
        record.entry = entry
        record.ready = True
        with self._lock:
            self._migrations.append(record)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/migrations")
        cb = self.on_migration_ready
        if cb is not None:
            cb()
        return handle

    # ---------------------------------------------------------------- dispatch
    def _sticky_key(self, prompt, adapter=None):
        # the adapter id is part of the prefix identity: a prefix cached
        # under adapter A on replica 0 is COLD data for adapter B (the
        # radix roots are per-adapter), so sticky routing must not send
        # B's matching prompt there expecting a hit
        p = np.asarray(prompt, np.int32).reshape(-1)
        return (adapter, p[:self._sticky_chunk].tobytes())

    def route(self, prompt, adapter=None):
        """The replica to place ``prompt`` on, or None when no eligible
        replica has a free slot. Sticky first, least-loaded otherwise; the
        sticky index re-points to wherever placement actually lands, so the
        NEXT matching prompt follows the freshest cached copy. ``adapter``
        scopes stickiness per model variant (multi-LoRA serving)."""
        with self._lock:
            candidates = [r for r in self.replicas
                          if r.available() and r.has_capacity()
                          and r.prefill_capable()]
            if not candidates:
                return None
            key = self._sticky_key(prompt, adapter)
            hit = self._sticky.get(key)
            tel = self.telemetry
            if hit is not None:
                rep = self.replicas[hit]
                if rep.available() and rep.has_capacity() and rep.prefill_capable():
                    self._sticky.move_to_end(key)
                    if tel.enabled:
                        tel.counter("serving/dispatch/sticky")
                    return rep
                if not rep.available() or not rep.prefill_capable():
                    del self._sticky[key]  # sick/draining/decode-role owner: re-home
            known = [r.ema_service_s for r in candidates
                     if r.ema_service_s is not None]
            fallback = (sum(known) / len(known)) if known else 1.0
            n = len(self.replicas)
            rep = min(candidates,
                      key=lambda r: (r.expected_drain_s(fallback),
                                     (r.idx - self._rr) % n))
            self._rr = (rep.idx + 1) % n
            self._record_sticky(key, rep.idx)
            if tel.enabled:
                tel.counter("serving/dispatch/least_loaded")
            return rep

    def _record_sticky(self, key, idx):
        self._sticky[key] = idx
        self._sticky.move_to_end(key)
        while len(self._sticky) > self._sticky_capacity:
            self._sticky.popitem(last=False)

    def dispatch(self, prompt, **submit_kwargs):
        """Route + submit in one step: returns ``(replica, handle)`` or
        ``(None, None)`` when the fleet has no free slot. The direct-drive
        entry point for benches/tests; the gateway calls :meth:`route` and
        submits itself (it owns request bookkeeping)."""
        rep = self.route(prompt, adapter=submit_kwargs.get("adapter_id"))
        if rep is None:
            return None, None
        handle = rep.scheduler.submit(prompt, **submit_kwargs)
        self.note_dispatch(rep)
        return rep, handle

    def note_dispatch(self, rep):
        """Account one placement on ``rep`` (called after a successful
        submit so failed validation doesn't skew the counters)."""
        rep.dispatched += 1
        tel = self.telemetry
        if tel.enabled:
            tel.counter(f"serving/replica/{rep.idx}/dispatched")

    # ---------------------------------------------------------------- drive (tests, dryrun)
    def pump_once(self):
        """One single-threaded fleet turn: let every replica claim parked
        handoffs, then step the non-idle ones. Returns whether anything
        progressed (the gateway's per-replica pump threads do the same two
        calls per turn, one replica each)."""
        progressed = False
        for rep in self.replicas:
            if rep.retired:
                continue
            if self.admit_migrations(rep):
                progressed = True
            if not rep.idle() and not rep.sick:
                rep.step()
                progressed = True
            elif rep.pending_drain and self.finish_scale_down(rep):
                progressed = True
        return progressed

    def drain_all_work(self):
        """Single-threaded convenience pump: step every replica (and place
        parked migrations) until the whole fleet is idle (tests and the
        multichip dryrun; the gateway runs one pump thread per replica)."""
        while True:
            if self.pump_once():
                continue
            if not self._migrations:
                return
            # handoffs pending but nothing progressed: either their
            # device->host fetch is still in flight (join it — ready flips
            # and the next turn places them) or no replica can ever take
            # them (fail rather than spin)
            if any(not rec.ready for rec in list(self._migrations)):
                for rep in self.replicas:
                    tier = rep.scheduler.kv_tier
                    if tier is not None:
                        tier.executor.drain_fetches()
                continue
            if all(rec.held for rec in list(self._migrations)):
                # only brownout-parked records remain and this is a
                # direct-drive pump with no controller to lift the hold:
                # release rather than spin (the gateway path releases
                # explicitly on de-escalation and on begin_drain)
                self.release_parked()
                continue
            if not any(r.available() for r in self.replicas):
                self._fail_handoffs()
                continue
