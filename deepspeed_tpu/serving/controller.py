"""Elastic fleet control plane: SLO-driven autoscaling, phase re-balancing,
and brownout preemption.

The loop-closer over signals and actuators the serving stack already had:
PR 8's multi-window SLO burn rates and PR 15's capacity gauges (MFU /
HBM-bandwidth / host-gap / goodput) *observe* saturation; PR 10's
:class:`~deepspeed_tpu.serving.replica.ReplicaSet` over a shared
compiled-program set, PR 13's runtime role flips + parked handoffs, PR 12's
tiered :class:`~deepspeed_tpu.serving.fair_queue.FairQueue`, and
``handle.cancel()`` are the *actuators* — but nothing connected them, so
sustained overload shed 429s until a human intervened. Runtime instance
re-scheduling and priority preemption are what Llumnix (OSDI '24) shows
recovers tail-latency SLOs; DistServe's phase-split provisioning argument
implies prefill/decode capacity must be RE-BALANCED as the traffic mix
drifts, not sized once.

Design:

- **One snapshot per tick**: the gateway consolidates every signal into a
  :class:`FleetSignals` value (SLO fast/slow burn, queue depth +
  ``oldest_wait_s``, phase-aware saturation split, ``serving/mfu`` /
  ``serving/hbm_bw_util`` / host-gap fraction / ``serving/
  goodput_fraction``, occupancy, fleet size) so a decision reads one
  coherent view, not N racing gauges.

- **Pure decisions**: :meth:`FleetController.decide` consumes only the
  snapshot and the controller's own cooldown stamps — all in the
  snapshot's ``now`` timebase, never the wall clock — so scripted signal
  traces drive grow/shrink/flip/brownout deterministically under test.

- **Ticked from the replica-0 pump**: no new thread owns scheduler state.
  The pump already runs the fleet-wide side duties (SLO evaluation,
  recompile watch) once per turn; the controller joins that slate.

- **Three actuators, cooldown-guarded**:
  (a) *scale* — ``ReplicaSet.add_replica()`` spawns a scheduler over the
  SHARED weight tree + compiled-program dict (zero new XLA programs, so
  warmup is pool allocation); scale-down is two-phase pending-drain →
  retire, freeing the pool's HBM. The host-gap signal VETOES scale-up
  when the host, not the device, is the bottleneck — another replica
  would only add host work.
  (b) *re-balance* — prefill- vs decode-side saturation skew flips one
  replica's role through the existing ``set_role`` protocol (which
  enforces both-phases-coverable).
  (c) *brownout* — a load-shedding ladder: each configured tier yields
  two levels — first EVICT that tier's queued flows from the FairQueue
  (503 + brownout Retry-After), then PREEMPT in-flight work below it
  (``handle.cancel()``, or park-for-resume through the PR 13 migrate-out
  transport). ``serving/goodput_fraction`` prices preemption: a fleet
  mostly doing wasted work (spec-rejected/replayed tokens) escalates
  without waiting out the step cooldown — the preempted work was free.

- **Fully observable**: every decision is an ``autoscale/decision``
  telemetry event carrying the signal vector that justified it, plus
  per-action counters and gauges on ``/v1/metrics`` + Prometheus; ``GET/
  POST /v1/autoscaler`` exposes live state and runtime enable/disable/
  dry-run. ``dry_run`` records decisions without actuating — the rollout
  mode.
"""

import collections
import threading


class FleetSignals:
    """One consolidated, per-tick snapshot of everything a fleet decision
    reads. Plain data; every field has a neutral default so tests can
    construct partial snapshots. ``now`` is the DECISION timebase — the
    gateway stamps ``time.monotonic()``, tests stamp whatever they like,
    and the controller never consults a clock of its own."""

    __slots__ = ("now", "burn_fast", "burn_slow", "queue_depth",
                 "oldest_wait_s", "prefill_sat", "decode_sat", "mfu",
                 "hbm_bw_util", "host_gap_frac", "goodput_fraction",
                 "occupancy", "replicas", "replicas_active", "inflight",
                 "disaggregated")

    def __init__(self, now=0.0, burn_fast=0.0, burn_slow=0.0, queue_depth=0,
                 oldest_wait_s=0.0, prefill_sat=0.0, decode_sat=0.0, mfu=0.0,
                 hbm_bw_util=0.0, host_gap_frac=0.0, goodput_fraction=1.0,
                 occupancy=0.0, replicas=1, replicas_active=1, inflight=0,
                 disaggregated=False):
        self.now = float(now)
        self.burn_fast = float(burn_fast)          # max fast-window SLO burn
        self.burn_slow = float(burn_slow)          # max slow-window SLO burn
        self.queue_depth = int(queue_depth)        # fair-queue depth
        self.oldest_wait_s = float(oldest_wait_s)  # head-of-line queue wait
        self.prefill_sat = float(prefill_sat)      # queued work / prefill slots
        self.decode_sat = float(decode_sat)        # in-flight work / decode slots
        self.mfu = float(mfu)                      # serving/mfu gauge
        self.hbm_bw_util = float(hbm_bw_util)      # serving/hbm_bw_util gauge
        # the host's share of the pumps' syncs since the last snapshot:
        # busy / (busy + wait) of their accounts (Gateway.fleet_signals)
        self.host_gap_frac = float(host_gap_frac)
        self.goodput_fraction = float(goodput_fraction)
        self.occupancy = float(occupancy)          # busy slots / total slots
        self.replicas = int(replicas)              # non-retired fleet size
        self.replicas_active = int(replicas_active)  # placement-eligible
        self.inflight = int(inflight)              # admitted, unfinished
        self.disaggregated = bool(disaggregated)

    def vector(self):
        """The signal vector a decision event records (plain floats/ints —
        json-serializable for telemetry and /v1/autoscaler)."""
        return {name: getattr(self, name) for name in self.__slots__}


class FleetController:
    """SLO-driven fleet controller. The gateway constructs it with the
    ``continuous_batching.autoscaler`` config section and binds the four
    actuator callables; :meth:`tick` runs once per replica-0 pump turn
    with a fresh :class:`FleetSignals` snapshot.

    Actuators (bound by the gateway; any may stay None — the decision is
    still recorded, marked unapplied):

    - ``scale_up_fn()`` -> bool — add one replica.
    - ``scale_down_fn()`` -> bool — begin retiring one replica.
    - ``rebalance_fn(phase)`` -> bool — flip one replica's role toward
      ``phase`` (``"prefill"``/``"decode"``).
    - ``brownout_fn(level)`` -> bool — move the shedding ladder to
      ``level`` (0 = off; odd = evict queued below tier, even = preempt
      in-flight below tier, tiers advancing per config).

    The decision ladder returns AT MOST ONE action per tick — legibility
    and testability over reaction latency (the tick interval is seconds;
    compound emergencies resolve over a few ticks).
    """

    def __init__(self, config, telemetry=None):
        self.config = config
        self.telemetry = telemetry
        self.enabled = bool(config.enabled)
        self.dry_run = bool(config.dry_run)
        self.scale_up_fn = None
        self.scale_down_fn = None
        self.rebalance_fn = None
        self.brownout_fn = None
        # brownout ladder position: 0 = off; level (2i+1, 2i+2) = (evict
        # queued, preempt in-flight) below tier config.brownout_tiers[i]
        self.brownout_level = 0
        self.max_brownout = 2 * len(list(config.brownout_tiers or []))
        # cooldown stamps, all in the SNAPSHOT timebase (sig.now): None =
        # never. No wall clock anywhere in the decision path.
        self._last_tick = None
        self._last_scale_up = None
        self._last_scale = None      # either direction (down-cooldown basis)
        self._last_flip = None
        self._last_brownout_step = None
        self._last_overload = None
        self.counters = collections.Counter()
        self.decisions = collections.deque(maxlen=64)  # /v1/autoscaler ring
        self._lock = threading.Lock()  # admin (event loop) vs pump tick

    # ------------------------------------------------------------------ policy
    def brownout_tier(self, level=None):
        """The tier name a ladder level sheds below (None at level 0)."""
        level = self.brownout_level if level is None else level
        tiers = list(self.config.brownout_tiers or [])
        if level <= 0 or not tiers:
            return None
        return tiers[min((level - 1) // 2, len(tiers) - 1)]

    def _overloaded(self, sig):
        cfg = self.config
        burn_hot = (sig.burn_fast >= cfg.scale_up_burn
                    and sig.burn_slow >= cfg.slow_burn_floor)
        return burn_hot or sig.oldest_wait_s >= cfg.queue_wait_up_s

    def _elapsed(self, stamp, now, hold):
        return stamp is None or (now - stamp) >= hold

    def decide(self, sig):
        """The pure decision function: one :class:`FleetSignals` snapshot
        (+ the controller's cooldown stamps) -> at most one action dict,
        or None. Never touches a clock, an actuator, or the telemetry
        sink — :meth:`tick` owns side effects."""
        cfg = self.config
        now = sig.now
        overloaded = self._overloaded(sig)
        if overloaded:
            self._last_overload = now
            # (a) grow: device-bound overload with headroom and a cold
            # cooldown. Host-bound overload (host_gap_frac at/above the
            # veto) must NOT grow — the bottleneck is the pump/host side,
            # and another replica only adds host work.
            host_bound = sig.host_gap_frac >= cfg.host_gap_veto
            if (sig.replicas < int(cfg.max_replicas) and not host_bound
                    and self._elapsed(self._last_scale_up, now,
                                      float(cfg.cooldown_up_s))):
                return {"action": "scale_up",
                        "reason": ("slo_burn" if sig.burn_fast >= cfg.scale_up_burn
                                   else "queue_wait")}
            # (c) shed: can't (or shouldn't) grow — escalate the ladder.
            # goodput below the free threshold waives the step cooldown:
            # preempting mostly-wasted work costs nothing.
            if self.brownout_level < self.max_brownout:
                free = sig.goodput_fraction < float(cfg.goodput_free_threshold)
                if free or self._elapsed(self._last_brownout_step, now,
                                         float(cfg.brownout_step_s)):
                    return {"action": "brownout",
                            "level": self.brownout_level + 1,
                            "reason": ("host_bound" if host_bound else
                                       "at_max_replicas" if sig.replicas >= int(cfg.max_replicas)
                                       else "scale_cooldown")
                                      + ("+goodput_free" if free else "")}
            return None  # overloaded but every move is cooldown-blocked
        # calm path ----------------------------------------------------
        if self.brownout_level > 0:
            # de-escalate one level after a sustained calm window (and a
            # step cooldown so the ladder doesn't slam open)
            if (self._elapsed(self._last_overload, now,
                              float(cfg.brownout_cooldown_s))
                    and self._elapsed(self._last_brownout_step, now,
                                      float(cfg.brownout_step_s))):
                return {"action": "brownout",
                        "level": self.brownout_level - 1,
                        "reason": "calm"}
            return None  # ladder engaged: hold before considering scale
        # (b) re-balance: phase saturation skew on a disaggregated fleet
        if sig.disaggregated and self._elapsed(self._last_flip, now,
                                               float(cfg.cooldown_flip_s)):
            ratio = float(cfg.rebalance_ratio)
            hi, lo = max(sig.prefill_sat, sig.decode_sat), \
                min(sig.prefill_sat, sig.decode_sat)
            # the busy side must be meaningfully loaded (>= 0.5 of its
            # capacity) — flipping an idle fleet's roles is churn
            if hi >= 0.5 and hi >= ratio * max(lo, 1e-9):
                phase = ("prefill" if sig.prefill_sat > sig.decode_sat
                         else "decode")
                return {"action": "rebalance", "phase": phase,
                        "reason": f"{phase}_saturated"}
        # shrink: both windows cold, queue empty, fleet mostly idle
        if (sig.replicas > max(1, int(cfg.min_replicas))
                and sig.burn_fast <= float(cfg.scale_down_burn)
                and sig.burn_slow <= float(cfg.scale_down_burn)
                and sig.queue_depth == 0
                and sig.occupancy <= float(cfg.scale_down_occupancy)
                and self._elapsed(self._last_scale, now,
                                  float(cfg.cooldown_down_s))):
            return {"action": "scale_down", "reason": "idle"}
        return None

    # ------------------------------------------------------------------ tick
    def tick(self, sig):
        """One control interval: rate-limit by ``interval_s`` (in the
        snapshot timebase), decide, actuate (unless dry_run), record.
        Returns the decision record, or None when idle/rate-limited."""
        if not self.enabled:
            return None
        now = sig.now
        if (self._last_tick is not None
                and now - self._last_tick < float(self.config.interval_s)):
            return None
        self._last_tick = now
        decision = self.decide(sig)
        if decision is None:
            return None
        decision["signals"] = sig.vector()
        decision["dry_run"] = self.dry_run
        applied = False
        if not self.dry_run:
            applied = self._apply(decision, now)
        else:
            # dry-run still advances the cooldown stamps: without this a
            # sustained overload re-decides the SAME action on every tick
            # (interval_s of scale_up spam), and the recorded stream no
            # longer resembles what a live controller would do — which is
            # the whole point of the dry-run rollout step. Actuators and
            # the brownout level stay untouched: dry-run proposes, never
            # moves.
            self._stamp(decision["action"], now)
        decision["applied"] = applied
        with self._lock:
            self.decisions.append(decision)
        self.counters[decision["action"]] += 1
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.event("autoscale/decision",
                      {k: v for k, v in decision.items()})
            tel.counter(f"autoscale/{decision['action']}")
            if not applied and not self.dry_run:
                tel.counter("autoscale/actuator_noop")
        return decision

    def _stamp(self, action, now):
        """Advance the cooldown stamp(s) an ``action`` paces on."""
        if action == "scale_up":
            self._last_scale_up = self._last_scale = now
        elif action == "scale_down":
            self._last_scale = now
        elif action == "rebalance":
            self._last_flip = now
        elif action == "brownout":
            self._last_brownout_step = now

    def _apply(self, decision, now):
        """Drive the bound actuator; update cooldown stamps only on
        SUCCESS (a failed actuation should retry next tick, not burn the
        cooldown)."""
        action = decision["action"]
        try:
            if action == "scale_up" and self.scale_up_fn is not None:
                if self.scale_up_fn():
                    self._stamp(action, now)
                    return True
            elif action == "scale_down" and self.scale_down_fn is not None:
                if self.scale_down_fn():
                    self._stamp(action, now)
                    return True
            elif action == "rebalance" and self.rebalance_fn is not None:
                if self.rebalance_fn(decision["phase"]):
                    self._stamp(action, now)
                    return True
            elif action == "brownout" and self.brownout_fn is not None:
                level = int(decision["level"])
                if self.brownout_fn(level):
                    self.brownout_level = level
                    self._stamp(action, now)
                    return True
        except Exception:  # noqa: BLE001 — a failing actuator must not
            # kill the pump; the decision records applied=False and the
            # gateway's own error handling covers the actuator's side
            pass
        return False

    # ------------------------------------------------------------------ surface
    def state(self):
        """GET /v1/autoscaler payload (and the /v1/metrics rollup)."""
        with self._lock:
            recent = list(self.decisions)[-16:]
        return {
            "enabled": self.enabled,
            "dry_run": self.dry_run,
            "brownout_level": self.brownout_level,
            "brownout_tier": self.brownout_tier(),
            "max_brownout_level": self.max_brownout,
            "counters": dict(self.counters),
            "config": {
                "min_replicas": int(self.config.min_replicas),
                "max_replicas": int(self.config.max_replicas),
                "interval_s": float(self.config.interval_s),
                "scale_up_burn": float(self.config.scale_up_burn),
                "scale_down_burn": float(self.config.scale_down_burn),
                "queue_wait_up_s": float(self.config.queue_wait_up_s),
                "cooldown_up_s": float(self.config.cooldown_up_s),
                "cooldown_down_s": float(self.config.cooldown_down_s),
                "host_gap_veto": float(self.config.host_gap_veto),
                "brownout_tiers": list(self.config.brownout_tiers or []),
                "brownout_park": bool(self.config.brownout_park),
                "rebalance_ratio": float(self.config.rebalance_ratio),
            },
            "recent_decisions": recent,
        }

    def admin(self, body):
        """POST /v1/autoscaler: runtime enable/disable/dry-run toggles
        (``{"enabled": bool, "dry_run": bool}``; unknown keys 400 at the
        gateway). Returns the fields that changed."""
        changed = {}
        if "enabled" in body:
            self.enabled = bool(body["enabled"])
            changed["enabled"] = self.enabled
        if "dry_run" in body:
            self.dry_run = bool(body["dry_run"])
            changed["dry_run"] = self.dry_run
        return changed
