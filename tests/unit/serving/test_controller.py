"""Elastic fleet control plane (``serving/controller.py`` + the gateway's
actuators + ``ReplicaSet``'s elastic lifecycle).

Three layers, cheapest first: the FairQueue's brownout surface (pure data
structure), the :class:`FleetController` decision ladder driven by SCRIPTED
:class:`FleetSignals` traces (no engine, no clock — the determinism the
pure-decide design exists for), and the engine-backed lifecycle: mid-stream
``add_replica`` bit-identity, the zero-new-XLA-programs guard across a full
grow -> park -> shrink -> role-flip cycle, and the gateway's brownout door
over real HTTP."""

import http.client
import json
import threading
import time

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.inference.config import AutoscalerConfig
from deepspeed_tpu.serving import (FairQueue, FleetController, FleetSignals,
                                   Gateway, ReplicaSet)

_XLA_COMPILES = []  # registered once: jax.monitoring listeners can't detach


def _count_xla_compiles():
    if not _XLA_COMPILES:
        _XLA_COMPILES.append("registered")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _XLA_COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _XLA_COMPILES


def make_engine(params=None, num_slots=2, roles=None, telemetry=None,
                **cb_extra):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)  # sink hermeticity: no cross-test counter bleed
    cb = {"enabled": True, "num_slots": num_slots}
    if roles is not None:
        cb["replicas"] = len(roles)
        cb["disaggregation"] = {"enabled": True, "roles": roles,
                                "migrate_min_tokens": 0}
    cb.update(cb_extra)
    cfg = {"dtype": "float32", "max_out_tokens": 512,
           "continuous_batching": cb}
    if telemetry:
        cfg["telemetry"] = telemetry
    return deepspeed_tpu.init_inference("tiny", config=cfg, params=params)


@pytest.fixture(scope="module")
def params():
    eng = make_engine()
    return jax.device_get(eng.params)


# ------------------------------------------------------------ fair queue
def _queue():
    return FairQueue(max_depth=32, priority_weights={
        "interactive": 4.0, "standard": 2.0, "batch": 1.0})


def test_flow_stats_depth_and_head_wait():
    q = _queue()
    q.push("a1", "acme", "standard", cost=5)
    q.push("a2", "acme", "standard", cost=5)
    q.push("b1", "bob", "batch", cost=1)
    stats = q.flow_stats()
    assert stats[("acme", "standard")]["depth"] == 2
    assert stats[("acme", "standard")]["weight"] == 2.0
    assert stats[("bob", "batch")]["priority"] == "batch"
    assert stats[("bob", "batch")]["oldest_wait_s"] >= 0.0
    # head wait tracks the FIRST enqueue, and is monotone with real time
    time.sleep(0.02)
    assert q.flow_stats()[("acme", "standard")]["oldest_wait_s"] >= 0.02


def test_tier_weight_unknown_is_floor():
    q = _queue()
    assert q.tier_weight("interactive") == 4.0
    assert q.tier_weight("nonsense") == 1.0  # floor — no invented fast lane


def test_evict_flows_sheds_strictly_below_tier():
    q = _queue()
    q.push("i1", "t", "interactive")
    q.push("s1", "t", "standard")
    q.push("s2", "u", "standard")
    q.push("b1", "t", "batch")
    q.push("b2", "u", "batch")
    evicted = q.evict_flows("standard")
    # strictly below the bar: batch goes, standard itself stays
    assert sorted(item for item, _, _ in evicted) == ["b1", "b2"]
    assert all(prio == "batch" for _, _, prio in evicted)
    assert len(q) == 3
    # the survivors still pop in DRR order without a corrupted rotation
    popped = [q.pop() for _ in range(3)]
    assert sorted(popped) == ["i1", "s1", "s2"]
    assert q.pop() is None and len(q) == 0


def test_evict_flows_unknown_tier_evicts_nothing():
    q = _queue()
    q.push("b1", "t", "batch")
    # unknown tier resolves to the FLOOR weight; strict comparison means
    # it evicts nothing rather than everything (a typo'd config must not
    # shed the whole queue)
    assert q.evict_flows("not-a-tier") == []
    assert len(q) == 1


def test_evict_flows_tenant_weight_does_not_shield():
    q = FairQueue(max_depth=32, tenant_weights={"vip": 100.0},
                  priority_weights={"standard": 2.0, "batch": 1.0})
    q.push("vip-batch", "vip", "batch")
    q.push("std", "t", "standard")
    evicted = q.evict_flows("standard")
    assert [item for item, _, _ in evicted] == ["vip-batch"]


# ------------------------------------------------------------ controller
def make_ctl(**over):
    cfg = {"enabled": True, "interval_s": 0.0, "min_replicas": 1,
           "max_replicas": 3, "scale_up_burn": 2.0, "slow_burn_floor": 1.0,
           "queue_wait_up_s": 5.0, "scale_down_burn": 0.5,
           "scale_down_occupancy": 0.3, "cooldown_up_s": 10.0,
           "cooldown_down_s": 30.0, "host_gap_veto": 0.5,
           "brownout_tiers": ["batch", "standard"], "brownout_step_s": 5.0,
           "brownout_cooldown_s": 15.0, "goodput_free_threshold": 0.5,
           "rebalance_ratio": 2.0, "cooldown_flip_s": 20.0}
    cfg.update(over)
    ctl = FleetController(AutoscalerConfig(cfg))
    ctl.applied = []
    ctl.scale_up_fn = lambda: ctl.applied.append("up") or True
    ctl.scale_down_fn = lambda: ctl.applied.append("down") or True
    ctl.rebalance_fn = lambda p: ctl.applied.append(f"flip:{p}") or True
    ctl.brownout_fn = lambda lv: ctl.applied.append(f"brownout:{lv}") or True
    return ctl


def hot(now, **over):
    base = dict(now=now, burn_fast=3.0, burn_slow=1.5, queue_depth=8,
                oldest_wait_s=1.0, occupancy=0.9, replicas=1,
                replicas_active=1)
    base.update(over)
    return FleetSignals(**base)


def calm(now, **over):
    base = dict(now=now, burn_fast=0.0, burn_slow=0.0, queue_depth=0,
                oldest_wait_s=0.0, occupancy=0.1, replicas=2,
                replicas_active=2)
    base.update(over)
    return FleetSignals(**base)


def test_scale_up_on_burn_and_on_queue_wait():
    ctl = make_ctl()
    d = ctl.tick(hot(10.0))
    assert d["action"] == "scale_up" and d["reason"] == "slo_burn"
    assert d["applied"] and ctl.applied == ["up"]
    # queue-wait trigger fires without any SLO burn
    ctl2 = make_ctl()
    d2 = ctl2.tick(FleetSignals(now=10.0, oldest_wait_s=6.0, replicas=1))
    assert d2["action"] == "scale_up" and d2["reason"] == "queue_wait"


def test_fast_burn_alone_does_not_scale():
    """The slow-window floor is the false-positive guard: a fast-window
    spike with a cold slow window (and no queue wait) must not grow."""
    ctl = make_ctl()
    assert ctl.tick(hot(10.0, burn_slow=0.0, oldest_wait_s=0.0)) is None


def test_host_gap_vetoes_scale_up_into_brownout():
    ctl = make_ctl()
    d = ctl.tick(hot(10.0, host_gap_frac=0.8))
    assert d["action"] == "brownout" and d["level"] == 1
    assert "host_bound" in d["reason"]
    assert ctl.brownout_level == 1


@pytest.mark.parametrize("busy_ms,wait_ms,vetoed", [(9.0, 1.0, True), (2.0, 8.0, False)])
def test_fleet_signals_host_fraction_is_the_account_s_not_the_gaps(
        params, tmp_path, busy_ms, wait_ms, vetoed):
    """D17: behind a pump that runs ahead every device-idle gap reads 0.0,
    so the veto's input is the account's own totals over the tick: a host
    busy 0.9 of its syncs vetoes the scale-up whatever the gaps say."""
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)})
    gw = Gateway(eng, port=0)               # never started: the signals alone
    gap = eng.scheduler()._gap
    gap._unlanded = lambda: True            # a pump that runs ahead
    assert gw.fleet_signals(now=0.0).host_gap_frac == 0.0   # nothing to compare with yet
    t = 0.0
    for _ in range(20):                     # twenty syncs of busy + wait
        gap.span_enter("sched/step", t)
        gap.span_enter("sched/dispatch", t + 0.0005)
        gap.span_exit("sched/dispatch", t + 0.0005, t + 0.001)
        t += (busy_ms + wait_ms) / 1e3
        gap.span_enter("sched/fetch", t - wait_ms / 1e3)
        gap.span_exit("sched/fetch", t - wait_ms / 1e3, t)
        gap.span_exit("sched/step", t - (busy_ms + wait_ms) / 1e3, t)
    assert gap.gaps >= 19 and gap.total_gap_s == 0.0        # every gap 0.0
    sig = gw.fleet_signals(now=1.0)
    assert sig.host_gap_frac == pytest.approx(busy_ms / (busy_ms + wait_ms))
    d = make_ctl().tick(hot(10.0, host_gap_frac=sig.host_gap_frac))
    if vetoed:
        assert d["action"] == "brownout" and "host_bound" in d["reason"]
    else:
        assert d["action"] == "scale_up"
    # the next tick sees only what was accounted since this one: nothing
    assert gw.fleet_signals(now=2.0).host_gap_frac == 0.0
    eng.telemetry.close()


def test_at_max_replicas_escalates_brownout_ladder():
    ctl = make_ctl()
    trace, t = [], 0.0
    for _ in range(6):
        d = ctl.tick(hot(t, replicas=3))
        if d is not None:
            trace.append((d["action"], d.get("level")))
        t += 6.0  # > brownout_step_s between ticks
    # ladder: evict batch -> preempt batch -> evict standard -> preempt
    # standard -> saturated at max (2 tiers x 2 modes)
    assert trace == [("brownout", 1), ("brownout", 2), ("brownout", 3),
                     ("brownout", 4)]
    assert ctl.brownout_level == ctl.max_brownout == 4
    assert ctl.brownout_tier() == "standard"
    assert ctl.brownout_tier(1) == "batch"


def test_scale_up_cooldown_brownouts_then_recovers():
    ctl = make_ctl()
    assert ctl.tick(hot(0.0))["action"] == "scale_up"
    # still overloaded inside the up-cooldown: shed instead of growing
    d = ctl.tick(hot(6.0, replicas=2))
    assert d["action"] == "brownout" and "scale_cooldown" in d["reason"]
    # cooldown elapsed: grows again (the engaged ladder holds its level
    # while overloaded — de-escalation needs calm)
    assert ctl.tick(hot(12.0, replicas=2))["action"] == "scale_up"


def test_goodput_free_waives_brownout_step_cooldown():
    ctl = make_ctl()
    assert ctl.tick(hot(0.0, replicas=3))["level"] == 1
    # 1s later — step cooldown cold, but goodput collapsed: escalation is
    # free (the preempted work was mostly waste) and must not wait
    d = ctl.tick(hot(1.0, replicas=3, goodput_fraction=0.2))
    assert d["action"] == "brownout" and d["level"] == 2
    assert "goodput_free" in d["reason"]
    # healthy goodput + cold step cooldown: held
    assert ctl.tick(hot(2.0, replicas=3)) is None


def test_brownout_deescalates_only_after_calm_window():
    ctl = make_ctl()
    assert ctl.tick(hot(0.0, replicas=3))["level"] == 1
    # calm, but inside brownout_cooldown_s since the last overload: hold
    assert ctl.tick(calm(10.0)) is None
    d = ctl.tick(calm(16.0))
    assert d["action"] == "brownout" and d["level"] == 0
    assert ctl.brownout_level == 0


def test_scale_down_needs_idle_queue_burn_and_cooldown():
    ctl = make_ctl()
    assert ctl.tick(calm(0.0))["action"] == "scale_down"
    # inside cooldown_down_s of that scale: held even though fully calm
    assert ctl.tick(calm(10.0)) is None
    # past the cooldown, every remaining guard individually blocks it
    assert ctl.tick(calm(100.0, queue_depth=1)) is None
    assert ctl.tick(calm(200.0, occupancy=0.5)) is None
    assert ctl.tick(calm(300.0, burn_fast=1.0)) is None
    assert ctl.tick(calm(400.0, replicas=1)) is None
    assert ctl.tick(calm(500.0))["action"] == "scale_down"


def test_rebalance_on_phase_skew_both_directions():
    ctl = make_ctl()
    d = ctl.tick(calm(0.0, disaggregated=True, prefill_sat=1.2,
                      decode_sat=0.1, occupancy=0.5))
    assert d["action"] == "rebalance" and d["phase"] == "prefill"
    d = ctl.tick(calm(30.0, disaggregated=True, prefill_sat=0.1,
                      decode_sat=1.2, occupancy=0.5))
    assert d["action"] == "rebalance" and d["phase"] == "decode"
    # an idle skew (busy side under half its capacity) is churn, not
    # pressure (occupancy 0.5 keeps scale_down out of the picture)
    assert ctl.tick(calm(60.0, disaggregated=True, prefill_sat=0.4,
                         decode_sat=0.05, occupancy=0.5)) is None
    # a non-disaggregated fleet never re-balances
    ctl2 = make_ctl()
    assert ctl2.tick(calm(0.0, prefill_sat=1.2, decode_sat=0.1,
                          replicas=1)) is None


def test_tick_interval_rate_limits():
    ctl = make_ctl(interval_s=2.0)
    assert ctl.tick(hot(0.0))["action"] == "scale_up"
    # inside the interval the tick is a no-op even with hot signals
    assert ctl.tick(hot(1.0, replicas=3)) is None
    assert ctl.tick(hot(2.5, replicas=3)) is not None


def test_dry_run_records_without_actuating():
    ctl = make_ctl(dry_run=True)
    d = ctl.tick(hot(0.0))
    assert d["action"] == "scale_up" and d["dry_run"] and not d["applied"]
    assert ctl.applied == []
    # decisions ring + counters still record (the rollout surface)
    assert ctl.counters["scale_up"] == 1
    assert ctl.state()["recent_decisions"][-1]["action"] == "scale_up"


def test_dry_run_paces_on_the_same_cooldowns():
    """Dry-run must advance cooldown stamps even though nothing actuates:
    a sustained overload otherwise re-proposes scale_up on EVERY tick and
    the recorded stream stops resembling what a live controller would do
    (the decision-storm the rollout recipe would then misread)."""
    ctl = make_ctl(dry_run=True)
    assert ctl.tick(hot(0.0))["action"] == "scale_up"
    # inside cooldown_up_s the overload escalates the brownout ladder
    # instead of re-proposing the same (unactuated) scale_up...
    d = ctl.tick(hot(1.0))
    assert d is not None and d["action"] == "brownout"
    # ...and inside brownout_step_s the overloaded tick proposes nothing
    assert ctl.tick(hot(2.0)) is None
    assert ctl.counters["scale_up"] == 1
    # past the scale cooldown the proposal is allowed again
    assert ctl.tick(hot(11.0))["action"] == "scale_up"
    assert ctl.applied == [] and ctl.brownout_level == 0


def test_failed_actuator_does_not_burn_cooldown():
    ctl = make_ctl()
    ctl.scale_up_fn = lambda: False
    d = ctl.tick(hot(0.0))
    assert d["action"] == "scale_up" and not d["applied"]
    ctl.scale_up_fn = lambda: True
    # next tick retries immediately: the failed attempt burned no cooldown
    assert ctl.tick(hot(0.5))["applied"]


def test_decision_carries_signal_vector():
    ctl = make_ctl()
    d = ctl.tick(hot(0.0, mfu=0.42))
    assert d["signals"]["mfu"] == 0.42
    assert d["signals"]["burn_fast"] == 3.0
    json.dumps(d)  # the telemetry/HTTP surface needs plain-JSON decisions


def test_admin_toggles_runtime():
    ctl = make_ctl(enabled=False)
    assert ctl.tick(hot(0.0)) is None
    assert ctl.admin({"enabled": True}) == {"enabled": True}
    assert ctl.tick(hot(1.0))["action"] == "scale_up"
    ctl.admin({"dry_run": True})
    assert ctl.state()["dry_run"]


# ------------------------------------------------------- elastic lifecycle
def test_add_replica_mid_stream_bit_identity_zero_programs(params):
    """Grow the fleet WHILE a request is mid-decode: the in-flight stream
    and a stream served on the new replica are both bit-identical to a
    never-resized run, and the grow adds zero XLA programs."""
    compiles = _count_xla_compiles()
    prompts = [[5, 6, 7, 8, 9], [10, 11, 12, 13, 14]]

    def ref():
        eng = make_engine(params)
        rs = ReplicaSet.build(eng, 1)
        hs = [rs.replicas[0].scheduler.submit(
            p, max_new_tokens=8, do_sample=True, temperature=0.8, top_k=9,
            seed=1000 + i) for i, p in enumerate(prompts)]
        rs.drain_all_work()
        return [np.asarray(h.result()) for h in hs]

    expected = ref()
    eng = make_engine(params)
    rs = ReplicaSet.build(eng, 1)
    r0 = rs.replicas[0]
    h0 = r0.scheduler.submit(prompts[0], max_new_tokens=8, do_sample=True,
                             temperature=0.8, top_k=9, seed=1000)
    for _ in range(3):  # mid-stream
        r0.step()
    before_programs = rs.compiled_program_count()
    before_compiles = len(compiles)
    rep = rs.add_replica()
    assert rep.idx == 1 and rs.active_count() == 2
    h1 = rep.scheduler.submit(prompts[1], max_new_tokens=8, do_sample=True,
                              temperature=0.8, top_k=9, seed=1001)
    rs.drain_all_work()
    np.testing.assert_array_equal(np.asarray(h0.result()), expected[0])
    np.testing.assert_array_equal(np.asarray(h1.result()), expected[1])
    assert rs.compiled_program_count() == before_programs
    assert len(compiles) == before_compiles, \
        f"add_replica compiled {len(compiles) - before_compiles} XLA programs"


def test_scale_down_two_phase_frees_pool_and_reuses_index(params):
    eng = make_engine(params)
    rs = ReplicaSet.build(eng, 1)
    rep = rs.add_replica()
    h = rep.scheduler.submit([5, 6, 7], max_new_tokens=8)
    rs.begin_scale_down(rep.idx)
    # phase 1: immediately out of every capacity surface, work unharmed
    assert not rep.available() and rep.pending_drain and not rep.retired
    assert rs.finish_scale_down(rep) is False  # not idle yet: refuses
    rs.drain_all_work()  # the pump retires the pending replica once idle
    assert len(h.result()) == 8
    assert rep.retired and rep.scheduler.cache.pool is None  # HBM freed
    assert rs.active_count() == 1
    assert rep.state()["status"] == "retired"
    assert rs.finish_scale_down(rep) is False  # idempotent post-retire
    # primary can never scale down; retired idx is reused densely
    with pytest.raises(ValueError):
        rs.begin_scale_down(0)
    rep2 = rs.add_replica()
    assert rep2.idx == rep.idx and rs.active_count() == 2
    h2 = rep2.scheduler.submit([5, 6, 7], max_new_tokens=4)
    rs.drain_all_work()
    assert h2.done and len(h2.result()) == 4


def test_grow_park_shrink_roleflip_cycle_bit_identical(params):
    """THE acceptance cycle: grow -> brownout-park -> release -> shrink ->
    role-flip on one fleet, with every token stream bit-identical to a
    never-resized disaggregated run and ZERO new XLA programs after the
    initial warmup."""
    compiles = _count_xla_compiles()
    prompts = [[5, 6, 7, 8, 9], [9, 8, 7, 6, 5], [1, 2, 3, 4, 5],
               [11, 12, 13, 14, 15]]

    def serve(rs, i, p):
        # prompt 2 decodes long enough to span several multi-step sync
        # rounds — the park must land MID-decode, so there has to be an
        # observable window where the request is active but unfinished
        mnt = 48 if i == 2 else 8
        while True:
            _, h = rs.dispatch(p, max_new_tokens=mnt, do_sample=(i % 2 == 1),
                               temperature=0.8, top_k=9, seed=2000 + i)
            if h is not None:
                return h
            rs.pump_once()

    # reference: same fleet shape, never resized
    eng = make_engine(params, roles=["prefill", "decode"])
    rs = ReplicaSet.build(eng)
    handles = [serve(rs, i, p) for i, p in enumerate(prompts)]
    rs.drain_all_work()
    expected = [np.asarray(h.result()) for h in handles]

    eng = make_engine(params, roles=["prefill", "decode"])
    rs = ReplicaSet.build(eng)
    # warm every program before the snapshot: the tier handoff pair plus
    # BOTH sampling variants of the fused step (h0 greedy, h1 sampled —
    # the step program is keyed on whether any batched request samples).
    # Served SEQUENTIALLY: concurrent warmup can race the async migration
    # adoption such that the greedy request never decodes a sync alone,
    # leaving the greedy steady-decode variant to compile post-snapshot
    h0 = serve(rs, 0, prompts[0])
    rs.drain_all_work()
    h1 = serve(rs, 1, prompts[1])
    rs.drain_all_work()
    before_programs = rs.compiled_program_count()
    before_compiles = len(compiles)

    # grow (shared programs), serve through the bigger fleet
    rep = rs.add_replica()

    # brownout-park: demote a mid-decode request's KV, hold it, release
    h2 = serve(rs, 2, prompts[2])
    req = h2._req
    for _ in range(200):
        owner = next((r for r in rs if r.scheduler.owns(req)), None)
        if (owner is not None and owner.decode_capable()
                and req.slot is not None
                and owner.scheduler.active.get(req.slot) is req
                and len(req.out) > 0):
            break
        rs.pump_once()
    else:
        pytest.fail("request never reached steady decode")
    rec = rs.park_out(owner, req)
    assert rec is not None and rec.held
    assert req.slot is None  # the decode slot freed the moment it parked
    # held records are never adopted by the pull rotation (drain the async
    # demote fetch so the record is READY and the hold is what blocks it)
    for r in rs:
        if r.scheduler.kv_tier is not None:
            r.scheduler.kv_tier.executor.drain_fetches()
    for r in rs:
        rs.admit_migrations(r)
    assert not h2.done and req.slot is None and rs.pending_migrations() == 1
    # ...until the brownout lifts
    assert rs.release_parked() == 1

    # shrink the grown replica away mid-fleet
    rs.begin_scale_down(rep.idx)

    # role-flip: the decode replica becomes mixed and back (runtime
    # re-balance on a warm fleet)
    rs.set_role(1, "mixed")
    rs.set_role(1, "decode")

    h3 = serve(rs, 3, prompts[3])
    rs.drain_all_work()
    for h, exp in zip((h0, h1, h2, h3), expected):
        np.testing.assert_array_equal(np.asarray(h.result()), exp)
    assert rep.retired  # drain's pump retired the pending replica
    assert rs.compiled_program_count() == before_programs
    assert len(compiles) == before_compiles, \
        (f"grow/park/shrink/flip cycle compiled "
         f"{len(compiles) - before_compiles} new XLA programs")


# ------------------------------------------------------------ gateway e2e
def _post(port, body, headers=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json", **(headers or {})})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), json.loads(resp.read())
    finally:
        conn.close()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _admin(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_gateway_autoscaler_surface_and_brownout_door(params):
    """The HTTP half: /v1/autoscaler GET/POST, the brownout door shedding
    below-bar arrivals with the brownout Retry-After, and elastic grow/
    shrink through the gateway's own actuators with zero new programs."""
    compiles = _count_xla_compiles()
    eng = make_engine(params, autoscaler={"enabled": False, "max_replicas": 3,
                                          "brownout_tiers": ["standard"],
                                          "brownout_retry_after_s": 17})
    gw = Gateway(eng, port=0, request_timeout_s=60)
    gw.start_background()
    try:
        port = gw.port
        st, out = _get(port, "/v1/autoscaler")
        assert st == 200 and out["enabled"] is False
        assert out["max_brownout_level"] == 2
        # runtime toggles; unknown keys refuse
        st, out = _admin(port, "/v1/autoscaler", {"dry_run": True})
        assert st == 200 and out["changed"] == {"dry_run": True}
        st, _ = _admin(port, "/v1/autoscaler", {"bogus": 1})
        assert st == 400
        _admin(port, "/v1/autoscaler", {"dry_run": False})

        st, _, out = _post(port, {"prompt": [5, 6, 7], "max_tokens": 8})
        assert st == 200 and len(out["choices"][0]["token_ids"]) == 8
        before_programs = gw.replicas.compiled_program_count()
        before_compiles = len(compiles)

        # grow through the gateway actuator: a pump thread spawns and the
        # new replica serves — with zero new XLA programs
        assert gw._scale_up()
        assert gw.replicas.active_count() == 2
        st, _, _ = _post(port, {"prompt": [5, 6, 7], "max_tokens": 8})
        assert st == 200
        assert gw.replicas.compiled_program_count() == before_programs
        assert len(compiles) == before_compiles

        # brownout level 1: below-"standard" arrivals shed at the door
        # with the brownout Retry-After; standard itself still serves
        # (the controller stays disabled, so the level holds for the test)
        assert gw._set_brownout(1)
        gw.autoscaler.brownout_level = 1
        st, hdrs, _ = _post(port, {"prompt": [5, 6], "max_tokens": 4},
                            headers={"x-priority": "batch"})
        assert st == 503 and hdrs.get("Retry-After") == "17"
        st, _, _ = _post(port, {"prompt": [5, 6], "max_tokens": 4})
        assert st == 200
        assert gw.stats["brownout_shed"] == 1
        st, out = _get(port, "/v1/metrics")
        assert out["gateway"]["brownout_shed"] == 1
        assert out["autoscaler"]["brownout_level"] == 1
        assert gw._set_brownout(0)
        gw.autoscaler.brownout_level = 0
        st, _, _ = _post(port, {"prompt": [5, 6], "max_tokens": 4},
                         headers={"x-priority": "batch"})
        assert st == 200

        # shrink back down: the victim's own pump retires it and exits
        assert gw._scale_down()
        deadline = time.monotonic() + 30
        while gw.replicas.active_count() > 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert gw.replicas.active_count() == 1
        st, _, _ = _post(port, {"prompt": [5, 6, 7], "max_tokens": 4})
        assert st == 200
    finally:
        assert gw.close(60)


def test_gateway_brownout_evicts_queued_tier(params):
    """An odd brownout level evicts the queue's below-tier flows: their
    waiting clients get the 503 + brownout Retry-After, higher tiers keep
    their place and finish."""
    eng = make_engine(params, autoscaler={"enabled": False,
                                          "brownout_tiers": ["standard"],
                                          "brownout_retry_after_s": 23})
    gw = Gateway(eng, port=0, request_timeout_s=60, max_queue_depth=8)
    gw.start_background()
    try:
        results = {}

        def client(name, prio, tokens):
            results[name] = _post(gw.port,
                                  {"prompt": [5, 6, 7], "max_tokens": tokens},
                                  headers={"x-priority": prio})

        # saturate both slots with standard work, then queue a batch row
        threads = [threading.Thread(target=client,
                                    args=(f"s{i}", "standard", 24),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        while len(gw._active) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        tq = threading.Thread(target=client, args=("b", "batch", 4),
                              daemon=True)
        tq.start()
        while len(gw._fair) < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(gw._fair) == 1, "batch request never queued"
        assert gw._set_brownout(1)
        gw.autoscaler.brownout_level = 1
        tq.join(30)
        st, hdrs, body = results["b"]
        assert st == 503 and hdrs.get("Retry-After") == "23"
        assert "brownout" in body["error"]["message"]
        assert gw.stats["brownout_evicted"] == 1
        gw._set_brownout(0)
        gw.autoscaler.brownout_level = 0
        for t in threads:
            t.join(60)
        assert all(results[f"s{i}"][0] == 200 for i in range(2))
    finally:
        assert gw.close(60)
