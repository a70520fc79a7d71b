"""Serving capacity observability guards (roofline / host-gap / goodput /
on-demand profiling).

The contracts under test, in the order the module docstring of
``telemetry/capacity.py`` states them:

- a landing's device-bound period is the capacity sample: nothing is fenced,
  a host-bound period is no sample, and sampling adds ZERO new XLA programs
  after warmup (jax.monitoring-guarded; a tiny model on the CPU is
  host-bound, so the real-scheduler tests set the tracker's device-bound
  share to 0 and EVERY landing samples);
- the account of the host's two threads: their CPU time from the two
  injected clocks, and the event loop's delivery (posted = written + taken +
  backlog at every landing);
- host-gap bucket counters sum EXACTLY to the measured gap — including the
  deferred-steal case where the nested timer stamps before its enclosing
  section, and the over-attribution scale-back;
- the analytic :class:`CapacityModel` FLOPs agree with XLA's own
  ``lower().cost_analysis()`` for the same forward (factor tolerance — the
  analytic model intentionally ignores norms/rope/softmax);
- goodput arithmetic (useful vs wasted token-FLOPs, byte waste converted at
  the machine balance);
- ``serving/mfu`` / ``serving/goodput_fraction`` / ``serving/host_gap_ms``
  actually land in the sink and in the Prometheus rendering (native
  ``_hist_bucket``/``le`` series) on a CPU smoke;
- the disabled sink allocates nothing (no meter, no tracker);
- instrumented decode stays within the overhead budget;
- :class:`XlaProfiler` produces a loadable trace, 409s on overlap, and the
  gateway's ``POST /v1/debug/profile`` does both end-to-end.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry.capacity import (
    BUSY_BUCKETS, DEVICE_BOUND_SHARE, PUMP_PARTS, PUMP_SPANS, CapacityMeter, CapacityModel,
    Delivery, HostGapTracker, program_shape, thread_cpu_clock, _program_kind)
from deepspeed_tpu.telemetry.profiler import (ProfileBusy, XlaProfiler,
                                              trace_artifacts)

_XLA_COMPILES = []  # registered once: jax.monitoring listeners can't detach


def _count_xla_compiles():
    if not _XLA_COMPILES:
        _XLA_COMPILES.append("registered")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _XLA_COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _XLA_COMPILES


def make_engine(params=None, num_slots=4, telemetry=None, every_landing_samples=False,
                **cb_extra):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)  # sink hermeticity: no cross-test counter bleed
    cb = {"enabled": True, "num_slots": num_slots}
    cb.update(cb_extra)
    cfg = {"dtype": "float32", "max_out_tokens": 512,
           "continuous_batching": cb}
    if telemetry:
        cfg["telemetry"] = telemetry
    eng = deepspeed_tpu.init_inference("tiny", config=cfg, params=params)
    if every_landing_samples:
        # a tiny model on the CPU is host-bound: its pump hardly waits. With
        # the share at 0 every landed period counts as the device's time
        eng.scheduler()._gap.device_bound_share = 0.0
    return eng


@pytest.fixture(scope="module")
def params():
    eng = make_engine()
    return jax.device_get(eng.params)


_RNG = np.random.default_rng(23)
PROMPTS = [_RNG.integers(0, 256, 40).astype(np.int32),
           _RNG.integers(0, 256, 17).astype(np.int32)]


class FakeSink:
    """Counter/gauge/histogram recorder for the pure-host unit tests."""

    enabled = True

    def __init__(self):
        self.counters = {}
        self.gauges = {}
        self.hists = {}

    def counter(self, name, value=1, attrs=None):
        c, t = self.counters.get(name, (0, 0))
        self.counters[name] = (c + 1, t + value)

    def gauge(self, name, value, step=None, attrs=None):
        self.gauges[name] = value

    def histogram(self, name, value, attrs=None):
        self.hists.setdefault(name, []).append(value)


# ------------------------------------------------------- pump-account units
def _play(gap, *events):
    """Feed the tracker span boundaries as the spans would: ``(name, t0, t1)``
    is a whole span, ``("+name", t)`` an entry and ``("-name", t0, t1)`` the
    exit of a span with others inside it."""
    for name, *ts in events:
        if name[0] == "+":
            gap.span_enter(name[1:], ts[0])
        elif name[0] == "-":
            gap.span_exit(name[1:], *ts)
        else:
            gap.span_enter(name, ts[0])
            gap.span_exit(name, *ts)


def _pump_ms(sink):
    """{part: total ms} of the ``serving/pump/<part>_ms`` counters."""
    return {name[len("serving/pump/"):-len("_ms")]: total
            for name, (_, total) in sink.counters.items() if name.startswith("serving/pump/")}


def _buckets_ms(sink):
    return {b: v for b, v in _pump_ms(sink).items() if b in BUSY_BUCKETS}


def test_pump_buckets_sum_exactly_to_busy():
    sink = FakeSink()
    gap = HostGapTracker(sink)
    _play(gap, ("sched/fetch", 9.991, 10.0),        # a landing: the period and the gap open
          ("+sched/step", 10.0), ("sched/admit", 10.001, 10.005),
          ("sched/assemble", 10.005, 10.007), ("sched/deliver", 10.007, 10.008),
          ("sched/dispatch", 10.020, 10.021),       # 20 ms after the landing
          ("sched/fetch", 10.021, 10.030), ("-sched/step", 10.0, 10.030))
    # the device-idle gap is what it was: fetch's end to the next dispatch's start
    assert sink.hists["serving/host_gap_ms"] == [pytest.approx(20.0)]
    assert gap.gaps == 1 and gap.total_gap_s == pytest.approx(0.020)
    # the period: 30 ms = 9 waited + 21 of host work, 14 of it under no inner span
    ms = _pump_ms(sink)
    assert ms["wait"] == pytest.approx(9.0) and ms["busy"] == pytest.approx(21.0)
    assert _buckets_ms(sink) == {"admit": pytest.approx(4.0), "assemble": pytest.approx(2.0),
                                 "deliver": pytest.approx(1.0), "dispatch": pytest.approx(1.0),
                                 "other": pytest.approx(13.0)}
    assert sum(_buckets_ms(sink).values()) == pytest.approx(ms["busy"], abs=1e-9)
    assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(21.0)]
    assert sink.hists["serving/pump_wait_ms"] == [pytest.approx(9.0)]
    assert gap.busy_s == pytest.approx(0.021) and gap.wait_s == pytest.approx(0.009)


def test_pump_nested_probe_comes_out_of_admission_wherever_it_runs():
    # the trie probe runs inside the admission span: its time is its own and
    # not admission's, whether it opens first thing or last
    results = []
    for probe in ((0.000, 0.003), (0.007, 0.010)):
        sink = FakeSink()
        gap = HostGapTracker(sink)
        _play(gap, ("sched/fetch", -0.005, 0.0), ("+sched/step", 0.0), ("+sched/admit", 0.0),
              ("sched/trie_probe", *probe), ("-sched/admit", 0.0, 0.010),
              ("sched/dispatch", 0.020, 0.020), ("sched/fetch", 0.020, 0.020),
              ("-sched/step", 0.0, 0.020))
        results.append(_pump_ms(sink))
    assert results[0] == pytest.approx(results[1])
    assert results[0]["admit"] == pytest.approx(7.0)
    assert results[0]["trie_probe"] == pytest.approx(3.0)
    assert results[0]["other"] == pytest.approx(10.0)
    assert results[0]["busy"] == pytest.approx(20.0) and "wait" not in results[0]


def test_pump_span_open_across_a_landing_is_cut_there():
    # where the old gap scaled timers that overlapped its ends back, a span
    # open across a landing gives each period its own share, unscaled
    sink = FakeSink()
    gap = HostGapTracker(sink, unlanded=lambda: True)   # a pump that runs ahead
    _play(gap, ("+sched/step", 0.0), ("sched/dispatch", 0.002, 0.003),
          ("sched/fetch", 0.003, 0.010))                    # period 1: 10 ms
    assert _pump_ms(sink) == {"busy": pytest.approx(3.0), "dispatch": pytest.approx(1.0),
                              "other": pytest.approx(2.0), "wait": pytest.approx(7.0)}
    _play(gap, ("sched/deliver", 0.010, 0.030), ("-sched/step", 0.0, 0.034),
          ("gateway/admit", 0.035, 0.036), ("+sched/step", 0.036),
          ("sched/fetch", 0.040, 0.050), ("-sched/step", 0.036, 0.050))   # period 2: 40 ms
    ms = _pump_ms(sink)
    assert ms["deliver"] == pytest.approx(20.0)
    assert ms["other"] == pytest.approx(2.0 + 4.0 + 4.0)    # the step's own time, both periods
    assert ms["gateway"] == pytest.approx(2.0)              # its span and the 1 ms under none
    assert ms["wait"] == pytest.approx(17.0) and ms["busy"] == pytest.approx(33.0)
    assert sum(_buckets_ms(sink).values()) == pytest.approx(ms["busy"], abs=1e-9)
    assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(3.0), pytest.approx(30.0)]


def test_pump_dispatch_before_any_sync_records_nothing():
    # warm-up dispatches (no landing before them) emit no phantom gap, and
    # no period has closed yet
    sink = FakeSink()
    gap = HostGapTracker(sink)
    _play(gap, ("+sched/step", 0.990), ("sched/admit", 0.990, 0.995),
          ("sched/dispatch", 1.0, 1.001))
    assert not sink.counters and not sink.hists and gap.gaps == 0


@pytest.mark.parametrize("in_flight,gateway", [(False, False), (True, False), (False, True),
                                               (True, True)])
def test_pump_time_under_no_span_is_the_gateway_s_loop_or_nobody_s(in_flight, gateway):
    # a caller that steps the scheduler itself and goes away for 5 s did no
    # host work for the pump meanwhile, a sync in flight or not; nor did a
    # gateway pump that starts 5 s after someone else's last step. Between a
    # gateway span and a step with a sync out, the time is the pump's loop
    sink = FakeSink()
    gap = HostGapTracker(sink, unlanded=lambda: in_flight)
    _play(gap, ("+sched/step", 0.0), ("sched/dispatch", 0.001, 0.002),
          ("sched/fetch", 0.002, 0.010), ("sched/deliver", 0.010, 0.012),
          ("-sched/step", 0.0, 0.013))
    if gateway:
        _play(gap, ("gateway/admit", 5.012, 5.013))
    _play(gap, ("+sched/step", 5.013), ("sched/dispatch", 5.014, 5.015),
          ("sched/fetch", 5.015, 5.020), ("-sched/step", 5.013, 5.020))
    ms = _pump_ms(sink)
    idle, loop = {(False, False): (5000.0, 0.0), (True, False): (5000.0, 0.0),
                  (False, True): (4999.0, 1.0),     # the gateway's own span alone
                  (True, True): (0.0, 5000.0)}[in_flight, gateway]
    assert ms.get("idle", 0.0) == pytest.approx(idle, abs=1e-6)
    assert ms.get("gateway", 0.0) == pytest.approx(loop, abs=1e-6)
    assert sink.hists["serving/pump_busy_ms"][1] == pytest.approx(5.0 + loop)
    assert sum(_buckets_ms(sink).values()) == pytest.approx(ms["busy"], abs=1e-9)
    assert ms["busy"] + ms["wait"] + ms.get("idle", 0.0) == pytest.approx(5020.0)


@pytest.mark.parametrize("ahead", [True, False])
def test_host_gap_of_a_pump_one_sync_deep(ahead):
    """A dispatch that opens before the previous fetch closes (the pump
    launched sync N+1 with N unlanded) found the device busy: ONE 0.0
    observation, whatever host work was done since; and the fetch that then
    lands N opens no gap, because N+1 is out. The serial order records the
    gap it always did. Either way every landing closes a period whose
    buckets sum to its ``busy``."""
    sink = FakeSink()
    unlanded = [False]
    gap = HostGapTracker(sink, unlanded=lambda: unlanded[0])
    _play(gap, ("+sched/step", 0.0), ("+sched/dispatch", 0.0))   # sync N: nothing before it
    assert not sink.hists and gap.gaps == 0
    gap.span_exit("sched/dispatch", 0.0, 0.001)
    unlanded[0] = ahead                               # N is out when N+1 is assembled
    if not ahead:
        _play(gap, ("sched/fetch", 0.001, 0.010))     # serial: N lands first
    _play(gap, ("sched/admit", 0.010, 0.014), ("sched/assemble", 0.014, 0.016),
          ("+sched/dispatch", 0.020))                 # sync N+1
    if ahead:
        assert sink.hists["serving/host_gap_ms"] == [0.0] and not sink.counters
        _play(gap, ("-sched/dispatch", 0.020, 0.021),
              ("sched/fetch", 0.021, 0.030),          # lands N while N+1 is out: no gap opens
              ("sched/deliver", 0.030, 0.035), ("+sched/dispatch", 0.040))  # sync N+2, ahead too
        assert sink.hists["serving/host_gap_ms"] == [0.0, 0.0]
        assert gap.gaps == 2 and gap.total_gap_s == 0.0
        # N's period ran from the step's start: 9 ms waited, 21 of host work
        assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(21.0)]
        assert sink.hists["serving/pump_wait_ms"] == [pytest.approx(9.0)]
        assert _buckets_ms(sink) == {"admit": pytest.approx(4.0), "assemble": pytest.approx(2.0),
                                     "dispatch": pytest.approx(2.0), "other": pytest.approx(13.0)}
        # the pump turns serial (say a row flagged for cancellation): N+1 and N+2
        # land with nothing out, and the next dispatch measures a real gap
        unlanded[0] = False
        _play(gap, ("-sched/dispatch", 0.040, 0.041), ("sched/fetch", 0.041, 0.050),
              ("sched/deliver", 0.050, 0.053), ("+sched/dispatch", 0.060))
        assert sink.hists["serving/host_gap_ms"][2] == pytest.approx(10.0)
        # N+1's period: its landing to N's, 20 ms = 9 waited + 5 deliver + 1 dispatch + 5 other
        assert sink.hists["serving/pump_busy_ms"][1] == pytest.approx(11.0)
        assert _pump_ms(sink)["deliver"] == pytest.approx(5.0)
        assert sum(_buckets_ms(sink).values()) == pytest.approx(_pump_ms(sink)["busy"], abs=1e-9)
    else:
        assert sink.hists["serving/host_gap_ms"] == [pytest.approx(10.0)]
        assert gap.gaps == 1
        _play(gap, ("-sched/dispatch", 0.020, 0.021), ("sched/fetch", 0.021, 0.030))
        # the old gap (10 ms) is the part of busy between the landing and the
        # dispatch; the dispatch itself (1 ms) is host work too
        assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(1.0), pytest.approx(11.0)]
        assert _buckets_ms(sink)["admit"] == pytest.approx(4.0)
        assert _buckets_ms(sink)["assemble"] == pytest.approx(2.0)
        assert sum(_buckets_ms(sink).values()) == pytest.approx(_pump_ms(sink)["busy"], abs=1e-9)


def test_pump_account_adds_up_over_a_scripted_run():
    """Ahead, an idle turn, a serial sync, a program built under a
    dispatch: after every boundary the busy buckets add up to ``busy`` and
    the parts to the time in closed periods, exactly; the histograms hold
    one observation a landing."""
    sink = FakeSink()
    unlanded = [False]
    compiles = [0]
    gap = HostGapTracker(sink, unlanded=lambda: unlanded[0], compiles=lambda: compiles[0])
    periods = []  # what the parts must add up to so far, ms

    def check(closed_ms):
        periods.append(closed_ms)
        ms = _pump_ms(sink)
        assert sum(_buckets_ms(sink).values()) == pytest.approx(ms.get("busy", 0.0), abs=1e-9)
        parts = sum(ms.get(p, 0.0) for p in ("busy", "wait", "idle", "compile"))
        assert parts == pytest.approx(sum(periods), abs=1e-9)

    # an idle gateway before the first request: booked nowhere
    _play(gap, ("gateway/admit", 0.000, 0.001), ("gateway/idle", 0.001, 0.021))
    check(0.0)
    # step 1 launches sync 1 (it builds its program: 2 s) and lands nothing
    _play(gap, ("gateway/admit", 0.021, 0.022), ("+sched/step", 0.022), ("sched/admit", 0.022, 0.023),
          ("sched/assemble", 0.023, 0.025), ("+sched/dispatch", 0.025))
    compiles[0] = 1
    unlanded[0] = True
    _play(gap, ("-sched/dispatch", 0.025, 2.025), ("-sched/step", 0.022, 2.026))
    # step 2 launches sync 2 ahead and lands sync 1: period 1 = 0.022 .. 2.050
    _play(gap, ("gateway/admit", 2.027, 2.028), ("+sched/step", 2.028),
          ("sched/admit", 2.028, 2.029), ("sched/assemble", 2.029, 2.032),
          ("sched/dispatch", 2.032, 2.033), ("sched/fetch", 2.033, 2.050))
    check(2028.0)
    assert _pump_ms(sink)["compile"] == pytest.approx(2000.0)
    assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(11.0)]
    assert sink.hists["serving/pump_wait_ms"] == [pytest.approx(17.0)]
    # ... and delivers it; step 3 finds nothing to launch and lands sync 2
    _play(gap, ("sched/deliver", 2.050, 2.054), ("-sched/step", 2.028, 2.055),
          ("gateway/admit", 2.055, 2.056), ("+sched/step", 2.056), ("sched/admit", 2.056, 2.057))
    unlanded[0] = False
    _play(gap, ("sched/fetch", 2.057, 2.070))
    check(20.0)
    _play(gap, ("sched/deliver", 2.070, 2.073), ("-sched/step", 2.056, 2.074))
    # the pump idles for two turns; the stretch since the landing (4 ms of
    # work, 40 idle) closes into the counters at the next step, no observation
    _play(gap, ("gateway/admit", 2.074, 2.075), ("gateway/idle", 2.075, 2.095),
          ("gateway/admit", 2.095, 2.096), ("gateway/idle", 2.096, 2.116),
          ("gateway/admit", 2.116, 2.118), ("+sched/step", 2.118))
    check(48.0)
    assert _pump_ms(sink)["idle"] == pytest.approx(40.0)
    assert len(sink.hists["serving/pump_busy_ms"]) == 2
    # a serial sync, landed by the step that launched it: its fetch is the
    # time blocked on the device
    _play(gap, ("sched/admit", 2.118, 2.119), ("sched/assemble", 2.119, 2.121),
          ("sched/dispatch", 2.121, 2.122), ("sched/fetch", 2.122, 2.141))
    check(23.0)
    assert sink.hists["serving/pump_wait_ms"][-1] == pytest.approx(19.0)
    assert sink.hists["serving/pump_busy_ms"][-1] == pytest.approx(4.0)
    assert len(sink.hists["serving/pump_busy_ms"]) == 3
    assert gap.busy_s * 1e3 == pytest.approx(_pump_ms(sink)["busy"])
    assert gap.wait_s * 1e3 == pytest.approx(_pump_ms(sink)["wait"])
    assert set(_pump_ms(sink)) <= {"busy", *PUMP_PARTS}
    assert "sched/fence" not in PUMP_SPANS  # nothing is fenced any more


# ------------------------------------------------ the account's two threads
class _Clock:
    """An injected thread CPU clock: reads what the test last set."""

    def __init__(self):
        self.s = 0.0

    def __call__(self):
        return self.s


def _bound_tracker(unlanded=True, primary=True, share=DEVICE_BOUND_SHARE):
    sink, pump, loop, sent = FakeSink(), _Clock(), _Clock(), Delivery()
    gap = HostGapTracker(sink, unlanded=lambda: unlanded, device_bound_share=share)
    gap.bind_threads(pump, sent, loop, primary=primary)
    return sink, gap, pump, loop, sent


def _sync(gap, t, busy=0.006, wait=0.004, burn=()):
    """One step of a pump that runs ahead, from ``t``: ``busy`` of host work
    (a dispatch in it) and ``wait`` under the fetch; ``burn``: (clock,
    seconds) the threads burn in it; returns its landing."""
    _play(gap, ("+sched/step", t))
    for clock, seconds in burn:
        clock.s += seconds
    _play(gap, ("sched/dispatch", t + 0.001, t + 0.002),
          ("sched/fetch", t + busy, t + busy + wait))
    gap.span_exit("sched/step", t, t + busy + wait)
    return t + busy + wait


def test_threads_cpu_comes_from_the_two_injected_clocks_and_identities_hold():
    sink, gap, pump, loop, _ = _bound_tracker()
    pump.s, loop.s = 5.0, 7.0           # whatever the threads burned before
    burn = ((pump, 0.0055), (loop, 0.0030))
    t = _sync(gap, 0.0, burn=burn)      # period 1: opens at the step, 10 ms, 5.5 + 3.0 ms of CPU
    t = _sync(gap, t, burn=burn)        # period 2: the same
    assert sink.hists["serving/pump_cpu_ms"] == [pytest.approx(5.5), pytest.approx(5.5)]
    assert sink.hists["serving/loop_cpu_ms"] == [pytest.approx(3.0), pytest.approx(3.0)]
    assert sink.hists["serving/host_threads_cpu_pct"] == [pytest.approx(85.0)] * 2
    assert sink.counters["serving/pump/cpu_ms"] == (2, pytest.approx(11.0))
    assert sink.counters["gateway/loop/cpu_ms"] == (2, pytest.approx(6.0))
    # a reading is the mean a sync over the run of landed periods that ends
    # in it: a third period that burns nothing reads two thirds of the others
    t = _sync(gap, t)
    assert sink.hists["serving/pump_cpu_ms"][2] == pytest.approx(11.0 / 3)
    assert sink.hists["serving/host_threads_cpu_pct"][2] == pytest.approx(100 * 17.0 / 30)
    # the period's identities are what they were: buckets to busy, parts to the period
    ms = _pump_ms(sink)
    assert sum(_buckets_ms(sink).values()) == pytest.approx(ms["busy"], abs=1e-9)
    assert ms["busy"] + ms["wait"] == pytest.approx(30.0)
    assert sink.hists["serving/pump_busy_ms"] == [pytest.approx(6.0)] * 3
    assert sink.hists["serving/pump_wait_ms"] == [pytest.approx(4.0)] * 3
    # one observation a landed sync of each, like pump_busy_ms; the pump's
    # CPU never passes the wall time it had (here: busy + wait)
    for name in ("serving/pump_cpu_ms", "serving/loop_cpu_ms", "serving/host_threads_cpu_pct"):
        assert len(sink.hists[name]) == len(sink.hists["serving/pump_busy_ms"]) == 3
    assert set(_pump_ms(sink)) <= {"busy", "cpu", *PUMP_PARTS}


def test_threads_cpu_reads_through_a_clock_that_ticks():
    """The chip machine's thread clocks tick in steps of 10 ms under periods
    of 16 ms and up: one period's reading is 0 or 10. Over the run of
    ``CPU_PERIODS`` periods that ends in a landing the mean a sync is within
    a tick over the run of the truth, and an idle stretch starts the run
    anew (a reading never spans time in which nobody pumped)."""
    from deepspeed_tpu.telemetry.capacity import CPU_PERIODS
    sink, gap, pump, loop, _ = _bound_tracker()
    true_pump = true_loop = 0.0
    t = 0.0
    for _ in range(3 * CPU_PERIODS):    # 16 ms periods: 9.3 ms of pump CPU, 4.1 of the loop's
        gap.span_enter("sched/step", t)
        true_pump, true_loop = true_pump + 0.0093, true_loop + 0.0041
        pump.s, loop.s = int(true_pump * 100) / 100, int(true_loop * 100) / 100   # 10 ms ticks
        _play(gap, ("sched/dispatch", t + 0.001, t + 0.002), ("sched/fetch", t + 0.012, t + 0.016))
        gap.span_exit("sched/step", t, t + 0.016)
        t += 0.016
    tick = 10.0 / CPU_PERIODS
    for got in sink.hists["serving/pump_cpu_ms"][CPU_PERIODS:]:
        assert abs(got - 9.3) <= tick + 1e-9
    for got in sink.hists["serving/loop_cpu_ms"][CPU_PERIODS:]:
        assert abs(got - 4.1) <= tick + 1e-9
    assert sink.hists["serving/pump_cpu_ms"][0] in (0.0, 10.0)     # one period: a tick or none
    assert sink.counters["serving/pump/cpu_ms"][1] == pytest.approx(pump.s * 1e3)  # totals exact
    # an idle stretch: the next landing's run starts at the step that follows it
    flight = [False]                    # the last sync has landed, nothing is out
    gap._unlanded = lambda: flight[0]
    _play(gap, ("gateway/idle", t, t + 5.0))
    n = len(sink.hists["serving/pump_cpu_ms"])
    pump.s += 0.5                       # burned while idle (say a collection): in the counter alone
    _play(gap, ("+sched/step", t + 5.0))
    flight[0] = True
    pump.s += 0.01
    _play(gap, ("sched/dispatch", t + 5.001, t + 5.002), ("sched/fetch", t + 5.006, t + 5.010))
    assert sink.hists["serving/pump_cpu_ms"][n] == pytest.approx(10.0)
    assert sink.hists["serving/host_threads_cpu_pct"][n] == pytest.approx(100.0)


def test_threads_unbound_or_clockless_leave_their_numbers_out():
    # no gateway told the tracker its threads: nothing of the four is emitted
    sink = FakeSink()
    gap = HostGapTracker(sink, unlanded=lambda: True)
    _sync(gap, _sync(gap, 0.0))
    assert not [n for n in (*sink.hists, *sink.counters)
                if "cpu" in n or n.startswith("gateway/")]
    # a platform without per-thread clocks (both None): the delivery is still
    # accounted, the CPU numbers are left out, not faked
    sink, sent = FakeSink(), Delivery()
    gap = HostGapTracker(sink, unlanded=lambda: True)
    gap.bind_threads(None, sent, None, primary=True)
    t = _sync(gap, 0.0)
    gap.posted += 2
    sent.lag_s += 0.004; sent.bytes += 200; sent.writes += 2; sent.events += 2
    _sync(gap, t)
    assert sink.hists["gateway/delivery_lag_ms"] == [pytest.approx(2.0)]
    assert not [n for n in (*sink.hists, *sink.counters) if "cpu" in n]
    # another replica's pump emits its own CPU and nothing of the loop's
    sink, gap, pump, loop, sent = _bound_tracker(primary=False)
    burn = ((pump, 0.004), (loop, 0.003))
    _sync(gap, _sync(gap, 0.0, burn=burn), burn=burn)
    assert sink.hists["serving/pump_cpu_ms"] == [pytest.approx(4.0)] * 2
    assert not [n for n in (*sink.hists, *sink.counters)
                if n.startswith("gateway/") or "loop" in n or "threads" in n]
    assert sent.pumps == [gap]          # ... but its posts count into the backlog


def test_thread_cpu_clock_reads_another_thread_s_clock():
    import threading
    done, go = threading.Event(), threading.Event()

    def burn():
        go.wait(10)
        x = 0
        while not done.is_set():
            x += 1

    th = threading.Thread(target=burn, daemon=True)
    th.start()
    clock = thread_cpu_clock(th.ident)
    if clock is None:
        done.set(), go.set()
        pytest.skip("no per-thread CPU clocks on this platform")
    before = clock()
    go.set()
    time.sleep(0.05)
    burned = clock() - before       # read from here, of the other thread, while it lives
    done.set()
    th.join(10)
    assert before < 0.02 and burned > 0.005


def test_delivery_posted_is_written_plus_taken_plus_backlog_at_every_landing():
    """In EVENTS: an event here carries four tokens (a row's of one landing),
    and only ``tokens`` (with the counter it feeds) and the bytes know."""
    sink, gap, pump, loop, sent = _bound_tracker()
    other = HostGapTracker(FakeSink())          # a second replica's pump posts too
    other.bind_threads(_Clock(), sent)
    t = _sync(gap, 0.0)
    script = [  # (posted here, posted there, written, taken, lag of each written, loop CPU)
        (8, 0, 5, 1, 0.002, 0.0004), (4, 3, 0, 0, 0.0, 0.0001), (0, 0, 9, 0, 0.010, 0.0009)]
    posted = written = taken = 0
    for here, there, wrote, took, lag, cpu in script:
        gap.posted += here
        other.posted += there
        for _ in range(wrote):                  # the loop's own arithmetic
            sent.lag_s += lag
            sent.lag_max_s = max(sent.lag_max_s, lag)
            sent.bytes += 100
            sent.tokens += 4
            sent.writes += 1
            sent.events += 1
        sent.taken += took
        loop.s += cpu
        t = _sync(gap, t)
        posted, written, taken = posted + here + there, written + wrote, taken + took
        assert sink.hists["gateway/backlog_events"][-1] == posted - written - taken >= 0
    assert sink.hists["gateway/backlog_events"] == [0, 2, 9, 0]
    # a period that wrote nothing observes no lag and no cost an event
    assert sink.hists["gateway/delivery_lag_ms"] == [pytest.approx(2.0), pytest.approx(10.0)]
    assert sink.hists["gateway/delivery_lag_max_ms"] == [pytest.approx(2.0), pytest.approx(10.0)]
    # ... the cost an event over the run of periods the thread readings span:
    # 400 us over 5 events, then 1,400 us over 14
    assert sink.hists["gateway/loop_cpu_us_per_event"] == [pytest.approx(80.0),
                                                           pytest.approx(100.0)]
    assert sink.counters["gateway/sse_events"] == (2, 14)
    assert sink.counters["gateway/sse_writes"] == (2, 14)
    assert sink.counters["gateway/sse_bytes"] == (2, 1400)
    assert sink.counters["gateway/sse_tokens"] == (2, 56)    # 25 events a hundred tokens
    assert len(sink.hists["gateway/backlog_events"]) == len(sink.hists["serving/pump_busy_ms"])


@pytest.mark.parametrize("per_event", [1, 4], ids=["a-token-an-event", "four-tokens-an-event"])
def test_sse_tokens_is_the_one_total_that_counts_tokens(per_event):
    """Three landings of 6 rows, every event written in the period behind its
    landing: posted, written, the backlog and the cost an event read the same
    whatever an event carries; ``gateway/sse_tokens`` over ``sse_events`` is
    what the batching buys (100 events a hundred tokens, or 25)."""
    sink, gap, pump, loop, sent = _bound_tracker()
    t = _sync(gap, 0.0)
    for _ in range(3):
        gap.posted += 6
        for _ in range(6):
            sent.bytes += 60 + 4 * per_event
            sent.tokens += per_event
            sent.writes += 1
            sent.events += 1
        loop.s += 6 * 0.0002
        t = _sync(gap, t)
    assert sent.posted() == sent.events == 18 and sent.tokens == 18 * per_event
    assert sink.hists["gateway/backlog_events"] == [0, 0, 0, 0]
    assert sink.hists["gateway/loop_cpu_us_per_event"] == [pytest.approx(200.0)] * 3
    events, tokens = sink.counters["gateway/sse_events"][1], sink.counters["gateway/sse_tokens"][1]
    assert (events, tokens) == (18, 18 * per_event)
    assert 100.0 * events / tokens == pytest.approx(100.0 / per_event)


def test_delivery_reads_an_event_s_landing_off_the_ring_not_off_the_event():
    """The loop handles events in the order they were posted, so the n-th
    it handles came from the landing that had ``< n`` posted before it:
    every landing leaves (posted before it, its time) and nothing rides an
    event. Events posted to a handler that has gone count ``unread`` and
    stay out of the backlog."""
    # nothing but the gateway's hand-over of a batch counts a post: a tracker
    # whose scheduler delivered to direct callers before any pump has posted 0
    assert HostGapTracker(FakeSink()).posted == 0
    sink, gap, _, _, sent = _bound_tracker()
    t1 = _sync(gap, 0.0)            # landing 1 at 10 ms delivers to 5 rows
    gap.posted += 5                 # (Gateway._flush_landing: an event a row, before the post)
    t2 = _sync(gap, t1)             # landing 2 at 20 ms delivers to 3
    gap.posted += 3
    assert list(sent.landings) == [(0, pytest.approx(0.010)), (5, pytest.approx(0.020))]
    assert [sent.landing_of(n) for n in (1, 5)] == [pytest.approx(0.010)] * 2
    assert [sent.landing_of(n) for n in (6, 8)] == [pytest.approx(0.020)] * 2
    assert list(sent.landings) == [(5, pytest.approx(0.020))]   # the head moved on
    assert Delivery().landing_of(1) is None                      # before any landing
    # 6 written, 1 taken, 1 posted to a handler that had gone: nothing is owed
    sent.events, sent.taken, sent.unread = 6, 1, 1
    _sync(gap, t2)
    assert sink.hists["gateway/backlog_events"] == [0, 5, 0]


@pytest.mark.parametrize("wait,ahead,sampled", [
    (0.0, True, None),            # host-bound: the pump never waited
    (0.0004, True, None),         # under the share: a fetch's own cost
    (0.004, True, 0.010),         # device-bound, ahead: the period
    (0.004, False, 0.005),        # device-bound, serial: from its dispatch (1 ms) to its landing
])
def test_device_bound_period_is_the_capacity_sample(wait, ahead, sampled):
    sink = FakeSink()
    gap = HostGapTracker(sink, unlanded=lambda: ahead)
    busy = 0.010 - wait
    t = _sync(gap, 0.0, busy, wait)
    _play(gap, ("+sched/step", t), ("sched/dispatch", t + busy - 0.001, t + busy),
          ("sched/fetch", t + busy, t + 0.010))
    assert gap.device_s == (None if sampled is None else pytest.approx(sampled))
    # what the scheduler does with it (DecodeScheduler._landed)
    model = CapacityModel(type("C", (), {"hidden_size": 64, "num_layers": 2,
                                         "num_heads": 4, "vocab_size": 128})(),
                          kv_bytes_per_token=1024, num_slots=4)
    meter = CapacityMeter(sink, model, peak_flops=1e12, peak_hbm_bw=1e11)
    if gap.device_s is not None:
        meter.observe_dispatch(("fused", True, False, 1, 4), gap.device_s,
                               np.array([10, 20]), width=1, ksteps=4)
    assert meter.samples == (sampled is not None)
    if sampled is not None:
        flops, _ = model.dispatch_cost(np.array([10, 20]), 1, 4)
        assert sink.gauges["serving/mfu"] == pytest.approx(flops / sampled / 1e12)
    # a period in which two programs were dispatched (a replay) is no sample
    _play(gap, ("sched/dispatch", t + 0.011, t + 0.012), ("sched/dispatch", t + 0.012, t + 0.013),
          ("sched/fetch", t + 0.013, t + 0.020))
    assert gap.device_s is None


# ---------------------------------------------------------- program-key units
def test_program_shape_and_kind():
    assert program_shape(("fused", True, False, 8, 4)) == (8, 4)
    assert program_shape(("fused", True, False, 8, 4, "lora")) == (8, 4)
    assert program_shape(("spec", False, False, 5)) == (5, 1)
    assert program_shape(("spec", False, False, 5, "lora")) == (5, 1)
    assert program_shape(("fused_block", False, False, 64, 4)) == (64, 4)
    assert program_shape("copy") == (1, 1)
    assert _program_kind(("fused", True, False, 8, 4, "lora")) == "fused+lora"
    assert _program_kind(("spec", False, False, 5)) == "spec"
    assert _program_kind("tier_slice") == "tier_slice"


# -------------------------------------------------------------- goodput units
def test_goodput_accounting():
    model = CapacityModel(type("C", (), {"hidden_size": 64, "num_layers": 2,
                                         "num_heads": 4, "vocab_size": 128})(),
                          kv_bytes_per_token=1024, num_slots=4)
    meter = CapacityMeter(FakeSink(), model, peak_flops=1e12, peak_hbm_bw=1e11)
    assert meter.goodput_fraction == 1.0  # nothing accounted yet
    meter.account(10, wasted_tokens=5, ctx=0.0)
    assert meter.goodput_fraction == pytest.approx(10 / 15)
    # byte waste converts at the machine balance (FLOPs/byte = 10 here)
    ft = model.flops_per_token(0.0)
    meter2 = CapacityMeter(FakeSink(), model, peak_flops=1e12, peak_hbm_bw=1e11)
    meter2.account(1, ctx=0.0, wasted_bytes=ft / 10.0)
    assert meter2.goodput_fraction == pytest.approx(0.5)


def test_observe_dispatch_roofline_classification():
    model = CapacityModel(type("C", (), {"hidden_size": 64, "num_layers": 2,
                                         "num_heads": 4, "vocab_size": 128})(),
                          kv_bytes_per_token=1024, num_slots=4)
    sink = FakeSink()
    meter = CapacityMeter(sink, model, peak_flops=1e12, peak_hbm_bw=1e11)
    key = ("fused", True, False, 1, 1)
    meter.register(key, model)  # any hashable stand-in for the fn
    assert meter.key_for(model) == key
    meter.observe_dispatch(key, 0.0, np.array([10, 20]), width=1, ksteps=1)
    assert meter.samples == 0  # no time, no sample
    meter.observe_dispatch(key, 1e-3, np.array([10, 20]), width=1, ksteps=1)
    assert meter.samples == 1
    assert 0.0 < sink.gauges["serving/mfu"]
    assert 0.0 < sink.gauges["serving/hbm_bw_util"]
    assert "serving/roofline/fused" in sink.gauges
    table = meter.program_table()
    ent = table[str(key)]
    assert ent["kind"] == "fused" and ent["samples"] == 1
    assert ent["bound"] in ("compute", "bandwidth")


@pytest.mark.parametrize("int8", [False, True], ids=["fused", "fused_block"])
@pytest.mark.parametrize("split", [False, True])
def test_dispatch_cost_prices_a_split_chunk_program(split, int8):
    """A chunk program that runs its first forward over the live rows is
    priced at ``num_slots + width`` first-forward rows and two weight streams
    for them, the whole block at ``num_slots * width`` rows and one; a split
    ``fused_block`` program's extra stream is one of int8 weights with their
    group scales (1 + 4/128 bytes a parameter, where bf16 moves 2)."""
    cfg = {"hidden_size": 64, "num_layers": 2, "num_heads": 4, "vocab_size": 128,
           "dtype": "bfloat16", "int8_weights": int8}
    model = CapacityModel(type("C", (), cfg)(), kv_bytes_per_token=0, num_slots=8)
    flops, bytes_ = model.dispatch_cost(np.zeros(0), width=64, ksteps=4, split=split)
    first = 8 + 64 if split else 8 * 64
    assert flops == (first + 3 * 8) * model.matmul_flops_per_col
    assert bytes_ == (4 + split) * model.weight_read_bytes
    weights = 2 * (64 * 16 * 12 + 4 * 16 * 64 + 2 * 64 * 256) + 64 * 128
    assert model.weight_read_bytes == weights * ((1 + 4 / 128) if int8 else 2)


# ------------------------------------------------- analytic-model cross-check
def test_capacity_model_flops_cross_check(params):
    """Analytic matmul+attention FLOPs vs XLA's own cost analysis of the
    same forward. The analytic model ignores norms/rope/softmax/router and
    counts the padded slot block, so the tolerance is a factor band — the
    guard is against being off by a power of ten (a miscounted projection,
    a dropped layer factor), not rounding."""
    eng = make_engine(params)
    T = 33
    ids = jax.numpy.asarray(PROMPTS[0][:T][None, :], jax.numpy.int32)
    lowered = jax.jit(eng.module.apply).lower(eng.params, ids)
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    measured = float(ca.get("flops", 0.0)) if ca else 0.0
    if measured <= 0.0:
        pytest.skip("backend reports no flops in cost_analysis")
    model = CapacityModel(eng.model_config,
                          kv_bytes_per_token=1.0, num_slots=1)
    # full-sequence causal forward: T columns, position i attends to i+1
    analytic = (T * model.matmul_flops_per_col
                + (T * (T + 1) / 2) * model.attn_flops_per_ctx_tok)
    ratio = measured / analytic
    assert 0.25 <= ratio <= 4.0, (measured, analytic)


# --------------------------------------------------------------- end-to-end
def _decode(eng, n=3, max_new=8):
    handles = [eng.scheduler().submit(PROMPTS[i % 2], max_new_tokens=max_new,
                                      seed=7 + i) for i in range(n)]
    return [h.result().tolist() for h in handles]


def test_capacity_metrics_emitted_cpu_smoke(params, tmp_path):
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)},
                      every_landing_samples=True)
    _decode(eng)
    sched = eng.scheduler()
    assert sched.capacity is not None and sched._gap is not None
    # every landing behind at most one dispatch was a sample, and no sync
    # launched serial for it: the pump ran ahead wherever rows were live
    landed = sched.syncs_ahead + sched.syncs_serial
    assert 0 < sched.capacity.samples <= landed
    assert eng.telemetry.snapshot()["counters"]["serving/capacity_samples"]["total"] \
        == sched.capacity.samples
    assert sched.syncs_ahead > sched.syncs_serial
    table = sched.capacity.program_table()
    assert table and all(e["bound"] in ("compute", "bandwidth")
                         for e in table.values())
    snap = eng.telemetry.snapshot()
    assert 0.0 < snap["gauges"]["serving/mfu"]
    assert 0.0 < snap["gauges"]["serving/hbm_bw_util"]
    assert snap["gauges"]["serving/goodput_fraction"] == pytest.approx(1.0)
    hg = snap["histograms"]["serving/host_gap_ms"]
    assert hg["count"] == sched._gap.gaps > 0
    # the account: one observation a landed sync, parts of known names only,
    # the busy buckets summing to busy
    assert snap["histograms"]["serving/pump_busy_ms"]["count"] == landed > 0
    assert snap["histograms"]["serving/pump_wait_ms"]["count"] == landed
    pump = {name[len("serving/pump/"):-len("_ms")]: c["total"]
            for name, c in snap["counters"].items() if name.startswith("serving/pump/")}
    assert set(pump) <= {"busy", *PUMP_PARTS}
    assert sum(v for b, v in pump.items() if b in BUSY_BUCKETS) == pytest.approx(pump["busy"])
    assert pump["busy"] == pytest.approx(sched._gap.busy_s * 1e3, rel=1e-6)
    assert pump["wait"] == pytest.approx(sched._gap.wait_s * 1e3, rel=1e-6)
    assert pump["compile"] > 0  # the step programs were built under sched/dispatch
    # no gateway told the account its threads: none of their numbers here
    assert "serving/pump_cpu_ms" not in snap["histograms"]
    # Prometheus rendering carries the gauges + the native histogram family
    from deepspeed_tpu.telemetry.prometheus import render
    text = render(snap)
    assert "dstpu_serving_mfu " in text
    assert "dstpu_serving_goodput_fraction " in text
    assert 'dstpu_serving_host_gap_ms_hist_bucket{le="' in text
    assert f'_hist_bucket{{le="+Inf"}} {hg["count"]}' in text
    eng.telemetry.close()


def test_disabled_sink_allocates_nothing(params):
    eng = make_engine(params)
    sched = eng.scheduler()
    assert sched.capacity is None and sched._gap is None
    assert _decode(eng, n=1)[0]  # and decode still works
    snap = eng.telemetry.snapshot()
    assert not [n for kind in ("counters", "histograms") for n in snap[kind]
                if n.startswith("serving/pump")]


def test_landing_samples_add_zero_new_xla_programs(params, tmp_path):
    """With EVERY landing a capacity sample, over a warm mix of both
    prompt-length buckets, fresh requests must add zero compiles: the
    sample is arithmetic on the period, no program and no fence."""
    compiles = _count_xla_compiles()
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)},
                      every_landing_samples=True)
    _decode(eng, n=3)  # warm: both prefill buckets + fused decode
    before = len(compiles)
    fresh = [np.roll(PROMPTS[0], 5), np.roll(PROMPTS[1], 3)]
    handles = [eng.scheduler().submit(p, max_new_tokens=8, seed=99 + i)
               for i, p in enumerate(fresh)]
    for h in handles:
        assert h.result().tolist()
    assert len(compiles) == before, \
        f"sampling added {len(compiles) - before} XLA program(s)"
    assert eng.scheduler().capacity.samples > 0
    eng.telemetry.close()


def test_instrumented_decode_overhead_bounded(params, tmp_path):
    """The capacity instrumentation's marginal cost per sync, as counts (a
    wall-clock ratio of two tiny CPU decodes is the machine's load, not the
    program's cost): sampling serializes nothing — with every landing a
    sample the pump still launches ahead of all but the first sync of a
    burst, and at the stated share a host-bound CPU run samples at most as
    often — and the pump marks at most 8 spans a sync, none of them a fence,
    however many tokens the sync carries."""
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)},
                      every_landing_samples=True)
    sched = eng.scheduler()
    _decode(eng, n=2)  # warm
    ahead0, serial0, sampled0 = sched.syncs_ahead, sched.syncs_serial, sched.capacity.samples
    _decode(eng, n=4, max_new=48)
    ahead, serial = sched.syncs_ahead - ahead0, sched.syncs_serial - serial0
    sampled = sched.capacity.samples - sampled0
    assert ahead + serial >= 8
    assert serial <= 2 and ahead >= 6, (ahead, serial)   # a burst's first sync alone
    assert 1 <= sampled <= ahead + serial, (sampled, ahead, serial)
    # at the stated share the same run samples no more than that
    sched._gap.device_bound_share = DEVICE_BOUND_SHARE
    sampled0, landed0 = sched.capacity.samples, sched.syncs_ahead + sched.syncs_serial
    _decode(eng, n=2, max_new=16)
    assert sched.capacity.samples - sampled0 <= sched.syncs_ahead + sched.syncs_serial - landed0
    eng.telemetry.close()
    with open(eng.telemetry.jsonl_path) as f:
        spans = [e["name"] for e in map(json.loads, f)
                 if e.get("type") == "span" and e["name"].startswith("sched/")]
    steps = spans.count("sched/step")
    assert steps >= 8 and "sched/fence" not in spans
    assert (len(spans) - steps) / steps <= 8, (len(spans), steps)


# ----------------------------------------------------------------- profiling
def test_xla_profiler_capture_and_busy(tmp_path):
    prof = XlaProfiler(str(tmp_path))
    trace_dir = prof.start(duration_s=0.2, tag="unit test!")
    assert os.path.isdir(trace_dir) and "unit_test_" in trace_dir
    with pytest.raises(ProfileBusy):
        prof.start(duration_s=0.2)
    # run some device work so the trace has content, then let it expire
    jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    # wait on captures, not .active: the stopper clears _active before the
    # (slow) trace write finishes and the capture is recorded
    deadline = time.monotonic() + 10.0
    while not prof.captures and time.monotonic() < deadline:
        time.sleep(0.05)
        prof.poll()
    assert prof.active is None
    assert prof.captures == [trace_dir]
    arts = trace_artifacts(trace_dir)
    assert arts, f"no trace artifacts under {trace_dir}"
    assert any(a.endswith((".xplane.pb", ".trace.json.gz", ".trace.json"))
               for a in arts)
    # manager is reusable after the capture ends; a long deadline keeps
    # the daemon timer from racing the explicit stop
    d2 = prof.start(duration_s=30.0)
    assert prof.stop() == d2


def test_profiler_report_boundary_request(tmp_path):
    prof = XlaProfiler(str(tmp_path))
    assert prof.maybe_capture() is None  # nothing pending: no-op
    prof.request(duration_s=0.05)
    with pytest.raises(ProfileBusy):
        prof.request(duration_s=0.05)  # pending counts as in-flight
    d = prof.maybe_capture(tag="report")
    assert d is not None and "report" in d
    prof.stop()
    assert prof.captures == [d]
    assert prof.maybe_capture() is None  # request was consumed


def test_gateway_profile_endpoint_and_capacity_metrics(params, tmp_path):
    from deepspeed_tpu.serving import Gateway
    eng = make_engine(params, num_slots=2,
                      telemetry={"enabled": True, "output_path": str(tmp_path)},
                      every_landing_samples=True)
    gw = Gateway(eng, port=0, request_timeout_s=60.0)
    gw.start_background()
    base = f"http://127.0.0.1:{gw.port}"

    def post(path, body):
        req = urllib.request.Request(base + path, data=json.dumps(body).encode(),
                                     headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=60).read())

    def get(path, headers=None):
        req = urllib.request.Request(base + path, headers=headers or {})
        return urllib.request.urlopen(req, timeout=60).read()

    try:
        out = post("/v1/completions",
                   {"prompt": PROMPTS[0].tolist(), "max_tokens": 6, "seed": 3})
        assert out["choices"][0]["token_ids"]
        m = json.loads(get("/v1/metrics"))
        cap = m["capacity"]
        assert cap["programs"] and cap["samples"] > 0
        assert cap["goodput_fraction"] == pytest.approx(1.0)
        assert cap["host_gap_total_s"] >= 0.0
        assert cap["host_gaps"] >= 0
        assert cap["pump_busy_total_s"] > 0.0 and cap["pump_wait_total_s"] >= 0.0
        # a unary response takes its token events off the queue and writes
        # none: 6 tokens at steps_per_sync 4 are two landings' events
        assert cap["delivery"] == {"posted": 2, "written": 0, "tokens": 0, "taken": 2,
                                   "unread": 0}
        text = get("/v1/metrics", {"Accept": "text/plain"}).decode()
        assert "dstpu_serving_mfu " in text
        assert 'dstpu_serving_host_gap_ms_hist_bucket{le="' in text
        # on-demand profiling: 200 with a trace dir, 409 while in flight
        resp = post("/v1/debug/profile", {"duration_ms": 400})
        assert os.path.isdir(resp["path"])
        assert cap["profiling"] is None  # was idle at the metrics scrape
        try:
            post("/v1/debug/profile", {"duration_ms": 100})
            assert False, "overlapping capture should 409"
        except urllib.error.HTTPError as e:
            assert e.code == 409
        # device work inside the capture window, then let it expire
        post("/v1/completions",
             {"prompt": PROMPTS[1].tolist(), "max_tokens": 4, "seed": 5})
        deadline = time.monotonic() + 10.0
        while not gw.profiler.captures and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gw.profiler.captures, "capture never expired"
        assert gw.profiler.active is None
        assert trace_artifacts(resp["path"]), "profile wrote no artifacts"
    finally:
        assert gw.close(60), "gateway failed to drain"
