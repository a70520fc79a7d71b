"""Program spans on the profiler's clock, names on what the device runs, the
set-up counters and the prefill-lane histogram.

- ``TelemetrySink.span`` marks a ``jax.profiler.TraceAnnotation``
  (``dstpu/<name>``) whether the sink is enabled or not: a CPU profiler
  capture around a tiny scheduler run holds ``dstpu/sched/step`` with
  ``admit``, ``assemble``, ``dispatch``, ``fetch`` and ``deliver`` inside it.
- The serving Pallas calls carry their ``dstpu_...`` names into the lowered
  program; the flash-attention calls carry none (the accepted
  ``flash_attention_roofline`` matches the names they have today).
- ``compile_cache.stats()`` counts a traced, lowered and compiled program.
- ``serving/prefill_wait_ms`` is observed once per admitted request.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry import TelemetrySink, set_sink
from deepspeed_tpu.telemetry.capacity import PUMP_PARTS, PUMP_SPANS, HostGapTracker
from deepspeed_tpu.utils import compile_cache

_RNG = np.random.default_rng(31)
PROMPTS = [_RNG.integers(0, 256, 40).astype(np.int32), _RNG.integers(0, 256, 17).astype(np.int32)]
INNER = ("admit", "assemble", "dispatch", "fetch", "deliver")


def make_engine(params=None, telemetry=None):
    comm._state["mesh"] = None
    set_sink(None)
    cfg = {"dtype": "float32", "max_out_tokens": 512,
           "continuous_batching": {"enabled": True, "num_slots": 4}}
    if telemetry:
        cfg["telemetry"] = telemetry
    return deepspeed_tpu.init_inference("tiny", config=cfg, params=params)


@pytest.fixture(scope="module")
def params():
    return jax.device_get(make_engine().params)


def _decode(eng, n=3, max_new=8):
    handles = [eng.scheduler().submit(PROMPTS[i % 2], max_new_tokens=max_new, seed=7 + i)
               for i in range(n)]
    return [h.result().tolist() for h in handles]


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the ``dstpu/`` annotations in a capture."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                        if e.name.startswith("dstpu/")]
    return out


@pytest.mark.parametrize("sink_on", [False, True], ids=["sink_disabled", "sink_enabled"])
def test_profiler_capture_holds_the_pump_spans(params, tmp_path, sink_on):
    tel = ({"enabled": True, "output_path": str(tmp_path / "tel")} if sink_on else None)
    eng = make_engine(params, telemetry=tel)
    _decode(eng, n=2, max_new=4)  # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _decode(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path / "trace"))
    steps = [(a, b) for name, a, b in spans if name == "dstpu/sched/step"]
    assert steps, sorted({name for name, _, _ in spans})
    for inner in INNER:
        mine = [(a, b) for name, a, b in spans if name == "dstpu/sched/" + inner]
        assert mine, f"no dstpu/sched/{inner} in the capture"
        # every inner span lies inside one iteration's span
        assert all(any(s0 <= a and b <= s1 for s0, s1 in steps) for a, b in mine), inner
    # block level only: a handful per iteration, however many tokens it carried
    per_step = sum(1 for name, _, _ in spans if name != "dstpu/sched/step") / len(steps)
    assert per_step <= 8, per_step
    if sink_on:
        eng.telemetry.close()
        import json
        with open(eng.telemetry.jsonl_path) as f:
            events = [json.loads(line) for line in f]
        names = {e["name"] for e in events if e.get("type") == "span"}
        assert {"sched/step"} | {"sched/" + i for i in INNER} <= names
    else:
        assert not eng.telemetry.enabled and eng.scheduler()._gap is None


def test_span_feeds_the_gap_tracker_from_its_boundaries():
    class Sink:
        enabled = True

        def __init__(self):
            self.hist, self.counters = [], {}

        def histogram(self, name, value, attrs=None):
            self.hist.append((name, value))

        def counter(self, name, value=1, attrs=None):
            self.counters[name] = self.counters.get(name, 0.0) + value

    fake = Sink()
    gap = HostGapTracker(fake)
    gap.span_enter("sched/fetch", 0.0)
    gap.span_exit("sched/fetch", 0.0, 1.0)            # results on the host: gap and period open
    gap.span_enter("sched/step", 1.0)
    gap.span_enter("sched/admit", 1.0)
    gap.span_enter("sched/trie_probe", 1.002)
    gap.span_exit("sched/trie_probe", 1.002, 1.005)   # 3 ms out of admission's 10
    gap.span_exit("sched/admit", 1.0, 1.010)
    gap.span_enter("sched/assemble", 1.010)
    gap.span_exit("sched/assemble", 1.010, 1.014)
    gap.span_enter("sched/dispatch", 1.020)           # closes the gap: 20 ms
    assert fake.hist == [("serving/host_gap_ms", pytest.approx(20.0))]
    assert not fake.counters                          # the period is open until the landing
    gap.span_exit("sched/dispatch", 1.020, 1.020)
    gap.span_enter("sched/fetch", 1.020)
    gap.span_exit("sched/fetch", 1.020, 1.030)
    assert fake.counters["serving/pump/admit_ms"] == pytest.approx(7.0)
    assert fake.counters["serving/pump/trie_probe_ms"] == pytest.approx(3.0)
    assert fake.counters["serving/pump/assemble_ms"] == pytest.approx(4.0)
    assert fake.counters["serving/pump/other_ms"] == pytest.approx(6.0)
    assert fake.counters["serving/pump/busy_ms"] == pytest.approx(20.0)
    assert fake.counters["serving/pump/wait_ms"] == pytest.approx(10.0)
    assert fake.hist[1:] == [("serving/pump_busy_ms", pytest.approx(20.0)),
                             ("serving/pump_wait_ms", pytest.approx(10.0))]
    # every span the pump opens has a part to go to, and every part a counter's name
    assert set(PUMP_SPANS.values()) <= set(PUMP_PARTS) == set(gap._acc)


def test_pump_ahead_observes_a_zero_gap_every_sync(params, tmp_path):
    """The scheduler's own spans through its own tracker: every sync launched
    with the last one unlanded adds one 0.0 to ``serving/host_gap_ms`` (an
    empty histogram has no quantile for ``sched_host_gap_ms`` to read), the
    gaps measured sum to ``total_gap_s`` as before, and every landed sync,
    ahead or serial, adds one observation of its host work and of its wait."""
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)})
    sched = eng.scheduler()
    handles = [sched.submit(PROMPTS[i], max_new_tokens=24, seed=i) for i in range(2)]
    sched.drain()
    assert all(len(h.result()) == 24 for h in handles)
    tel = eng.telemetry
    assert sched.syncs_ahead > 0
    assert tel.counter_total("serving/syncs_ahead") == sched.syncs_ahead
    assert tel.counter_total("serving/syncs_serial") == sched.syncs_serial
    snap = tel.snapshot()
    hg = snap["histograms"]["serving/host_gap_ms"]
    assert hg["count"] == sched._gap.gaps >= sched.syncs_ahead
    assert hg["min"] == 0.0
    assert hg["sum"] == pytest.approx(sched._gap.total_gap_s * 1e3, rel=1e-6)
    landed = sched.syncs_ahead + sched.syncs_serial
    busy, wait = (snap["histograms"][f"serving/pump_{part}_ms"] for part in ("busy", "wait"))
    assert busy["count"] == wait["count"] == landed
    counters = snap["counters"]
    # the histograms hold the landed periods; the counters the last landing's delivery too
    assert busy["sum"] <= counters["serving/pump/busy_ms"]["total"] + 1e-5
    assert wait["sum"] == pytest.approx(counters["serving/pump/wait_ms"]["total"], rel=1e-6)
    tel.close()
    set_sink(None)


def test_scheduler_times_no_gap_section_by_hand():
    import inspect
    from deepspeed_tpu.inference import scheduler
    src = inspect.getsource(scheduler)
    assert "_gap.add(" not in src and "_gap.sync_end(" not in src and "_gap.dispatch(" not in src
    # and every span it opens is one the account has a part for
    import re
    opened = set(re.findall(r'_span\("([^"]+)"\)', src))
    assert {"sched/step", "sched/fetch", "sched/dispatch"} <= opened <= set(PUMP_SPANS)


def test_disabled_sink_span_records_nothing_but_tells_its_observer(tmp_path):
    seen = []

    class Obs:
        def span_enter(self, name, ts):
            seen.append(("enter", name))

        def span_exit(self, name, t0, t1):
            seen.append(("exit", name, t1 >= t0))

    sink = TelemetrySink({"enabled": False, "output_path": str(tmp_path / "t")})
    with sink.span("x/y", observer=Obs(), k=1) as span:
        assert span.name == "x/y" and span.attrs == {"k": 1}
    assert seen == [("enter", "x/y"), ("exit", "x/y", True)]
    assert sink._buffer == [] and not (tmp_path / "t").exists()


# ------------------------------------------------------------------ names
def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_serving_kernels_carry_their_names_into_the_program():
    """The int8 fused serving step, one layer deep: its lowering names the
    four serving kernels and the region that writes the pool."""
    import dataclasses
    from deepspeed_tpu.models import get_model
    cfg = dataclasses.replace(
        get_model("tiny").cfg, dtype=jnp.bfloat16, int8_weights=True, int8_fused_qkv=True,
        attention_impl="flash", scan_layers=False, num_layers=1)
    model = type(get_model("tiny"))(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    pool = jax.eval_shape(lambda: model.init_cache(8, 128))
    ids = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    rows = jax.ShapeDtypeStruct((8, ), jnp.int32)
    text = jax.jit(model.fused_paged_step).lower(params, ids, pool, ids, rows, rows).as_text(
        debug_info=True)
    for name in ("dstpu_decode_attn", "dstpu_fused_qkv_ln", "dstpu_fused_out_mlp",
                 "dstpu_quant_matmul", "kv_commit", "/lm_head/"):
        assert name in text, name


class _Lowered(Exception):
    """Carries a step program's lowering out of the pump that was about to run it."""


def _step_program_text(model_name, mark_head):
    """The first step program ``warm_programs`` would dispatch, lowered and
    not run: (text without locations, text with them). With ``mark_head``
    off, ``lm_head`` scopes are not entered (the parent's program)."""
    import contextlib
    from unittest import mock
    from deepspeed_tpu.models import get_model
    comm._state["mesh"] = None
    set_sink(None)
    eng = deepspeed_tpu.init_inference(
        get_model(model_name, dtype=jnp.float32),
        config={"dtype": "float32", "max_out_tokens": 128,
                "continuous_batching": {"enabled": True, "num_slots": 3, "steps_per_sync": 2,
                                        "prefill_chunk": 16}})
    sched = eng.scheduler()

    def lower(fn, call_args):
        lowered = fn.lower(*call_args)
        raise _Lowered(lowered.as_text(), lowered.as_text(debug_info=True))

    sched._run_program = lower
    scope = jax.named_scope
    unmarked = mock.patch.object(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name == "lm_head" else scope(name))
    with pytest.raises(_Lowered) as got, (contextlib.nullcontext() if mark_head else unmarked):
        sched.warm_programs(ladder=False)
    return got.value.args


@pytest.mark.parametrize("model_name", ["tiny", "tiny-hybrid", "tiny-mla-moe"])
def test_lm_head_scope_marks_the_head_and_moves_nothing_else(model_name):
    """The served step program's final norm and vocabulary product are
    traced under ``lm_head`` (what ``lm_head_device_pct`` reads in the
    device trace), and the scope is metadata: without locations the program
    is text for text the one lowered with the scope never entered."""
    import re
    plain, located = _step_program_text(model_name, mark_head=True)
    parent_plain, parent_located = _step_program_text(model_name, mark_head=False)
    assert plain == parent_plain
    # the product over the vocabulary, and the norm before it
    assert re.search(r'"[^"]*/lm_head/[^"]*dot_general', located), model_name
    assert re.search(r'"[^"]*/lm_head/[^"]*final_norm', located), model_name
    assert not re.search(r'"[^"]*/lm_head/[^"]*final_norm', parent_located)
    # the choice right behind it keeps its own mark
    assert re.search(r'"[^"]*/sample["/]', located)


def test_flash_attention_calls_stay_unnamed():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    text = _lowered(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert "tpu_custom_call" in text or "pallas" in text.lower()
    assert "dstpu_" not in text


def test_train_step_regions_are_named():
    from deepspeed_tpu.models import get_model
    comm._state["mesh"] = None
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=get_model("tiny", dtype=jnp.float32, max_seq_len=32),
        config={"train_micro_batch_size_per_gpu": 2, "steps_per_print": 10**9,
                "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    ids = np.zeros((engine.train_batch_size(), 32), np.int32)
    engine.train_batch(batch={"input_ids": ids})
    stacked = {"input_ids": ids.reshape((1, ) + ids.shape)}
    with engine.mesh:
        text = engine._compiled["train_batch"].lower(engine.state, stacked).as_text(
            debug_info=True)
    for scope in ("grad_norm", "optimizer", "loss_ce"):
        assert scope in text, scope


# ------------------------------------------------------------------ counters
def test_compile_stats_count_one_program():
    compile_cache.listen()
    before = compile_cache.stats()

    def fresh(x):  # a function no other test has compiled
        return (x * 3.25 + 1.5).sum()

    jax.jit(fresh)(np.ones(7, np.float32))
    after = compile_cache.stats()
    for phase in ("trace", "lower", "backend"):
        assert after[phase + "_count"] - before[phase + "_count"] == 1, phase
        assert after[phase + "_s"] > before[phase + "_s"], phase
    assert after["cache_read_s"] >= before["cache_read_s"]
    assert after["cache_read_s"] <= after["backend_s"]


def test_compile_stats_nested_jit_seconds_are_a_union():
    compile_cache.listen()

    @jax.jit
    def inner(x):
        return x * 2.75

    def outer(x):
        return inner(x) + inner(x + 1.0)

    before = compile_cache.stats()
    import time
    t0 = time.time()
    jax.jit(outer)(np.ones(5, np.float32))
    wall = time.time() - t0
    after = compile_cache.stats()
    assert after["trace_count"] - before["trace_count"] == 1  # inner's lie inside outer's
    assert after["trace_s"] - before["trace_s"] <= wall


def test_prefill_wait_observed_once_per_admitted_request(params, tmp_path):
    eng = make_engine(params, telemetry={"enabled": True, "output_path": str(tmp_path)})
    _decode(eng, n=5, max_new=3)
    hists = eng.telemetry.snapshot()["histograms"]
    assert hists["serving/prefill_wait_ms"]["count"] == 5
    assert hists["serving/ttft_ms"]["count"] == 5
    # waiting for the lane is part of the time to the first token
    assert hists["serving/prefill_wait_ms"]["sum"] <= hists["serving/ttft_ms"]["sum"]
    eng.telemetry.close()
