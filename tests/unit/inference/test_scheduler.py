"""Continuous-batching decode scheduler tests.

Covers the serving invariants the static-batch engine tests can't: admission
and eviction at token-iteration granularity, queue saturation, slot reuse
purity (a request's tokens AND logits must not depend on which slot it lands
in or what else is in flight), and the compile-count bound that makes
continuous batching viable on XLA.
"""

import numpy as np
import pytest
import jax

import deepspeed_tpu

from ._serving import engine, fresh_process_state

PROMPTS = [[5, 6, 7, 8, 9], [10, 11, 12]]


def make_engine(model="tiny", params=None, **cfg):
    # (drops any process-global telemetry sink a previous test's engine
    # installed: an enabled global sink takes precedence over this engine's
    # own config, so counter assertions would see cross-test events)
    fresh_process_state()
    config = {"dtype": "float32"}
    config.update(cfg)
    return deepspeed_tpu.init_inference(model, config=config, params=params)


def make_sched_engine(params=None, num_slots=4, collect_logits=False, fresh=False, **cfg):
    """An engine over ``tiny`` whose scheduler shares its shape's step programs
    (``_serving.engine``); ``fresh``: a case that asserts what its scheduler
    BUILT (``_compiled``, ``compiled_program_count()``, XLA's compiles) or sets
    what a trace reads builds its own."""
    kernels = cfg.pop("replace_with_kernel_inject", False)
    return engine(("tiny", params), num_slots, 64, 4, kernels, fresh=fresh,
                  config=dict({"max_out_tokens": 1024}, **cfg), collect_logits=collect_logits)


@pytest.fixture(scope="module")
def baseline():
    eng = make_engine()
    params = jax.device_get(eng.params)
    out = eng.generate(PROMPTS, max_new_tokens=8)
    return params, out


def test_scheduler_matches_generate(baseline):
    """Mixed-length greedy requests through the scheduler == the static
    generate() path."""
    params, out = baseline
    eng = make_sched_engine(params)
    sched = eng.scheduler()
    handles = [sched.submit(p, max_new_tokens=8) for p in PROMPTS]
    got = [h.result() for h in handles]
    assert all((a == b).all() for a, b in zip(out, got))


def test_submit_routes_through_scheduler(baseline):
    """engine.submit() on the continuous-batching config serves the batch
    through the shared scheduler and matches generate()."""
    params, out = baseline
    eng = make_sched_engine(params)
    h = eng.submit(PROMPTS, max_new_tokens=8)
    got = h.result()
    assert h.done
    assert all((a == b).all() for a, b in zip(out, got))
    assert eng._scheduler is not None and eng._scheduler.cache.total_allocs == len(PROMPTS)


def test_queue_saturation_and_slot_reuse(baseline):
    """More requests than slots: the queue drains through slot reuse, every
    request completes, and the pool ends empty."""
    params, out = baseline
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler()
    handles = [sched.submit(PROMPTS[i % 2], max_new_tokens=8) for i in range(7)]
    # saturated: only num_slots admitted, the rest queued
    sched.step()
    assert sched.cache.active_slots <= 2 and len(sched.queue) >= 3
    results = [h.result() for h in handles]
    for i, r in enumerate(results):
        assert (r == out[i % 2]).all(), f"request {i} corrupted by slot reuse"
    assert sched.cache.active_slots == 0 and not sched.queue
    assert sched.cache.total_allocs == 7 and sched.cache.total_frees == 7


def test_eos_evicts_mid_loop(baseline):
    """Rows finishing at different steps (EOS hit, length budget, full run)
    evict at token-iteration granularity; freed slots admit queued requests
    before the next decode step."""
    params, out = baseline
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler()
    eos0 = int(out[0][0])  # greedy row 0 emits this immediately
    hs = [sched.submit(PROMPTS[0], max_new_tokens=8, eos_token_id=eos0),
          sched.submit(PROMPTS[1], max_new_tokens=3),  # length budget at step 3
          sched.submit(PROMPTS[1], max_new_tokens=8),  # queued behind the first two
          sched.submit(PROMPTS[0], max_new_tokens=8, eos_token_id=int(out[1][0]))]
    r0 = hs[0].result()
    assert r0[-1] == eos0 and len(r0) == 1  # evicted after its first token
    assert (hs[1].result() == out[1][:3]).all()
    # served on reused slots, bit-identical to the static path
    assert (hs[2].result() == out[1]).all()
    assert (hs[3].result() == out[0]).all()  # eos never hit: full 8 tokens
    assert sched.cache.active_slots == 0 and sched.cache.total_frees == 4


def test_slot_reuse_bit_identical_logits(baseline):
    """The same request run solo vs late in a busy mixed stream must produce
    BIT-identical per-step logits (slot reuse and batch composition must not
    leak into any row's math)."""
    params, _ = baseline
    eng = make_sched_engine(params, num_slots=2, collect_logits=True)
    sched = eng.scheduler()
    solo = sched.submit(PROMPTS[0], max_new_tokens=6)
    solo_logits = solo.result_logits()
    # busy stream: different prompts in flight, then the same request again —
    # admitted onto a reused slot
    filler = [sched.submit(PROMPTS[1], max_new_tokens=7) for _ in range(3)]
    again = sched.submit(PROMPTS[0], max_new_tokens=6)
    again_logits = again.result_logits()
    for h in filler:
        h.result()
    assert (solo.result() == again.result()).all()
    np.testing.assert_array_equal(solo_logits, again_logits)


def test_sampling_reproducible_and_slot_independent(baseline):
    """Seeded sampling is a function of (seed, step), not slot or batch
    composition: the same request re-submitted into a busy pool repeats."""
    params, _ = baseline
    eng = make_sched_engine(params, fresh=True, num_slots=3)
    sched = eng.scheduler()
    kw = dict(max_new_tokens=6, do_sample=True, temperature=0.7, top_k=20, top_p=0.9,
              seed=11)
    a = sched.submit(PROMPTS[0], **kw).result()
    filler = [sched.submit(PROMPTS[1], max_new_tokens=5) for _ in range(2)]
    b = sched.submit(PROMPTS[0], **kw).result()
    for h in filler:
        h.result()
    assert (a == b).all()
    # and mixed greedy/sampled rows share one decode program (the width-1
    # variant of the fused step)
    assert ("fused", True, False, 1, sched.steps_per_sync) in sched._compiled


def test_scheduler_kernel_inject_matches_xla(baseline):
    """The paged Pallas decode kernel path == the XLA slot path — including
    the span kernel (paged_span_attention) through a multi-chunk prefill."""
    params, _ = baseline
    prompts = PROMPTS + [list(range(1, 101))]  # 100 tokens: 2 fused chunks
    eng_x = make_sched_engine(params)
    got_x = [h.result() for h in
             [eng_x.scheduler().submit(p, max_new_tokens=8) for p in prompts]]
    eng_k = make_sched_engine(params, replace_with_kernel_inject=True)
    assert eng_k.model_config.attention_impl == "flash"
    got_k = [h.result() for h in
             [eng_k.scheduler().submit(p, max_new_tokens=8) for p in prompts]]
    assert all((a == b).all() for a, b in zip(got_x, got_k))


def test_steps_per_sync_invariant(baseline):
    """Multi-step scheduling must not change results: K=1 (pure
    iteration-level) and K=3 (budget not a multiple of K) produce identical
    tokens for greedy AND seeded sampling."""
    params, out = baseline
    outs = []
    for k in (1, 3):
        eng = make_sched_engine(params, num_slots=2)
        sched = eng.scheduler(steps_per_sync=k)
        assert sched.steps_per_sync == k
        hs = [sched.submit(PROMPTS[0], max_new_tokens=8),
              sched.submit(PROMPTS[1], max_new_tokens=7, do_sample=True,
                           temperature=0.8, top_k=15, seed=7)]
        outs.append([h.result() for h in hs])
    (g1, s1), (g3, s3) = outs
    assert (g1 == out[0]).all() and (g1 == g3).all()
    assert (s1 == s3).all() and len(s1) == 7


def test_cancelled_handles_free_slots(baseline):
    """Dropping an unfinished batch handle flags its requests; the next
    scheduler iteration evicts them (no GC-time decode pumping) and their
    slots serve the queue."""
    params, out = baseline
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler()
    abandoned = eng.submit([PROMPTS[0], PROMPTS[1]], max_new_tokens=64)
    sched.step()  # chunked admission: at most ONE prefill starts per iteration
    sched.step()  # second request admitted, both mid-generation
    assert sched.cache.active_slots == 2
    del abandoned  # __del__ cancels, must not run the decode loop
    import gc
    gc.collect()
    assert sched.cache.active_slots == 2  # nothing mutated from GC
    kept = sched.submit(PROMPTS[0], max_new_tokens=8)
    got = kept.result()  # pump: reaps the cancelled pair, then serves
    assert (got == out[0]).all()
    assert sched.cache.active_slots == 0 and not sched.queue


def test_request_too_long_rejected(baseline):
    params, _ = baseline
    eng = make_sched_engine(params)
    sched = eng.scheduler()
    with pytest.raises(ValueError, match="cache rows"):
        sched.submit(list(range(1, 100)), max_new_tokens=sched.max_len)


def test_edge_budgets_and_seeds(baseline):
    """Static-path parity at the boundaries: zero budget returns zero
    tokens (no slot consumed); negative seeds are accepted (masked to
    uint32) and stay reproducible."""
    params, _ = baseline
    eng = make_sched_engine(params)
    sched = eng.scheduler()
    h = sched.submit(PROMPTS[0], max_new_tokens=0)
    assert h.done and len(h.result()) == 0
    assert sched.cache.total_allocs == 0
    a = sched.submit(PROMPTS[0], max_new_tokens=5, do_sample=True, seed=-3).result()
    b = sched.submit(PROMPTS[0], max_new_tokens=5, do_sample=True, seed=-3).result()
    assert (a == b).all() and len(a) == 5
    assert sched.cache.active_slots == 0  # nothing stranded


_XLA_COMPILES = []  # registered once: jax.monitoring listeners can't detach


def _count_xla_compiles():
    if not _XLA_COMPILES:
        _XLA_COMPILES.append("registered")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _XLA_COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _XLA_COMPILES


def test_fused_compile_count_o1_in_length_mix(baseline):
    """Compile-count guard: a mixed-length stream through the fused
    chunk+decode sync compiles O(1) programs — the fused sync (its K-step
    and, for idle-pool non-final chunks, 1-step variants), its width-1
    pure-decode variant, and the slot-copy program — with no growth in the
    prompt-length mix, measured by actual XLA backend compiles
    (jax.monitoring), not just the scheduler's own cache."""
    params, _ = baseline
    eng = make_sched_engine(params, fresh=True, num_slots=3)
    sched = eng.scheduler()  # radix cache on by default
    assert sched.radix is not None
    compiles = _count_xla_compiles()
    n_before = len(compiles)
    lens = [2, 3, 5, 9, 17, 33, 40, 50, 63, 64, 65, 70, 90, 100]
    handles = [sched.submit(list(range(1, n + 1)), max_new_tokens=4) for n in lens]
    for h in handles:
        h.result()
    n_compiles = len(compiles) - n_before
    keys = set(sched._compiled)
    C, K = sched.prefill_chunk, sched.steps_per_sync
    assert keys <= {("fused", False, False, C, K), ("fused", False, False, C, 1),
                    ("fused", False, False, 1, K), "copy"}, keys
    assert sched.compiled_program_count() <= 4
    assert n_compiles <= 5, f"XLA compiled {n_compiles} programs on the fused path"
    assert all(len(h.result()) == 4 for h in handles)
    # the nested-range stream shares prefixes: the radix cache must land hits
    assert sched.radix.hits > 0


def test_telemetry_gauges_and_counters(tmp_path, baseline):
    """Scheduler wires occupancy/batch-efficiency gauges, admitted/evicted
    counters, and TTFT/step histograms into the PR-1 sink."""
    params, _ = baseline
    eng = make_sched_engine(params, num_slots=2,
                            telemetry={"enabled": True, "output_path": str(tmp_path)})
    sched = eng.scheduler()
    hs = [sched.submit(PROMPTS[i % 2], max_new_tokens=5) for i in range(4)]
    for h in hs:
        h.result()
    tel = eng.telemetry
    assert tel.counter_total("serving/admitted") == 4
    assert tel.counter_total("serving/evicted") == 4
    assert tel.counter_total("serving/decode_tokens") > 0
    tel.flush()
    text = (tmp_path / "telemetry.jsonl").read_text()
    for name in ("serving/slot_occupancy", "serving/batch_efficiency",
                 "serving/kv_token_utilization", "serving/ttft_ms", "serving/step_ms"):
        assert name in text, f"{name} missing from telemetry stream"


def test_prompt_exceeding_capacity_rejected_at_submit(baseline):
    """A prompt that can never fit a slot fails at submit() with a clear
    message — not deep inside a compiled prefill — and leaves no state
    behind (satellite bugfix: the pre-chunking scheduler only validated
    prompt + budget, so a too-long prompt with a tiny budget crashed in
    the prefill program)."""
    params, _ = baseline
    eng = make_sched_engine(params)
    sched = eng.scheduler()
    with pytest.raises(ValueError, match="per-slot KV capacity"):
        sched.submit(list(range(1, sched.max_len + 2)), max_new_tokens=1)
    # boundary: prompt == max_len leaves no decode headroom either
    with pytest.raises(ValueError, match="per-slot KV capacity"):
        sched.submit([1] * sched.max_len, max_new_tokens=1)
    assert sched.cache.total_allocs == 0 and not sched.queue


def test_chunk_size_does_not_change_tokens(baseline):
    """Multi-chunk prefill (prompt >> chunk) produces the same tokens as one
    chunk wider than the prompt (the whole prompt in a single forward), for
    any chunk size. (generate() parity for scheduler-servable prompt lengths
    is test_scheduler_matches_generate — the static path can't fit this
    prompt's padded cache on the tiny model.)"""
    params, _ = baseline
    prompt = [int(t) for t in np.resize(np.arange(3, 40), 100)]
    out_one = make_sched_engine(params).scheduler(prefill_chunk=128).submit(
        prompt, max_new_tokens=8).result()
    assert len(out_one) == 8
    for chunk in (16, 64):  # 7 chunks and 2 chunks through the state machine
        eng = make_sched_engine(params)
        got = eng.scheduler(prefill_chunk=chunk).submit(prompt, max_new_tokens=8).result()
        assert (got == out_one).all(), f"chunk={chunk} diverged from the one-chunk prefill"


@pytest.mark.parametrize("chunk", [0, -1])
def test_prefill_chunk_below_one_is_refused(baseline, chunk):
    """``prefill_chunk=0`` selected the monolithic bucketed prefill until
    PR 28 removed it: the constructor and the config both say so."""
    params, _ = baseline
    msg = "monolithic prefill path .* was removed in PR 28.*wide as the prompt"
    with pytest.raises(ValueError, match=msg):
        make_sched_engine(params).scheduler(prefill_chunk=chunk)
    with pytest.raises(ValueError, match=msg):
        make_engine(params=params, continuous_batching={"enabled": True,
                                                        "prefill_chunk": chunk})


def test_decode_advances_during_chunked_prefill(baseline):
    """The Sarathi-Serve property: while a long prompt chunk-prefills, live
    decode rows keep advancing every scheduler iteration (one token in the
    fused step + the sync's remaining K-1 decode steps — never stalling for
    the whole prompt) and their outputs stay BIT-identical to an idle-pool
    run."""
    params, _ = baseline
    long_prompt = [int(t) for t in np.resize(np.arange(3, 40), 100)]
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler(prefill_chunk=16)
    solo_out = sched.submit(PROMPTS[0], max_new_tokens=10).result()
    a = sched.submit(PROMPTS[0], max_new_tokens=10)
    sched.step()  # a admitted + prefilled (single chunk)
    b = sched.submit(long_prompt, max_new_tokens=4)
    sched.step()  # b's first chunk rides the fused step
    assert sched._prefill is not None, "100-token prompt must span many chunks"
    n_before = len(a._req.out)
    sched.step()
    # the fused step advances a one token and the sync's remaining K-1
    # decode steps keep multi-step amortization (capped by a's budget)
    n_after = len(a._req.out)
    assert n_after > n_before, "decode stalled behind the prefill"
    assert n_after <= n_before + sched.steps_per_sync
    assert sched._prefill is not None
    assert (a.result() == solo_out).all()
    assert len(b.result()) == 4
    sched.cache.check_invariants()


def test_prefix_cache_hit_bit_identical_logits(baseline):
    """Acceptance criterion: a request served via a radix prefix hit (donor
    KV rows copied, only the suffix chunk-prefilled) produces BIT-identical
    per-step logits to the same request cold-prefilled on a cache-less
    scheduler."""
    params, _ = baseline
    prompt = [int(t) for t in np.resize(np.arange(5, 47), 70)]  # > one chunk
    eng_cold = make_sched_engine(params, collect_logits=True)
    sched_cold = eng_cold.scheduler(prefix_cache=False)
    cold = sched_cold.submit(prompt, max_new_tokens=6)
    cold_logits = cold.result_logits()
    assert sched_cold.radix is None

    eng = make_sched_engine(params, fresh=True, collect_logits=True)
    sched = eng.scheduler()
    first = sched.submit(prompt, max_new_tokens=6)
    first_logits = first.result_logits()  # cold: registers the 70-token prefix
    hit = sched.submit(prompt, max_new_tokens=6)
    hit_logits = hit.result_logits()  # 64 rows copied from the donor slot
    assert sched.radix.misses == 1 and sched.radix.hits == 1
    assert "copy" in sched._compiled, "prefix hit must run the slot-copy program"
    np.testing.assert_array_equal(cold_logits, first_logits)
    np.testing.assert_array_equal(cold_logits, hit_logits)
    assert (cold.result() == hit.result()).all()
    sched.cache.check_invariants()


def test_prefix_cache_single_slot_repeat_hits(baseline):
    """Admission-for-eviction must not destroy the incoming prompt's only
    donor: with ONE slot, re-submitting the same prompt reclaims the cached
    donor slot itself — the freed slot IS the donor, its rows stay
    resident (src == dst copy is a no-op), and the hit stands."""
    params, _ = baseline
    prompt = [int(t) for t in np.resize(np.arange(5, 47), 70)]  # > one chunk
    eng = make_sched_engine(params, fresh=True, num_slots=1)
    sched = eng.scheduler()
    first = sched.submit(prompt, max_new_tokens=6).result()
    again = sched.submit(prompt, max_new_tokens=6).result()
    assert sched.radix.hits == 1 and sched.radix.misses == 1
    assert sched.radix.evictions == 1  # the donor slot was reclaimed...
    assert "copy" not in sched._compiled  # ...so the hit needed no copy
    assert (first == again).all()
    # retained lengths clamp to the registered prompt prefix: decode and
    # K-step-overshoot rows must not inflate the utilization gauges
    assert sched.cache.cached_tokens() == len(prompt)
    sched.cache.check_invariants()


def test_prefix_cache_eviction_spares_matched_donor(baseline):
    """When OTHER cached slots exist, eviction-for-admission must pick one
    of them over the incoming prompt's matched donor — even when the donor
    is the least recently used registration."""
    params, _ = baseline
    pa = [int(t) for t in np.resize(np.arange(5, 47), 70)]
    pb = [int(t) for t in np.resize(np.arange(90, 140), 70)]
    eng = make_sched_engine(params, fresh=True, num_slots=2)
    sched = eng.scheduler()
    sched.submit(pa, max_new_tokens=3).result()  # donor, and the LRU entry
    sched.submit(pb, max_new_tokens=3).result()
    out_a = sched.submit(pa, max_new_tokens=3).result()  # must evict pb's slot
    assert sched.radix.hits == 1 and sched.radix.evictions == 1
    assert "copy" in sched._compiled, "spared donor should seed via slot copy"
    assert (out_a == sched.submit(pa, max_new_tokens=3).result()).all()
    sched.cache.check_invariants()


def test_prefix_cache_eviction_storm_through_scheduler(baseline):
    """More distinct prompts than slots: every admission reclaims the LRU
    cached prefix; accounting never drifts and every request completes."""
    params, _ = baseline
    rng = np.random.default_rng(3)
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler()
    for i in range(8):
        p = [int(t) for t in rng.integers(1, 200, int(rng.integers(2, 90)))]
        out = sched.submit(p, max_new_tokens=3).result()
        assert len(out) == 3
        sched.cache.check_invariants()
    assert sched.radix.evictions > 0
    assert sched.cache.active_slots == 0 and sched.cache.cached_slots > 0
    assert sched.cache.total_allocs == sched.cache.total_frees == 8


def test_prefix_cache_telemetry(tmp_path, baseline):
    """Satellite: serving/prefix_cache_{hit,miss,evict} counters and the
    hit-rate gauge reach the sink."""
    params, _ = baseline
    eng = make_sched_engine(params, num_slots=2,
                            telemetry={"enabled": True, "output_path": str(tmp_path)})
    sched = eng.scheduler()
    shared = [int(t) for t in np.resize(np.arange(5, 47), 70)]
    sched.submit(shared, max_new_tokens=3).result()  # miss: registers
    sched.submit(shared, max_new_tokens=3).result()  # hit: donor copy
    for base in (100, 140):  # distinct prompts forcing LRU eviction
        sched.submit(list(range(base, base + 80)), max_new_tokens=3).result()
    tel = eng.telemetry
    assert tel.counter_total("serving/prefix_cache_hit") == 1
    assert tel.counter_total("serving/prefix_cache_miss") == 3
    assert tel.counter_total("serving/prefix_cache_evict") >= 1
    assert tel.counter_total("serving/prefix_cache_hit_tokens") == 64
    tel.flush()
    text = (tmp_path / "telemetry.jsonl").read_text()
    assert "serving/prefix_cache_hit_rate" in text


def test_on_token_streams_in_delivery_order(baseline):
    """The incremental streaming hook: on_token sees every generated token
    in order, done=True exactly on the final one, and the hooked result
    equals result() — for greedy AND seeded sampling, across slot reuse."""
    params, out = baseline
    eng = make_sched_engine(params, num_slots=2)
    sched = eng.scheduler()
    seen = {}

    def hook(name):
        seen[name] = []
        return lambda tok, done: seen[name].append((tok, done))

    hs = [sched.submit(PROMPTS[0], max_new_tokens=8, on_token=hook("a")),
          sched.submit(PROMPTS[1], max_new_tokens=5, do_sample=True, seed=3,
                       on_token=hook("b")),
          sched.submit(PROMPTS[0], max_new_tokens=8, on_token=hook("c"))]
    res = [h.result() for h in hs]
    assert [t for t, _ in seen["a"]] == list(res[0]) == list(out[0])
    assert [t for t, _ in seen["b"]] == list(res[1])
    assert [t for t, _ in seen["c"]] == list(res[2])
    for evs in seen.values():
        assert [d for _, d in evs] == [False] * (len(evs) - 1) + [True]
    # zero-budget edge: done at submit, the hook never fires
    h0 = sched.submit(PROMPTS[0], max_new_tokens=0, on_token=hook("z"))
    assert h0.done and seen["z"] == []


def test_on_token_changes_nothing(baseline):
    """Hook presence must not change logits or the compiled-program set —
    it runs host-side after the fetch, never inside a program. A raising
    hook is logged and swallowed: delivery and the shared loop continue."""
    params, _ = baseline
    eng = make_sched_engine(params, fresh=True, num_slots=2, collect_logits=True)
    sched = eng.scheduler()
    plain = sched.submit(PROMPTS[0], max_new_tokens=6)
    plain_logits = plain.result_logits()
    programs_before = sched.compiled_program_count()
    toks = []
    hooked = sched.submit(PROMPTS[0], max_new_tokens=6,
                          on_token=lambda tok, done: toks.append(tok))
    hooked_logits = hooked.result_logits()
    np.testing.assert_array_equal(plain_logits, hooked_logits)
    assert (plain.result() == hooked.result()).all()
    assert toks == list(hooked.result())
    assert sched.compiled_program_count() == programs_before

    def bad_hook(tok, done):
        raise RuntimeError("consumer bug")

    broken = sched.submit(PROMPTS[1], max_new_tokens=4, on_token=bad_hook)
    assert len(broken.result()) == 4  # delivery survived the raising hook
    assert sched.cache.active_slots == 0


def test_abandoned_submit_handle_never_raises(baseline):
    """_Handle.__del__ must settle the queue-depth gauge and never raise —
    even when the handle is dropped without result() (satellite: teardown
    safety)."""
    params, _ = baseline
    eng = make_engine(params=params, telemetry={"enabled": False})
    eng.telemetry.enabled = True  # force the gauge-accounting path
    h = eng.submit(PROMPTS, max_new_tokens=4)
    assert eng._inflight == 1
    del h
    import gc
    gc.collect()
    assert eng._inflight == 0
    # and a half-torn-down handle is silent: break the settle path the way
    # interpreter teardown does (globals gone) and call __del__ directly —
    # the exception must be swallowed, not propagated
    h2 = eng.submit(PROMPTS, max_new_tokens=4)
    h2._settle = lambda: (_ for _ in ()).throw(RuntimeError("teardown"))
    h2.__del__()  # must not raise
    h2._accounted = True  # neutralize the real deletion's accounting


# ------------------------------------------------------------- speculative
def test_speculative_greedy_and_sampled_bit_identical(baseline):
    """Acceptance criterion: speculation is LOSSLESS — tokens AND per-step
    logits with spec_tokens > 0 are bit-identical to the non-speculative
    scheduler, for greedy and seeded-sampling requests alike (every verify
    column samples with the request's keys at its absolute step index, and
    a draft commits only on exact equality)."""
    params, _ = baseline
    kw_s = dict(max_new_tokens=10, do_sample=True, temperature=0.7, top_k=20,
                top_p=0.9, seed=11)
    eng0 = make_sched_engine(params, collect_logits=True)
    s0 = eng0.scheduler()
    base = [s0.submit(p, max_new_tokens=10) for p in PROMPTS]
    base_logits = [h.result_logits() for h in base]
    base_sampled = s0.submit(PROMPTS[0], **kw_s).result()

    eng1 = make_sched_engine(params, collect_logits=True)
    s1 = eng1.scheduler(spec_tokens=4)
    spec = [s1.submit(p, max_new_tokens=10) for p in PROMPTS]
    spec_logits = [h.result_logits() for h in spec]
    spec_sampled = s1.submit(PROMPTS[0], **kw_s).result()
    for a, b in zip(base, spec):
        assert (a.result() == b.result()).all()
    for a, b in zip(base_logits, spec_logits):
        np.testing.assert_array_equal(a, b)
    assert (base_sampled == spec_sampled).all()
    # speculation actually ran and accepted (the tiny greedy model settles
    # into a repeating stream the prompt-lookup drafter predicts)
    assert s1.spec_steps > 0 and s1.spec_accepted > 0
    assert s1.mean_spec_tokens_per_step() > 1.0
    s1.cache.check_invariants()


def test_speculative_eos_and_budget_mid_acceptance(baseline):
    """EOS landing inside an accepted draft block stops delivery at the EOS
    token (later accepted tokens are discarded, like K-step overshoot), and
    budgets cap drafting so a verify block never overruns max_new_tokens."""
    params, out = baseline
    eos0 = int(out[0][0])
    eng = make_sched_engine(params)
    sched = eng.scheduler(spec_tokens=4)
    h_eos = sched.submit(PROMPTS[0], max_new_tokens=10, eos_token_id=eos0)
    r = h_eos.result()
    assert len(r) == 1 and r[-1] == eos0
    h_budget = sched.submit(PROMPTS[1], max_new_tokens=3)
    assert len(h_budget.result()) == 3
    assert (h_budget.result() == out[1][:3]).all()
    assert sched.cache.active_slots == 0
    sched.cache.check_invariants()


def test_speculative_compile_count_o1(baseline):
    """Compile-count guard (jax.monitoring): the speculative scheduler's
    program set is O(1) across the request mix and acceptance mix — the
    fused chunk/decode programs plus ONE spec verify variant per
    (sampling, collect) actually used, at the single configured width.
    Draft counts, acceptance patterns, and prompt lengths are runtime data."""
    params, _ = baseline
    eng = make_sched_engine(params, fresh=True, num_slots=3)
    sched = eng.scheduler(spec_tokens=4)
    # phase 1 warms the full program set: short/long prompts (both fused
    # sync step-count variants + a radix copy), a repetitive prompt (spec
    # verify program) and a low-repetition one (K-step decode fallback)
    warm = [list(range(1, 6)), list(range(1, 100)), list(range(1, 100)),
            [int(t) for t in np.resize([7, 8, 9], 40)], [5, 3, 11, 2]]
    for p in warm:
        sched.submit(p, max_new_tokens=8).result()
    assert sched.spec_steps > 0
    compiles = _count_xla_compiles()
    n_before = len(compiles)
    # phase 2: a DIFFERENT mix of lengths, draft fills, and acceptance
    # patterns — zero new XLA programs allowed
    lens = [2, 9, 33, 40, 64, 70, 90]
    handles = [sched.submit(list(range(2, n + 2)), max_new_tokens=6) for n in lens]
    handles += [sched.submit([int(t) for t in np.resize([4, 5], 50)],
                             max_new_tokens=12),
                sched.submit([13, 2, 28, 6, 91], max_new_tokens=4)]
    for h in handles:
        h.result()
    n_compiles = len(compiles) - n_before
    W = sched._spec_width
    keys = set(sched._compiled)
    C, K = sched.prefill_chunk, sched.steps_per_sync
    assert keys <= {("fused", False, False, C, K), ("fused", False, False, C, 1),
                    ("fused", False, False, 1, K), ("spec", False, False, W),
                    "copy"}, keys
    assert n_compiles == 0, f"XLA compiled {n_compiles} new programs under spec mix"


def test_speculative_matches_prompt_lookup_simulation(baseline):
    """The host acceptance walk exactly mirrors an offline prompt-lookup
    simulation over the realized greedy stream: same accepted-draft count,
    same delivered tokens (end-to-end check of drafter + verify + delivery
    bookkeeping)."""
    from deepspeed_tpu.inference.speculative import PromptLookupDrafter
    params, _ = baseline
    max_new, k = 14, 3
    eng0 = make_sched_engine(params)
    truth = eng0.scheduler().submit(PROMPTS[0], max_new_tokens=max_new).result()

    eng1 = make_sched_engine(params)
    sched = eng1.scheduler(spec_tokens=k)
    got = sched.submit(PROMPTS[0], max_new_tokens=max_new).result()
    assert (got == truth).all()

    # offline replay: one request, so every spec sync drafts from the
    # prefix delivered so far and accepts matches against the true stream.
    # The final prefill chunk's sync delivers steps_per_sync tokens (token 0
    # + the K-1 substeps) before the first spec sync runs.
    drafter = PromptLookupDrafter(k, 3, 1)
    prompt = np.asarray(PROMPTS[0], np.int32)
    out = [int(t) for t in truth[:min(sched.steps_per_sync, max_new)]]
    expect_accepted = 0
    while len(out) < max_new:
        cap = min(k, max_new - len(out) - 1)
        d = drafter.draft(np.concatenate([prompt, np.asarray(out, np.int32)]), cap)
        if d.size == 0:
            # K-step decode fallback delivers steps_per_sync tokens
            take = min(sched.steps_per_sync, max_new - len(out))
            out.extend(int(t) for t in truth[len(out):len(out) + take])
            continue
        m = 1
        while m <= d.size and int(truth[len(out) + m - 1]) == int(d[m - 1]):
            m += 1
        out.extend(int(t) for t in truth[len(out):len(out) + m])
        expect_accepted += m - 1
    assert out == [int(t) for t in truth]
    assert sched.spec_accepted == expect_accepted


# ------------------------------------------------------------- int8 paged KV
def test_int8_kv_logit_error_bound_vs_bf16(baseline):
    """Acceptance criterion: the int8 paged KV tier fits >= 1.9x the bf16
    slot count at equal HBM budget, with a BOUNDED logit error against the
    full-precision pool (per-token-row joint scales keep the error within a
    few int8 steps through the whole decode)."""
    params, _ = baseline
    eng_f = make_sched_engine(params, collect_logits=True)
    s_f = eng_f.scheduler()  # "auto": full-precision (float32 test dtype)
    ref = s_f.submit(PROMPTS[0], max_new_tokens=12).result_logits()

    eng_b = make_sched_engine(params)
    s_b = eng_b.scheduler(kv_cache_dtype="bf16")
    eng_q = make_sched_engine(params, collect_logits=True)
    s_q = eng_q.scheduler(kv_cache_dtype="int8")
    assert s_q.kv_quantized and not s_b.kv_quantized
    # >= 1.9x resident rows per HBM byte vs the bf16 pool
    ratio = s_b.cache.bytes_per_token() / s_q.cache.bytes_per_token()
    assert ratio >= 1.9, f"int8 pool only {ratio:.3f}x denser than bf16"

    h = s_q.submit(PROMPTS[0], max_new_tokens=12)
    q_logits = h.result_logits()
    err = np.abs(q_logits - ref).max()
    scale = max(np.abs(ref).max(), 1e-6)
    assert err <= 0.05 * scale + 0.05, f"int8 KV logit error {err} vs scale {scale}"
    # greedy argmax survives quantization on this stream
    assert (q_logits.argmax(-1) == ref.argmax(-1)).all()
    s_q.cache.check_invariants()


def test_int8_kv_prefix_hit_and_spec_bit_identical(baseline):
    """Within the int8 tier everything stays self-consistent: a radix
    prefix hit replays the cold path bit-identically (quantized rows copy
    byte-stable), and speculation over int8 KV matches non-speculative
    int8 decode bit-for-bit."""
    params, _ = baseline
    prompt = [int(t) for t in np.resize(np.arange(5, 47), 70)]
    eng = make_sched_engine(params, collect_logits=True)
    sched = eng.scheduler(kv_cache_dtype="int8")
    cold = sched.submit(prompt, max_new_tokens=6)
    cold_logits = cold.result_logits()
    hit = sched.submit(prompt, max_new_tokens=6)
    hit_logits = hit.result_logits()
    assert sched.radix.hits == 1
    np.testing.assert_array_equal(cold_logits, hit_logits)

    eng_s = make_sched_engine(params, collect_logits=True)
    sched_s = eng_s.scheduler(kv_cache_dtype="int8", spec_tokens=4)
    spec_logits = sched_s.submit(prompt, max_new_tokens=6).result_logits()
    np.testing.assert_array_equal(cold_logits, spec_logits)


def test_kv_cache_dtype_validation(baseline):
    params, _ = baseline
    eng = make_sched_engine(params)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        eng.scheduler(kv_cache_dtype="int3")


def test_spec_telemetry_counters(tmp_path, baseline):
    """Speculation and KV-bytes metrics reach the PR-1 sink (and therefore
    the gateway's /v1/metrics snapshot): spec_* counters, acceptance-rate
    gauge, and the kv-bytes gauges."""
    params, _ = baseline
    eng = make_sched_engine(params, num_slots=2,
                            telemetry={"enabled": True, "output_path": str(tmp_path)})
    sched = eng.scheduler(spec_tokens=4, kv_cache_dtype="int8")
    for h in [sched.submit(PROMPTS[i % 2], max_new_tokens=8) for i in range(3)]:
        h.result()
    tel = eng.telemetry
    assert tel.counter_total("serving/spec_steps") == sched.spec_steps > 0
    assert tel.counter_total("serving/spec_draft_tokens") == sched.spec_drafted
    assert tel.counter_total("serving/spec_accepted_tokens") == sched.spec_accepted
    tel.flush()
    text = (tmp_path / "telemetry.jsonl").read_text()
    for name in ("serving/spec_acceptance_rate", "serving/spec_tokens_per_step",
                 "serving/kv_bytes_per_token", "serving/kv_cache_capacity_bytes",
                 "serving/kv_bytes_live"):
        assert name in text, f"{name} missing from telemetry stream"


# ---------------------------------------------------------------------------
# A chunk sync's first forward over its live rows only (`_fused_fn`'s split)

SPLIT_SHAPE = dict(num_slots=8, prefill_chunk=64)  # over the threshold; (4, 16) is under it


# rides the fused int8 decode blocks (tag ``fused_block``, Pallas in interpret
# mode): cell 2's path, tiny-gpt2 at head size 64 so that its pool leaf is packed
FUSED_INT8 = "tiny-int8-fused"


def _program_tags(sched):
    return {k[0] for k in sched._compiled if k != "copy"}


def _split_engine(model, params=None, whole_block=False, fresh=False):
    """An (8, 64) scheduler of ``model`` (a preset, or ``FUSED_INT8``) that
    collects logits, with the scheduler module's shape rule as it is or
    (``whole_block``) answering no, so the same traffic runs through the
    whole-block programs. ``whole_block`` sets what a program's build reads,
    and the pair whose ``_compiled`` is compared is ``fresh``: both build
    their own programs; the others share."""
    from deepspeed_tpu.inference.scheduler import _split_pays
    from deepspeed_tpu.models import get_model
    fused = model == FUSED_INT8
    if fused:
        model = get_model("tiny-gpt2", head_dim=64)
    eng = engine((model, params), 8, 64, 4, fused, fresh=fresh or whole_block,
                 config={"dtype": "int8"} if fused else {}, collect_logits=True)
    sched = eng.scheduler()
    assert sched._fused_block == fused, sched._fused_block_reasons
    if whole_block:
        sched._splits_chunk = lambda key: False
    assert _split_pays(sched.cache.num_slots, sched.prefill_chunk, 1 if fused else 2)
    return eng, sched


def _mixed_traffic(sched, vocab):
    """A non-final chunk on an idle pool (the K = 1 program), its final chunk,
    then beside the rows that decode: a 65-token prompt (a non-final chunk and
    a final chunk of one token), a 112-token prompt and a short one (the tiny
    presets hold 128 positions). Returns each request's (tokens, logits)."""
    rng = np.random.default_rng(5)
    prompt = lambda n: [int(t) for t in rng.integers(3, vocab, n)]
    handles = [sched.submit(prompt(100), max_new_tokens=14)]
    sched.step()
    assert sched._prefill is not None and not sched.active  # chunk on an idle pool
    sched.step()
    handles += [sched.submit(prompt(n), max_new_tokens=8) for n in (65, 112, 7)]
    return [(h.result(), h.result_logits()) for h in handles]


@pytest.fixture(scope="module", params=["tiny", "tiny-mla-moe", FUSED_INT8])
def split_pair(request):
    """(model name, params, results of the mixed traffic through the split
    programs, the same through the whole-block programs, the split scheduler)."""
    model = request.param
    params = None
    if model == FUSED_INT8:  # the engine quantizes: hand every one the same floats
        from deepspeed_tpu.models import get_model
        params = jax.device_get(get_model("tiny-gpt2", head_dim=64).init_params(
            jax.random.key(3)))
    eng, sched = _split_engine(model, params, fresh=True)
    if params is None:
        params = jax.device_get(eng.params)
    vocab = eng.model_config.vocab_size
    split = _mixed_traffic(sched, vocab)
    _, block_sched = _split_engine(model, params, whole_block=True)
    block = _mixed_traffic(block_sched, vocab)
    return model, params, split, block, sched, block_sched


def test_split_chunk_sync_matches_whole_block(split_pair):
    """(a) The split programs' tokens equal the whole-block programs' and
    their logits agree within what the (slots, 1) and (slots, C) programs
    already differ by; both ran the same program keys, and the split
    scheduler's chunk programs say they split."""
    model, _, split, block, sched, block_sched = split_pair
    for (ta, la), (tb, lb) in zip(split, block):
        assert list(ta) == list(tb)
        # (the fused kernels' rows are independent: their logits come out equal)
        np.testing.assert_allclose(la, lb, rtol=0, atol=1e-6)
    assert set(sched._compiled) == set(block_sched._compiled)
    assert _program_tags(sched) == {"fused_block" if model == FUSED_INT8 else "fused"}
    chunk_keys = [k for k in sched._compiled if k != "copy" and k[3] == 64]
    assert {k[4] for k in chunk_keys} == {1, 4}
    assert all(sched._splits_chunk(k) for k in chunk_keys)
    assert not any(sched._splits_chunk(k) for k in sched._compiled if k not in chunk_keys)
    sched.cache.check_invariants()


def test_split_decode_rows_bit_identical_to_decode_program(split_pair):
    """(b) A row that decodes while chunks ride its syncs gets, bit for bit,
    the logits the decode program gives it on a pool with no prefill: its
    column is the decode program's own first forward."""
    model, params, _, _, _, _ = split_pair
    vocab = 256
    rng = np.random.default_rng(9)
    short = [int(t) for t in rng.integers(3, vocab, 9)]
    long_prompt = [int(t) for t in rng.integers(3, vocab, 120)]
    _, alone = _split_engine(model, params)
    want = alone.submit(short, max_new_tokens=14)
    want_tokens, want_logits = want.result(), want.result_logits()
    _, sched = _split_engine(model, params)
    got = sched.submit(short, max_new_tokens=14)
    sched.step()  # one chunk prefills it and its first K tokens
    rider = sched.submit(long_prompt, max_new_tokens=2)
    chunk_syncs = 0
    while not got.done:
        sched.step()
        fl = sched._flight  # the sync this step launched, if any: does it carry a chunk?
        chunk_syncs += fl is not None and fl.chunk is not None
    assert chunk_syncs == 2
    assert list(got.result()) == list(want_tokens)
    np.testing.assert_array_equal(got.result_logits(), want_logits)


def test_split_leaves_other_slots_byte_stable(split_pair):
    """(c) Span-0 rows and a retained prefix's slot are byte-stable across
    split syncs, and a radix hit after them returns the same tokens."""
    from deepspeed_tpu.inference.kv_cache import slot_slice
    model, params, _, _, _, _ = split_pair
    rng = np.random.default_rng(11)
    kept = [int(t) for t in rng.integers(3, 256, 70)]
    _, sched = _split_engine(model, params)
    first = sched.submit(kept, max_new_tokens=6)
    tokens = first.result()
    slot = next(iter(sched.radix.match(kept)[1:]))
    rows = lambda s: [np.asarray(x) for x in
                      jax.tree_util.tree_leaves(slot_slice(sched.cache.pool, s))]
    before, idle_before = rows(slot), rows(7)
    other = sched.submit([int(t) for t in rng.integers(3, 256, 110)], max_new_tokens=6)
    other.result()
    assert other._req.slot != slot
    for a, b in zip(before + idle_before, rows(slot) + rows(7)):
        np.testing.assert_array_equal(a, b)
    hits = sched.radix.hits
    again = sched.submit(kept, max_new_tokens=6)
    assert list(again.result()) == list(tokens) and sched.radix.hits == hits + 1
    sched.cache.check_invariants()


def _warm_unrun(sched):
    """``warm_programs(ladder=False)`` with its dispatches left out: every
    program it reaches is built (its key, its split decision) and none is
    traced or run. For the scheduler whose KEYS are compared with those of one
    that warmed for real."""
    sched._call_step = lambda fn, args, lora, spans, lens: (sched.cache.pool, )
    sched.warm_programs(ladder=False)


def _fused_int8_sched(shape=SPLIT_SHAPE, **cfg):
    """A scheduler on the fused int8 decode blocks (tag ``fused_block``)."""
    sched = make_engine(dtype="int8", kernel_inject=True,
                        continuous_batching=dict(enabled=True, **shape), **cfg).scheduler()
    assert sched._fused_block, sched._fused_block_reasons
    return sched


@pytest.mark.parametrize("case", ["fused_block", "spec_block", "ext", "seqp", "lora", "verify",
                                  "tp2", "under_threshold", "fused_block_under_threshold",
                                  "fused_block_int8_ridge", "warmed_count",
                                  "fused_block_warmed_count"])
def test_programs_that_keep_the_whole_block(baseline, case):
    """(e) Only the plain per-projection program and the fused int8 decode
    blocks, on one device and over the shape rule for the bytes of their
    weights, split; every other says it keeps the block, and a warmed
    scheduler holds the programs it held."""
    params, _ = baseline
    cb = dict(enabled=True, **SPLIT_SHAPE)
    plain = ("fused", False, False, 64, 4)
    block = ("fused_block", False, False, 64, 4)
    if case == "tp2":
        sched = make_engine(params=params, continuous_batching=cb,
                            tensor_parallel={"tp_size": 2}).scheduler()
        assert sched._shard_deg == 2 and not sched._splits_chunk(plain)
        return
    if case == "under_threshold":
        sched = make_engine(params=params, continuous_batching=dict(
            enabled=True, num_slots=4, prefill_chunk=16)).scheduler()
        assert not sched._splits_chunk(("fused", False, False, 16, 4))
        return
    if case == "fused_block_under_threshold":  # (4, 16) and a pool of one slot keep the block
        sched = _fused_int8_sched(dict(num_slots=4, prefill_chunk=16))
        assert not sched._splits_chunk(("fused_block", False, False, 16, 4))
        assert not _fused_int8_sched(dict(num_slots=1))._splits_chunk(block)
        return
    if case == "fused_block_int8_ridge":  # 256 rows of block: over int8's ridge, under bf16's
        sched = _fused_int8_sched(dict(num_slots=4))
        assert sched._splits_chunk(block) and not sched._splits_chunk(plain)
        return
    if case.startswith("fused_block"):
        sched = _fused_int8_sched()
        assert sched._splits_chunk(block) and sched._splits_chunk(("fused_block", True, True, 64, 1))
        assert not sched._splits_chunk(block[:3] + (1, 4))
        if case == "fused_block_warmed_count":
            sched.warm_programs(ladder=False)
            whole = _fused_int8_sched()
            whole._splits_chunk = lambda key: False
            _warm_unrun(whole)
            assert set(sched._compiled) == set(whole._compiled)
            assert sched.compiled_program_count() == whole.compiled_program_count() == 7
            assert _program_tags(sched) == {"fused_block"}
        return
    sched = make_engine(params=params, continuous_batching=cb).scheduler()
    assert sched._splits_chunk(plain) and sched._splits_chunk(("fused", True, True, 64, 1))
    if case == "warmed_count":
        sched.warm_programs(ladder=False)
        block = make_engine(params=params, continuous_batching=cb).scheduler()
        block._splits_chunk = lambda key: False
        _warm_unrun(block)
        assert set(sched._compiled) == set(block._compiled)
        assert sched.compiled_program_count() == block.compiled_program_count() == 7
        return
    key = {"spec_block": ("spec_block", False, False, 64),
           "ext": ("fused_ext", False, False, 64, 4),
           "seqp": ("fused_seqp", False, False, 64, 4),
           "lora": plain + ("lora", ),
           "verify": ("spec", False, False, 64)}[case]
    assert not sched._splits_chunk(key)
    assert not sched._splits_chunk(plain[:3] + (1, 4))  # the decode program is one column already


@pytest.mark.parametrize("n, c, bf16, int8", [
    (64, 256, True, True), (24, 64, True, True), (8, 64, True, True),   # both ridges split
    (4, 64, False, True), (8, 32, False, True), (2, 128, False, True),  # 256 rows: int8 alone
    (4, 16, False, False), (3, 64, False, False), (1, 512, False, False)])
def test_split_pays_at_both_ridges(n, c, bf16, int8):
    """The shape rule at a bf16 weight's ridge (240 rows) and an int8 weight's
    (120): they differ for blocks of 240 to 480 rows."""
    from deepspeed_tpu.inference.scheduler import _split_pays
    assert _split_pays(n, c) == _split_pays(n, c, 2) == bf16
    assert _split_pays(n, c, 1) == int8


@pytest.mark.parametrize("case", ["whole_block", "split", "sink_off"])
def test_attention_key_counters(tmp_path, case):
    """``serving/attn_keys_live`` and ``serving/attn_keys_walked`` against a
    hand count: three slots of 128 rows in 32-key blocks, 2 attention layers,
    K = 2, chunk 16, the serial order. Slot 0 takes a 16-token prompt (one
    final chunk) and decodes 9 tokens, slot 1 a 20-token prompt (16 + 4) and
    3 tokens, slot 2 stays idle. A (3, 16) forward's span reaches 15 keys
    past a row's column: row 0 at 18 and 20 keys walks two blocks there and
    one as a column of the split forward. Nothing is counted with the sink off."""
    tel = {"enabled": True, "output_path": str(tmp_path)} if case != "sink_off" else {}
    eng = make_engine(kernel_inject=True, decode_block_kv=32, max_out_tokens=128,
                      continuous_batching=dict(enabled=True, num_slots=3, steps_per_sync=2,
                                               prefill_chunk=16), telemetry=tel)
    sched = eng.scheduler()
    assert sched.max_len == 128 and eng.module.cfg.num_layers == 2
    sched._splits_chunk = lambda key: case == "split"
    sched._lands_first = lambda: True
    counted = []
    if case == "sink_off":
        assert sched._work is None  # no observer: required_work.py
    else:
        heard = sched._work.dispatched
        sched._work.dispatched = lambda key, split, spans, lens, chunk=None: (
            counted.append((lens, spans)), heard(key, split, spans, lens, chunk))
    sched.submit(list(range(3, 19)), max_new_tokens=9)
    sched.submit(list(range(40, 60)), max_new_tokens=3)  # no prefix of the first: nothing copied
    sched.drain()
    total = eng.telemetry.counter_total
    if case == "sink_off":
        assert not counted and sched.syncs_ahead + sched.syncs_serial == 5
        assert not total("serving/attn_keys_live") and not total("serving/attn_keys_walked")
        return
    assert [(list(lens), list(spans)) for lens, spans, *_ in counted] == [
        ([0, 0, 0], [16, 0, 0]), ([17, 0, 0], [1, 16, 0]), ([19, 16, 0], [1, 4, 0]),
        ([21, 21, 0], [1, 1, 0]), ([23, 0, 0], [1, 0, 0])]
    # a layer, by sync, first forward then substep: the final chunk alone;
    # the column beside a non-final chunk (which rides the substep on a
    # garbage token: a pool of rows); the column beside the final chunk; two
    # columns; one
    live = (16 + 17) + (18 + 16 + 19 + 17) + (20 + 20 + 21 + 21) + (22 + 22 + 23 + 23) + (24 + 25)
    blocks = {"whole_block": (1 + 1) + (2 + 1 + 1 + 1) + (2 + 1 + 1 + 1) + 4 + 2,
              "split": (1 + 1) + (1 + 1 + 1 + 1) + (1 + 1 + 1 + 1) + 4 + 2}[case]
    assert total("serving/attn_keys_live") == 2 * live == 648
    assert total("serving/attn_keys_walked") == 2 * 32 * blocks
    eng.telemetry.close()


@pytest.mark.parametrize("split", [True, False, "fused_block"])
def test_step_row_counters(tmp_path, baseline, split):
    """(f) ``serving/step_rows_run`` and ``_live`` over three syncs of an
    (8, 64) pool, K = 4: a 70-token prompt's non-final chunk on an idle pool
    (the K = 1 program), its final chunk of 6, one decode sync. The fused int8
    decode blocks split as the per-projection program does."""
    params, _ = baseline
    tel_cfg = {"enabled": True, "output_path": str(tmp_path)}
    if split == "fused_block":
        sched = _fused_int8_sched(telemetry=tel_cfg)
    else:
        sched = make_engine(params=params, continuous_batching=dict(enabled=True, **SPLIT_SHAPE),
                            telemetry=tel_cfg).scheduler()
    if not split:
        sched._splits_chunk = lambda key: False
    h = sched.submit(list(range(3, 73)), max_new_tokens=6)
    while not h.done:
        sched.step()
    assert sched.syncs_ahead + sched.syncs_serial == 3
    assert _program_tags(sched) == {"fused_block" if split == "fused_block" else "fused"}
    first = (8 + 64) if split else 8 * 64
    tel = sched.telemetry
    assert tel.counter_total("serving/step_rows_run") == first + (first + 3 * 8) + 4 * 8
    assert tel.counter_total("serving/step_rows_live") == 64 + (6 + 3) + 4
    tel.close()
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
