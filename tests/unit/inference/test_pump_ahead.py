"""The serving pump is one sync deep: each iteration launches sync N+1
before it lands sync N.

(a) token for token what a pump that lands first gives, on every pool
geometry the benchmark serves; (b) the prefill lane advances at launch;
(c) the pump is serial where it can see that the next sync needs the last
one's results on the host; (d) whatever reads or moves landed state lands
the sync in flight first.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unittest import mock

import deepspeed_tpu
from deepspeed_tpu.inference import scheduler as sched_mod
from deepspeed_tpu.models import get_model

from ._serving import engine
from ._serving import fresh_process_state as _fresh

K = 4
CHUNK = 16


def _tiny():
    return get_model("tiny", dtype=jnp.float32), None, {}


def _packed():
    # head size 64: K beside V in one 128-lane leaf a layer (cell 2's pool)
    return get_model("tiny", dtype=jnp.float32, head_dim=64), None, {}


def _mla_moe():
    # latent rows and routed experts (cell 4's pool); collects logits and
    # every router's choice through the pump the window runs
    return get_model("tiny-mla-moe", dtype=jnp.float32), None, {"collect_logits": True}


def _hybrid():
    from chipbench.jobs.serve_hybrid import hybrid_params
    model = get_model("tiny-hybrid", dtype=jnp.float32)
    return model, hybrid_params(model, 7, jnp.dtype("float32")), {}


def _sambay():
    from chipbench.jobs.serve_sambay import sambay_params
    model = get_model("tiny-sambay", dtype=jnp.float32)
    return model, sambay_params(model, 7, jnp.dtype("float32")), {}


MODELS = {"tiny": _tiny, "packed-hd64": _packed, "latent-moe": _mla_moe,
          "state-pool": _hybrid, "ring-shared": _sambay}


@pytest.fixture(scope="module", params=list(MODELS))
def served(request):
    """(name, model, params, submit keywords) with the params drawn once."""
    _fresh()
    model, params, kw = MODELS[request.param]()
    if params is None:
        params = jax.jit(model.init_params)(jax.random.key(5))  # (jitted: seconds less a worker)
    return request.param, model, jax.device_get(params), kw


def _scheduler(model, params, serial=False, slots=3, fresh=False):
    """The schedulers of one model share their step programs, as the replicas
    of a fleet do (``_serving.engine``): each is built once a model and not
    once a scheduler. The pumps forced to land first share among themselves
    only."""
    sched = engine((model, params), slots, CHUNK, K, fresh=fresh,
                   also="lands first" if serial else None).scheduler()
    if serial:  # the pump the parent ran: every sync lands before the next is launched
        sched._lands_first = lambda: True
    return sched


def _requests(vocab):
    """Greedy and sampled, prompts of one to three chunks, mixed budgets,
    more of them than slots."""
    rng = np.random.default_rng(17)
    shapes = [(9, 11, False), (20, 6, True), (40, 14, True), (33, 5, False), (12, 9, True),
              (47, 13, False), (5, 10, True)]
    reqs = []
    for i, (n, budget, sampled) in enumerate(shapes):
        kw = {"max_new_tokens": budget}
        if sampled:
            kw.update(do_sample=True, temperature=0.9, top_k=12, top_p=0.95, seed=40 + i)
        reqs.append(([int(t) for t in rng.integers(3, vocab, n)], kw))
    return reqs


def _run(sched, reqs, **submit_kw):
    handles = [sched.submit(p, **kw, **submit_kw) for p, kw in reqs]
    sched.drain()
    return handles


def _mid_sync_eos(tokens):
    """An index j into a request's stream whose token first appears there
    and is not the last of its sync (the final chunk's sync delivers tokens
    0..K-1, the next K..2K-1), past the first sync: an EOS on it ends the
    request inside a sync the pump has already launched the successor of."""
    for j in range(K, len(tokens) - 1):
        # ... and with budget left past that sync, or the row is known to end in it
        if j % K != K - 1 and (j // K + 1) * K < len(tokens) and tokens[j] not in tokens[:j]:
            return j
    return None


def test_ahead_pump_is_token_for_token_the_serial_pump(served):
    """(a) Same requests through the one-deep pump and through a pump forced
    to land first: the same tokens, for every request, with a request ended
    by an EOS in mid-sync among them (its slot's next tenant included)."""
    name, model, params, submit_kw = served
    vocab = model.cfg.vocab_size
    reqs = _requests(vocab)
    # a first serial pass finds where an EOS can fire in mid-sync
    plain = [h.result().tolist()
             for h in _run(_scheduler(model, params, serial=True), reqs)]
    picked = [(i, _mid_sync_eos(toks)) for i, toks in enumerate(plain)
              if reqs[i][1].get("do_sample") and _mid_sync_eos(toks) is not None]
    assert picked, "no sampled stream has a fresh token in mid-sync"
    i, j = picked[0]
    reqs[i] = (reqs[i][0], dict(reqs[i][1], eos_token_id=plain[i][j]))

    serial = _scheduler(model, params, serial=True)
    ahead = _scheduler(model, params)
    want = _run(serial, reqs, **submit_kw)
    got = _run(ahead, reqs, **submit_kw)
    assert serial.syncs_ahead == 0 and serial.ahead_rows_discarded == 0
    assert ahead.syncs_ahead > ahead.syncs_serial >= 1
    for n, (a, b) in enumerate(zip(got, want)):
        assert a.result().tolist() == b.result().tolist(), f"request {n} of {name}"
    assert len(got[i].result()) == j + 1 < reqs[i][1]["max_new_tokens"]
    # the EOS row rode the sync launched before its end was known
    assert ahead.ahead_rows_discarded > 0
    if submit_kw.get("collect_logits"):
        for a, b in zip(got, want):
            np.testing.assert_allclose(a.result_logits(), b.result_logits(), rtol=0, atol=1e-5)
            np.testing.assert_array_equal(a.result_choice(), b.result_choice())
    for s in (serial, ahead):
        assert s._flight is None and not s.active and s.cache.active_slots == 0
        assert all(h._req.inflight == 0 for h in got + want)
        s.cache.check_invariants()


def test_warm_programs_warms_the_carried_token_merge(served):
    """The carried token is merged OUTSIDE the step programs, by a program of
    its own at each ids width: after ``warm_programs`` the one-deep pump's
    traffic, greedy and sampled, compiles nothing (a compile inside a
    benchmark window makes the run not ``correct``)."""
    _, model, params, _ = served
    sched = _scheduler(model, params, fresh=True)  # what it compiles is what is counted
    sched.warm_programs()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    programs = sched.compiled_program_count()
    handles = _run(sched, _requests(model.cfg.vocab_size))
    assert sched.syncs_ahead > 0 and all(h.done for h in handles)
    assert not compiles and sched.compiled_program_count() == programs


def test_merge_carried_touches_flagged_rows_only():
    ids = np.array([[7, 8, 9], [sched_mod._CARRIED, 0, 0], [5, 0, 0], [sched_mod._CARRIED, 4, 4]],
                   np.int32)
    toks = np.array([[1, 2, 3, 4], [11, 12, 13, 14]], np.int32)
    out = np.asarray(sched_mod._merge_carried(jnp.asarray(ids), jnp.asarray(toks)))
    assert out.tolist() == [[7, 8, 9], [12, 0, 0], [5, 0, 0], [14, 4, 4]]


def test_prefill_lane_advances_at_launch():
    """(b) Two queued two-chunk prompts occupy four consecutive syncs, none
    empty: a final chunk frees the lane when it is launched, not a sync later
    when it lands."""
    model, _, _ = _tiny()
    sched = _scheduler(model, jax.jit(model.init_params)(jax.random.key(5)))
    rng = np.random.default_rng(3)
    a, b = ([int(t) for t in rng.integers(3, 256, 2 * CHUNK)] for _ in range(2))
    ha, hb = sched.submit(a, max_new_tokens=12), sched.submit(b, max_new_tokens=12)
    chunks = []
    for _ in range(4):
        sched.step()
        req, pos, take, final = sched._flight.chunk
        chunks.append((req.rid, pos, take, final))
    assert chunks == [(ha._req.rid, 0, CHUNK, False), (ha._req.rid, CHUNK, CHUNK, True),
                      (hb._req.rid, 0, CHUNK, False), (hb._req.rid, CHUNK, CHUNK, True)]
    # the bookkeeping of the first token stayed at the landing
    assert ha._req.first_token_ts is not None and hb._req.first_token_ts is None
    assert len(ha._req.out) == 2 * K and not hb._req.out and hb._req.inflight == K
    sched.drain()
    assert len(ha.result()) == len(hb.result()) == 12


def _drafter_sched():
    model, _, _ = _tiny()
    _fresh()
    eng = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "continuous_batching": {"enabled": True, "num_slots": 3}})
    return eng.scheduler(spec_tokens=3), [([5, 6, 7, 8, 9] * 3, {"max_new_tokens": 12}),
                                          ([10, 11, 12], {"max_new_tokens": 9})]


# an offload or long-context scheduler warms every step program its ladder can
# reach as it is built, with every span zero: nothing of the pump's order shows
# there. Built cold, the traffic below compiles the few it reaches
_COLD = mock.patch.object(sched_mod.DecodeScheduler, "warm_programs",
                          lambda self, ladder=True: None)


def _offload_sched():
    from .test_moe_decode import OFFLOAD_REQS, make_engine
    with _COLD:
        return make_engine(1, offload=2).scheduler(), OFFLOAD_REQS


def _paged_sched():
    from .test_long_context import LPROMPT, make_long_engine
    eng = make_long_engine(hierarchical_kv={"enabled": True, "host_capacity_mb": 64})
    with _COLD:
        return (eng.scheduler(max_len=64, prefill_chunk=16, max_extents=4),
                [(LPROMPT, {"max_new_tokens": 24})])


@pytest.mark.parametrize("build", [_drafter_sched, _offload_sched, _paged_sched],
                         ids=["drafter", "expert_offload", "parked_extent"])
def test_pump_is_serial_where_it_can_see_it_must_be(build):
    """(c) A drafter reads the accepted tokens, offload replays on the
    routing counts, a chained row is paged by its landed length: with any of
    them every sync lands before the next is launched, and every step()
    leaves nothing in flight."""
    sched, reqs = build()
    handles = [sched.submit(p, **kw) for p, kw in reqs]
    parked = False
    while not all(h.done for h in handles):
        sched.step()
        assert sched._flight is None
        if sched.cache.chain and not parked:
            slot = next(iter(sched.cache.chain))
            parked = bool(slot in sched.active and sched.demote_cold_extents(slot))
    assert sched.syncs_ahead == 0 and sched.syncs_serial > 0
    if build is _paged_sched:
        assert parked and sched.longctx_restores >= 1
    assert all(len(h.result()) == kw["max_new_tokens"] for h, (_, kw) in zip(handles, reqs))


def _in_flight_sched(n=2, budget=24, **submit_kw):
    """A scheduler with ``n`` rows decoding and a sync in flight."""
    model, _, _ = _tiny()
    sched = _scheduler(model, jax.jit(model.init_params)(jax.random.key(5)))
    rng = np.random.default_rng(8)
    handles = [sched.submit([int(t) for t in rng.integers(3, 256, 10 + i)], max_new_tokens=budget,
                            **submit_kw) for i in range(n)]
    for _ in range(n + 1):
        sched.step()
    assert sched.in_flight and sched.syncs_ahead > 0
    return sched, handles


@pytest.mark.parametrize("op", ["pause", "flush", "drain", "swap_weights", "cancel"])
def test_nothing_is_left_unlanded(op):
    """(d) pause, flush, swap_weights, drain and a cancellation with a sync
    in flight leave nothing unlanded, and the tokens in flight reach their
    requests."""
    sched, handles = _in_flight_sched()
    before = [len(h._req.out) for h in handles]
    if op == "pause":
        sched.pause()
        assert not sched.in_flight
        assert all(len(h._req.out) == n + K for h, n in zip(handles, before))
        sched.resume()
    elif op == "flush":
        sched.pause()
        sched.flush()
        assert not sched.in_flight and not sched.active
        assert all(h.done and len(h._req.out) == 24 for h in handles)
    elif op == "drain":
        sched.drain()
        assert not sched.in_flight and all(len(h.result()) == 24 for h in handles)
    elif op == "swap_weights":
        # an EOS in mid-sync: when its sync lands the request is over, the
        # pool is empty, and the sync launched behind it is still out
        sampled = dict(do_sample=True, temperature=0.9, top_k=12, seed=3)
        plain, (h, ) = _in_flight_sched(n=1, **sampled)
        plain.drain()
        j = _mid_sync_eos(h.result().tolist())
        sched, (h, ) = _in_flight_sched(n=1, eos_token_id=int(h.result()[j]), **sampled)
        while not h.done:
            sched.step()
        assert sched.in_flight and not sched.active and len(h.result()) == j + 1
        sched.swap_weights(sched.engine.params, version=2)
        assert not sched.in_flight and sched.ahead_rows_discarded == 1
        handles = [h]
    else:
        handles[0].cancel()
        sched.step()  # what was computed for the row lands before it is reaped
        assert handles[0].done and len(handles[0]._req.out) == before[0] + K
        sched.drain()
        assert not sched.in_flight and len(handles[1].result()) == 24
    sched.drain()
    assert all(h._req.inflight == 0 for h in handles)
    assert sched.cache.active_slots == 0
    sched.cache.check_invariants()


def test_replica_is_not_idle_with_a_sync_in_flight():
    """(d) ``Replica.idle()`` counts a launched sync as work: a pump that
    parks on idle() would otherwise leave the last tokens on the device."""
    from deepspeed_tpu.serving.replica import Replica
    sched, handles = _in_flight_sched(n=1, budget=2 * K)
    rep = Replica(0, sched)
    assert len(handles[0]._req.out) == handles[0]._req.inflight == K
    # every token is launched; the last K are not landed yet
    assert sched.in_flight and not handles[0].done and not rep.idle()
    while not rep.idle():
        rep.step()
    assert handles[0].done and not sched.in_flight and len(handles[0].result()) == 2 * K
