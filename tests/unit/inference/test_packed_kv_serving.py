"""A scheduler over the packed K/V pool (head size 64) against one over
split leaves (``kv_packs`` patched off): tokens AND logits of every request
through slot reuse, a radix hit, ``copy_slot``, demotion and a tier
restore, a migration between replicas, and the fused int8 decode blocks
with the static ``generate()`` loop."""

import jax
import numpy as np
import pytest

from ._packed_kv import FILLERS, LONG, OTHER, ask, assert_same_runs, engine, split_rule


def _tier_stream(kv_dtype):
    """Cold, radix hit (``copy_slot`` on a two-slot pool), slot reuse under
    fillers that evict and demote both prefixes, then a host-tier restore."""
    eng = engine(kv_cache_dtype=kv_dtype, hierarchical_kv={"enabled": True})
    sched = eng.scheduler(num_slots=2, prefill_chunk=16)
    runs = [ask(sched, LONG), ask(sched, OTHER, sampled=True),      # cold
            ask(sched, LONG), ask(sched, OTHER, sampled=True)]      # radix hit, restore
    runs += [ask(sched, f, n=4) for f in FILLERS]                     # reuse, demote
    r0 = sched.kv_tier.restores
    runs += [ask(sched, LONG), ask(sched, OTHER, sampled=True)]      # restored
    # (on two slots the second repeat already finds its donor demoted)
    assert sched.radix.hits >= 1 and sched.kv_tier.restores >= r0 + 2
    sched.radix.check_invariants()
    sched.cache.check_invariants()
    return sched, runs


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_scheduler_packed_equals_split(monkeypatch, kv_dtype):
    """A scheduler over the packed pool against one over split leaves (the
    rule patched off): tokens AND logits of every request of a stream with
    slot reuse, radix hits against cold, the ``copy_slot`` program, demotion
    and a tier restore; cold, hit and restored agree among themselves; the
    pool costs the same bytes a token."""
    packed, got = _tier_stream(kv_dtype)
    assert packed.kv_pool_geometry == "packed"
    with monkeypatch.context() as m:
        split_rule(m)
        split, want = _tier_stream(kv_dtype)
    assert split.kv_pool_geometry == "split"
    assert packed.cache.bytes_per_token() == split.cache.bytes_per_token()
    assert len(jax.tree_util.tree_leaves(packed.cache.pool)) < len(
        jax.tree_util.tree_leaves(split.cache.pool))
    assert_same_runs(got, want)
    for cold, hit, restored in ((got[0], got[2], got[8]), (got[1], got[3], got[9])):
        assert cold[0] == hit[0] == restored[0]
        np.testing.assert_array_equal(cold[1], hit[1])
        np.testing.assert_array_equal(cold[1], restored[1])


def _migrated(kv_dtype):
    from deepspeed_tpu.serving import ReplicaSet
    rs = ReplicaSet.build(engine(slots=4, kv_cache_dtype=kv_dtype,
                                  roles=["prefill", "decode"]), 2)
    handles = [rs.dispatch(p, max_new_tokens=10, collect_logits=True, seed=7)[1]
               for p in (LONG, LONG, OTHER)]
    rs.drain_all_work()
    assert rs.primary.migrations_out == 3 and rs.replicas[1].scheduler.migrations_in == 3
    return [(h.result().tolist(), h.result_logits()) for h in handles]


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_migration_packed_equals_split(monkeypatch, kv_dtype):
    """Prefill on one replica, decode on another: the slot's packed rows
    cross the host store and decode to the split pool's tokens and logits."""
    got = _migrated(kv_dtype)
    with monkeypatch.context() as m:
        split_rule(m)
        want = _migrated(kv_dtype)
    assert_same_runs(got, want)


def _fused_stream():
    eng = engine("tiny-gpt2", slots=3)
    sched = eng.scheduler()
    assert sched._fused_block, sched._fused_block_reasons
    runs = [ask(sched, p, n=6) for p in (LONG[:40], OTHER[:7], LONG[:23], LONG[:40])]
    gen = eng.generate([LONG[:40].tolist(), OTHER[:7].tolist()], max_new_tokens=6)
    return sched, runs, [g.tolist() for g in gen]


def test_fused_int8_path_packed_equals_split(monkeypatch):
    """Cell 2's path at head size 64: the fused decode blocks with the
    in-place commit and the paged kernels over the packed pool, and the
    static ``generate()`` loop (``fused_decode_block``), against split
    leaves; every commit is the kernel's."""
    packed, got, gen = _fused_stream()
    with monkeypatch.context() as m:
        split_rule(m)
        split, want, gen_split = _fused_stream()
    assert (packed.kv_pool_geometry, split.kv_pool_geometry) == ("packed", "split")
    assert packed.kv_commit_programs["scatter"] == 0 and packed.kv_commit_programs["inplace"] > 0
    assert packed.kv_commit_programs == split.kv_commit_programs
    assert packed.compiled_program_count() == split.compiled_program_count()
    assert gen == gen_split and gen[0] == got[0][0]
    assert_same_runs(got, want)
