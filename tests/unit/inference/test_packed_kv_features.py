"""The packed K/V pool (head size 64) under the serving features that
reach the cache their own way: extent chains, a lossy window, speculative
verify programs and a tensor-parallel pool, each against split leaves
(``kv_packs`` patched off) or against tp=1."""

from unittest import mock

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.scheduler import DecodeScheduler
from deepspeed_tpu.models import get_model

from ._packed_kv import HD, LONG, OTHER, ask, assert_same_runs, fresh_process_state, split_rule


def _feature_stream(feature):
    """Extent chains (a prompt over two 64-row slots, the extent write and
    the extent-walking kernels), a lossy window, or speculative verify
    programs, on the paged kernels at head size 64."""
    fresh_process_state()
    cb = {"enabled": True, "num_slots": 4, "collect_logits": True}
    kw = {"prefill_chunk": 16}
    if feature in ("extents", "lossy"):
        cb["long_context"] = {"allow_lossy_kv": feature == "lossy"}
        kw.update(max_len=32, max_extents=4)
    else:
        kw.update(spec_tokens=3)
    eng = deepspeed_tpu.init_inference(get_model("tiny", head_dim=HD), config={
        "dtype": "float32", "kernel_inject": True, "decode_block_kv": 32,
        "continuous_batching": cb})
    # a long-context scheduler warms all sixteen of its step programs as it is
    # built, with every span zero: nothing of a pool's geometry shows there, and
    # tracing them through the interpreter was 100 s of this test's 120. Built
    # cold, the traffic below compiles the four it reaches
    with mock.patch.object(DecodeScheduler, "warm_programs", lambda self, ladder=True: None):
        sched = eng.scheduler(**kw)
    prompt = [int(t) for t in np.resize(np.arange(3, 40), 100)]
    extra = {"kv_window": (4, 32)} if feature == "lossy" else {}
    # the chained row outlives the short one, so that every sync of either
    # walks extents: the (16, 1) and (16, 4) chunk programs and the (1, 4) decode
    n, n_long = (20, 20) if feature == "spec" else (8, 16)
    hs = [sched.submit(prompt, max_new_tokens=n_long, collect_logits=True, **extra),
          sched.submit(prompt[:30], max_new_tokens=n, collect_logits=True, temperature=0.8,
                       top_k=20, seed=7)]
    runs = [(h.result().tolist(), h.result_logits()) for h in hs]
    if feature == "spec":
        assert sched.spec_accepted > 0
    else:
        assert sched.cache.max_extents == 2 and not sched.cache.chain
    return sched, runs


@pytest.mark.parametrize("feature", ["extents", "lossy", "spec"])
def test_long_context_and_speculation_packed_equal_split(monkeypatch, feature):
    packed, got = _feature_stream(feature)
    with monkeypatch.context() as m:
        split_rule(m)
        split, want = _feature_stream(feature)
    assert (packed.kv_pool_geometry, split.kv_pool_geometry) == ("packed", "split")
    assert packed.kv_commit_programs == split.kv_commit_programs
    assert_same_runs(got, want)


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
@pytest.mark.parametrize("inject", [False, True], ids=["xla", "kernels"])
def test_tp2_packed_pool_bit_identical_to_tp1(inject, kv_dtype):
    """A tensor-parallel packed pool: the leaf shards over its head axis as
    the split leaves do, the paged kernels run under ``shard_map`` on the
    local heads' packed rows (the span commit stays the scatter there), and
    tp=2 gives tp=1's tokens and logits."""
    def run(tp, params=None):
        fresh_process_state()
        eng = deepspeed_tpu.init_inference(get_model("tiny", head_dim=HD), params=params, config={
            "dtype": "float32", "kernel_inject": inject, "tensor_parallel": {"tp_size": tp},
            "continuous_batching": {"enabled": True, "num_slots": 3, "prefill_chunk": 16,
                                    "kv_cache_dtype": kv_dtype}})
        sched = eng.scheduler()
        assert sched.kv_pool_geometry == "packed" and sched.tp_size == tp
        runs = [ask(sched, p, n=6) for p in (LONG[:40], OTHER[:7], LONG[:40])]
        return jax.device_get(eng.params), runs, sched

    params, want, _ = run(1)
    _, got, sched = run(2, params)
    leaf = jax.tree_util.tree_leaves(sched.cache.pool)[0]
    assert leaf.shape[-1] == 2 * HD and leaf.sharding.spec[leaf.ndim - 3] == "tensor"
    assert_same_runs(got, want)
