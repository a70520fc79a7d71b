"""Shared by the packed-pool serving tests: a head-size-64 engine, one
request's tokens and logits, and the patch that serves the same head size
from split leaves (readers recognise the geometry from the leaves, so the
split runs go through the very same code)."""

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

HD = 64
_RNG = np.random.default_rng(29)
LONG = _RNG.integers(0, 256, 100).astype(np.int32)
OTHER = _RNG.integers(0, 256, 70).astype(np.int32)
FILLERS = [_RNG.integers(0, 256, 40 + 7 * i).astype(np.int32) for i in range(4)]


def split_rule(monkeypatch):
    """Serve head size 64 from split leaves, as before the packed pool."""
    monkeypatch.setattr(tfm, "kv_packs", lambda head_size: False)


def fresh_process_state():
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)


def engine(model="tiny", params=None, roles=None, **cb):
    fresh_process_state()
    cb = dict({"enabled": True, "num_slots": 2, "prefill_chunk": 16}, **cb)
    if roles is not None:
        cb["disaggregation"] = {"enabled": True, "roles": roles, "migrate_min_tokens": 0}
    config = {"dtype": "float32", "max_out_tokens": 512, "continuous_batching": cb}
    if model == "tiny-gpt2":  # the fused int8 decode blocks (cell 2's path)
        config.update(dtype="int8", kernel_inject=True)
    return deepspeed_tpu.init_inference(get_model(model, head_dim=HD), config=config,
                                        params=params)


def ask(sched, prompt, sampled=False, n=8):
    kw = dict(do_sample=True, temperature=0.8, top_k=8, seed=1234) if sampled else dict(seed=7)
    h = sched.submit(prompt, max_new_tokens=n, collect_logits=True, **kw)
    return h.result().tolist(), h.result_logits()


def assert_same_runs(got, want):
    for (t_p, l_p), (t_s, l_s) in zip(got, want):
        assert t_p == t_s
        np.testing.assert_array_equal(l_p, l_s)
