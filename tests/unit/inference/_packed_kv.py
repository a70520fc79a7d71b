"""Shared by the packed-pool serving tests: a head-size-64 engine, one
request's tokens and logits, and the patch that serves the same head size
from split leaves (readers recognise the geometry from the leaves, so the
split runs go through the very same code)."""

import numpy as np

from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

from ._serving import engine as _engine
from ._serving import fresh_process_state  # noqa: F401 (the tests' own import)

HD = 64
_RNG = np.random.default_rng(29)
LONG = _RNG.integers(0, 256, 100).astype(np.int32)
OTHER = _RNG.integers(0, 256, 70).astype(np.int32)
FILLERS = [_RNG.integers(0, 256, 40 + 7 * i).astype(np.int32) for i in range(4)]


def split_rule(monkeypatch):
    """Serve head size 64 from split leaves, as before the packed pool."""
    monkeypatch.setattr(tfm, "kv_packs", lambda head_size: False)


def engine(model="tiny", params=None, roles=None, slots=2, chunk=16, **cb):
    """``tiny-gpt2``: the fused int8 decode blocks (cell 2's path). The callers
    compare a packed pool with one served under :func:`split_rule`, or tallies
    of what was built: programs of its own (``_serving.engine``'s ``fresh``)."""
    if roles is not None:
        cb["disaggregation"] = {"enabled": True, "roles": roles, "migrate_min_tokens": 0}
    fused = model == "tiny-gpt2"
    return _engine((get_model(model, head_dim=HD), params), slots, chunk, 4, fused, fresh=True,
                   config={"max_out_tokens": 512, **({"dtype": "int8"} if fused else {})}, **cb)


def ask(sched, prompt, sampled=False, n=8):
    kw = dict(do_sample=True, temperature=0.8, top_k=8, seed=1234) if sampled else dict(seed=7)
    h = sched.submit(prompt, max_new_tokens=n, collect_logits=True, **kw)
    return h.result().tolist(), h.result_logits()


def assert_same_runs(got, want):
    for (t_p, l_p), (t_s, l_s) in zip(got, want):
        assert t_p == t_s
        np.testing.assert_array_equal(l_p, l_s)
