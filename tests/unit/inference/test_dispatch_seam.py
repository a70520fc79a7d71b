"""The serving pump's one dispatch seam (``DecodeScheduler._assemble`` and
``_step_args``): whatever a launch is (a decode sync, a chunk sync, a back-off
group, a speculative verify, the device drafter's two, a warm-up), its step
program gets the canonical operands, in the canonical order, at the dtypes,
shapes and shardings the program was built for; a warm-up's tuple and a served
one's of the same program agree in every operand. And the observer of required
work (``required_work.py``) exists only where the sink is on.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import required_work
from deepspeed_tpu.models import get_model

from ._serving import engine

N, K, C = 4, 2, 16
# the block's operands behind the pool, in order: ids is (slots, width), the rest (slots, )
CANON = (("ids", jnp.int32), ("lens", jnp.int32), ("spans", jnp.int32), ("seeds", jnp.uint32),
         ("steps", jnp.int32), ("flags", jnp.bool_), ("temps", jnp.float32),
         ("topks", jnp.int32), ("topps", jnp.float32))
# kind -> (preset, scheduler arguments, the leading tag of its program's key)
KINDS = {
    "decode": ("tiny", {}, "fused"),
    "chunk": ("tiny", {}, "fused"),
    "chunk_final": ("tiny", {}, "fused"),
    "backoff": ("tiny", {}, "fused"),
    "verify": ("tiny", {"spec_tokens": 3}, "spec"),
    "chunk_state": ("tiny-hybrid", {}, "fused"),
    "draft_decode": ("tiny-exaone-moe", {"spec_tokens": 1, "spec_draft": "module"}, "draft"),
    "draft_chunk": ("tiny-exaone-moe", {"spec_tokens": 1, "spec_draft": "module"}, "draft"),
    "warm": ("tiny", {"spec_tokens": 3}, None),
}


def _sched(preset, telemetry=None, counts=True, **kw):
    """A case that counts what its scheduler built (``counts``) builds programs
    of its own; the others are all set the same way (``_record``, ``_serve``)
    and share among themselves."""
    model = get_model(preset, dtype=jnp.float32) if preset != "tiny" else "tiny"
    return engine((model, None), N, C, K, fresh=counts, also="heard, lands first",
                  config={"telemetry": telemetry or {}}).scheduler(**kw)


def _record(sched):
    """Every dispatch from here on as ``(key, chunk, operands behind the
    pool)``, each operand as ``(shape, dtype, sharding, committed)``. While
    ``sched.hears_only`` is set the program is handed its operands and not run
    (a warm-up: the seam's work is the tuple; the pool comes back as it went),
    so a case traces the programs its traffic reaches and no others."""
    seen = []
    dispatch = sched._dispatch

    def heard(fn, call_args, spans, lens, chunk=None):
        key = next(k for k, f in sched._compiled.items() if f is fn)
        sig = [(x.shape, x.dtype, x.sharding, x.committed)
               for x in jax.tree_util.tree_leaves(call_args[2:])]
        seen.append((key, chunk, sig))
        assert (np.asarray(call_args[4]) == spans).all() or key[0] == "draft"
        if sched.hears_only:
            return (call_args[1], )
        return dispatch(fn, call_args, spans, lens, chunk)

    sched._dispatch, sched.hears_only = heard, False
    return seen


def _serve(sched, kind):
    """Traffic that launches ``kind``; the test of the dispatches that are it."""
    sched._lands_first = lambda: True
    sched.submit(list(range(3, 25)), max_new_tokens=6)   # 16 + 6: two chunks
    sched.submit([7, 8, 9] * 4, max_new_tokens=8)        # repeats: the host drafter drafts
    if kind == "backoff":
        while sched._prefill is not None or sched.queue:
            sched.step()
        sched.land_in_flight()
        sched._decode_backoff(sched._live_rows())
    sched.drain()
    return {"decode": lambda key, chunk: chunk is None and key[3:5] == (1, K),
            "chunk": lambda key, chunk: chunk is not None and not chunk[1],
            "chunk_final": lambda key, chunk: chunk is not None and chunk[1],
            "chunk_state": lambda key, chunk: chunk is not None and not chunk[1],
            "backoff": lambda key, chunk: chunk is None and key[3:5] == (1, 1),
            "verify": lambda key, chunk: key[0] == "spec",
            "draft_decode": lambda key, chunk: chunk is None,
            "draft_chunk": lambda key, chunk: chunk is not None}[kind]


def _canonical(sched, key, sig, chunk):
    """The operands a program under ``key`` takes behind the pool: the nine
    of the block, then a state pool's substep spans (the plain programs) or
    the chunk's four integers (the drafter's chunk program)."""
    width = key[3]
    want = [((N, width) if name == "ids" else (N, ), jnp.dtype(t)) for name, t in CANON]
    if key[0] == "draft":
        want += [((4, ), jnp.dtype(jnp.int32))] * (chunk is not None)
    elif sched._state_pool:
        want.append(((N, ), jnp.dtype(jnp.int32)))
    assert [(shape, dtype) for shape, dtype, _, _ in sig] == want, (key, sig)


@pytest.mark.parametrize("kind", list(KINDS))
def test_every_launch_takes_the_canonical_operands(kind):
    preset, kw, tag = KINDS[kind]
    sched = _sched(preset, counts=tag != "draft", **kw)
    seen = _record(sched)
    warmed = {}
    if tag != "draft":  # the drafter's programs are built by their first traffic
        sched.hears_only = True
        sched.warm_programs()
        sched.hears_only = False
        warmed = {key: sig for key, _, sig in seen}
        assert len(warmed) == len(seen)  # each program warmed once
        for key, sig in warmed.items():
            _canonical(sched, key, sig, None)
        del seen[:]
    if kind == "warm":
        assert {k[0] for k in warmed} == {"fused", "spec"}
        return
    programs = sched.compiled_program_count()
    is_kind = _serve(sched, kind)
    mine = [(key, chunk, sig) for key, chunk, sig in seen if is_kind(key, chunk)]
    assert mine and all(key[0] == tag for key, *_ in mine), [key for key, *_ in seen]
    for key, chunk, sig in mine:
        _canonical(sched, key, sig, chunk)
        if warmed:
            # dtype, shape, sharding and commitment, operand by operand
            assert sig == warmed[key], (key, sig, warmed[key])
    if warmed:
        assert sched.compiled_program_count() == programs


def test_no_observer_and_no_counter_code_with_the_sink_off(monkeypatch, tmp_path):
    """``RequiredWork`` is built where the capacity meter is (the sink on) and
    nowhere else; with the sink off a dispatch reaches none of its code."""
    def refuse(*a, **kw):
        raise AssertionError("counter code ran with the sink off")

    with monkeypatch.context() as m:
        for name in ("__init__", "dispatched"):
            m.setattr(required_work.RequiredWork, name, refuse)
        m.setattr(required_work, "attention_walks", refuse)
        sched = _sched("tiny")
        assert sched.capacity is None and sched._work is None
        out = sched.submit(list(range(3, 25)), max_new_tokens=5).result()
        assert len(out) == 5
    on = _sched("tiny", telemetry={"enabled": True, "output_path": str(tmp_path)})
    assert isinstance(on._work, required_work.RequiredWork) and on.capacity is not None
    on.submit(list(range(3, 25)), max_new_tokens=5).result()
    assert on.telemetry.counter_total("serving/step_rows_run") > 0
    on.telemetry.close()
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
