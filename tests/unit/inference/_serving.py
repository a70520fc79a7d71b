"""The one place that makes serving engines for the tests of the serving path,
and hands their schedulers step programs.

A scheduler test's time is mostly building step programs (tracing, lowering,
the CPU back end's compile). The system's remedy is ``DecodeScheduler(
compiled_cache=...)``: schedulers of one shape share ONE dict of programs, as
the replicas of a fleet do (``serving/replica.py``). :func:`engine` gives every
scheduler it makes the dict of its KEY: the model's configuration, the engine's
whole config, every ``continuous_batching`` field and scheduler override, and
the module-level predicates a trace reads (:data:`TRACE_READS`), so that a
program built while one of them is patched can never serve a scheduler that
runs with it unpatched. ``tests/conftest.py`` deals the cases that share to one
worker.

The rule of the call: ``fresh=True`` for a case that asserts what was BUILT
(``kv_commit_programs``, ``gdn_step_programs``, ``ssd_step_programs``,
``moe_dispatch_programs``, ``compiled_program_count()``, ``_compiled``'s keys,
anything ``warm_programs`` counts: only the scheduler whose call traced a
program tallies it, ``DecodeScheduler._run_program``), and for any case that
takes ``monkeypatch`` or sets an attribute that a trace reads. Such a scheduler
builds its own programs, as one made without this module does.
"""

import hashlib
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.ops.pallas import gdn_step, kv_commit, ssd_step

VOCAB = 256

# what a trace of a step program reads from a module and a test may patch: the
# objects bound there when a scheduler is made are part of its programs' key
TRACE_READS = ((moe_layer, "dense_held_pays"), (moe_layer, "SPARSE_TILE"), (tfm, "kv_packs"),
               (kv_commit, "commits_columns_in_place"), (gdn_step, "tiles"), (ssd_step, "tiles"),
               (InferenceEngine, "_fused_decode_eligible"))

_PROGRAMS = {}  # key -> the dict of step programs the schedulers of that key share


def fresh_process_state():
    """No mesh and no telemetry sink left over from the test before."""
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)


def prompts(lengths, seed=0, vocab=VOCAB):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab, n)] for n in lengths]


def digest(tree):
    """(sha256 of the tree's (path, shape, dtype) list, number of leaves)."""
    items = [(jax.tree_util.keystr(p), tuple(getattr(leaf, "shape", ())),
              str(getattr(leaf, "dtype", leaf)))
             for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16], len(items)


def params(model, draw, seed=7, biases=(), scales=()):
    """The benchmark job's own draw (``draw``: ``"job_module:function"`` under
    ``chipbench.jobs``) in float32, with the leaves named in ``biases`` moved
    off 0 and those in ``scales`` off 1, so that a dropped one shows."""
    module, name = draw.split(":")
    drawn = getattr(importlib.import_module("chipbench.jobs." + module), name)(
        model, seed, jnp.dtype("float32"))
    root = jax.random.key(seed)

    def perturb(path, leaf):
        where = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, int(hashlib.sha256(where.encode()).hexdigest()[:7], 16))
        if where.endswith(tuple(f"['{b}']" for b in biases)):
            return 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if where.endswith(tuple(f"['{s}']" for s in scales)):
            return 1.0 + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(perturb, drawn) if biases or scales else drawn


def _plain(value):
    """``value`` as a hashable of plain data, or None where it holds an object
    (a store, a hook) whose state a program may depend on."""
    try:
        return json.dumps(value, sort_keys=True, default=lambda o: {}[o])
    except (KeyError, TypeError):
        return None


def programs(model, config, overrides, also=None):
    """The dict of step programs for schedulers of ``model`` under ``config``
    (the engine's), ``overrides`` (the scheduler's) and ``also`` (what else a
    case says its programs depend on); a new dict where any of it is an object
    and not data."""
    said = _plain((config, overrides, also))
    if said is None:
        return {}
    key = (type(model), model.cfg, said) + tuple(getattr(m, name) for m, name in TRACE_READS)
    return _PROGRAMS.setdefault(key, {})


def engine(twin, slots=4, chunk=16, steps=4, kernels=False, *, fresh=False, also=None,
           config=None, **cb):
    """An inference engine over ``twin`` (``(model, params)``; ``params`` None:
    the engine's own draw) whose scheduler takes its step programs from the
    dict of its key, or builds its own (``fresh``: see the module's rule).
    ``also``: a name for what the case then sets on the scheduler itself, so
    that its programs are shared only among schedulers set the same way.
    ``cb``: ``continuous_batching`` fields; ``config``: other sections of the
    engine's config (``dtype`` float32, ``max_out_tokens`` 128 unless given)."""
    fresh_process_state()
    model, weights = twin
    config = dict({"dtype": "float32", "kernel_inject": kernels, "max_out_tokens": 128},
                  **(config or {}))
    config["continuous_batching"] = dict({"enabled": True, "num_slots": slots,
                                          "steps_per_sync": steps, "prefill_chunk": chunk}, **cb)
    eng = deepspeed_tpu.init_inference(model, config=config, params=weights)
    if not fresh:
        build = eng.scheduler

        def scheduler(**overrides):
            if eng._scheduler is None:
                overrides.setdefault("compiled_cache", programs(eng.module, config, overrides, also))
            return build(**overrides)

        eng.scheduler = scheduler
    return eng
