"""The rungs every served model climbs, written once, over a registry of twins.

A twin is a published model's kinds at a small size (a ``tiny-*`` preset),
served on the CPU in float32 and held to the plain reference of its benchmark
cell. Its entry in :data:`TWINS` is data; a rung whose data an entry leaves
out is not collected for it. A model's own file subclasses :class:`Ladder`
(``class TestLadder(Ladder): twin = "tiny-..."``), extends a rung where it has
more to assert of it, and keeps beside it what only that model has.

Programs: the rungs build through ``_serving.engine``. ``test_served_path...``
asserts what its programs were built with and builds ``fresh``; the others
share their shape's programs.
"""

import dataclasses
import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench
import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model

from . import _serving
from ._serving import VOCAB, prompts

STATE_POOL = r"holds recurrent state \(layer_types\).*"
# what every pool with state refuses, by the option that asks for it
STATE_REFUSES = [({"spec_tokens": 2}, "speculative verify"),
                 ({"max_extents": 2}, "extent chains"),
                 ({"seq_parallel_min_tokens": 64}, "sequence-parallel prefill"),
                 ({"prefix_store": object()}, "tier demotion"),
                 ({"allow_lossy_kv": True}, "lossy KV windows"),
                 ({"kv_cache_dtype": "int8"}, "an int8 KV pool"),
                 ({"adapter_store": object()}, "adapters")]
FIVE = "slots, chunk, steps, split, kernels"


@dataclasses.dataclass(frozen=True)
class Twin:
    draw: str  # the benchmark job's draw, "module:function" under chipbench.jobs
    reference: str  # the plain reference, a module under chipbench.references
    tree: tuple  # the fields of the configuration ``from_tree`` takes after the params
    hp: object  # the reference's hyper-parameters, or the fixture config ``kwargs_for`` reads
    routed: bool = False  # ``forward`` takes the program's choice and returns its routing too
    biases: tuple = ()  # leaves the draw leaves at 0, moved off it
    scales: tuple = ()  # leaves the draw leaves at 1, moved off it
    engine: dict = dataclasses.field(default_factory=dict)  # this twin's ``_serving.engine`` defaults
    forward_len: int = 70
    not_the_model: tuple = ()  # (hp changes, forward keywords) of models the program is NOT
    served_fields: str = FIVE
    served: tuple = ()  # the cases of ``test_served_path_matches_the_reference``
    served_prompts: tuple = (37, 70, 9)
    served_new: int = 16
    expert_layers: int = 0  # routed: the layers that choose
    geometry: str = None  # ``kv_pool_geometry``, where the twin's file asserted it
    idle: dict = None  # the span-0 rung: the late prompt's length, the syncs it takes, the leaves
    reuse: dict = None  # the reused-slot rung: repeats, and whether ONE pool serves all of it
    refuses: tuple = None  # (overrides, message)
    refusal: dict = dataclasses.field(default_factory=dict)  # prefix, in_config, kernels, raises
    fused_reason: str = None  # the other refusals: what the fused decode gate says of the kinds


TWINS = {
    "tiny-hybrid": Twin(
        draw="serve_hybrid:hybrid_params", reference="olmo_hybrid", tree=("layer_types", ),
        hp={"eps": 1e-6, "neg_eigval": True},
        engine={"kernels": True, "config": {"max_out_tokens": 256}}, forward_len=150,
        served_fields="slots, chunk, steps, split",
        served=((4, 16, 1, False), (4, 16, 4, False), (4, 64, 4, False), (8, 64, 1, True),
                (8, 64, 4, True), (8, 128, 4, True)),
        served_prompts=(37, 150, 70), served_new=12,
        idle={"late": 120, "syncs": 8}, reuse={"repeats": 1},
        refuses=tuple(STATE_REFUSES), refusal={"prefix": "holds recurrent state.*"},
        fused_reason="layer_types"),
    "tiny-sambay": Twin(
        draw="serve_sambay:sambay_params", reference="phi4_flash",
        tree=("layer_types", "layer_windows"), hp={"eps": 1e-5, "head_dim": 64},
        biases=("bias", "conv_bias"), scales=("scale", ),
        served=((4, 16, 1, False, False), (4, 16, 4, False, False), (4, 12, 4, False, False),
                (8, 64, 4, True, False), (4, 16, 4, False, True), (8, 64, 4, True, True)),
        idle={}, reuse={}, refuses=tuple(STATE_REFUSES),
        refusal={"prefix": "holds recurrent state, ring rows, rows that layers share.*",
                 "kernels": True},
        fused_reason="cross_attention, diff_attention, gmu, mamba"),
    "tiny-nemotron-h": Twin(
        draw="serve_nemotron_h:nemotron_params", reference="nemotron_h", tree=("layer_types", ),
        hp={"eps": 1e-5, "top_k": 2, "routed_scale": 2.5, "ssm_heads": 4, "ssm_head_dim": 8,
            "ssm_state": 16, "ssm_groups": 2, "first": 0},
        routed=True, biases=("conv_bias", ), scales=("scale", "D"),
        served=((4, 16, 1, False, False), (4, 16, 4, False, False), (4, 12, 4, False, False),
                (4, 2, 4, False, False), (8, 64, 4, True, False), (4, 16, 4, False, True),
                (8, 64, 4, True, True)),
        expert_layers=3, idle={}, reuse={}, refuses=tuple(STATE_REFUSES),
        refusal={"prefix": STATE_POOL, "kernels": True},
        fused_reason="attention, mamba2, mlp, moe"),
    "tiny-exaone-moe": Twin(
        draw="serve_ref:seeded_params", reference="exaone_moe", tree=("layer_windows", ),
        hp={"eps": 1e-5, "top_k": 2, "routed_scale": 2.5, "theta": 1e6, "first": 0},
        scales=("scale", ), served_fields="slots, chunk, steps, kernels, draft",
        served=((4, 16, 4, False, False), (4, 16, 4, True, False), (4, 16, 4, False, True),
                (4, 12, 3, False, True), (4, 16, 4, True, True)),
        refuses=(({"spec_tokens": 2}, "speculative verify by a host drafter"),
                 ({"spec_tokens": 2, "spec_draft": "module"}, "spec_tokens other than 1"),
                 ({"spec_tokens": 1, "spec_draft": "module", "prefill_chunk": 2},
                  "prefill_chunk under 3"),
                 ({"spec_tokens": 1, "spec_draft": "oracle"}, "spec_draft must be"),
                 ({"kv_cache_dtype": "int8"}, "int8 KV pool")),
        refusal={"in_config": True}),
    "tiny-lfm2-moe": Twin(
        draw="serve_nemotron_h:nemotron_params", reference="lfm2_moe", tree=("layer_types", ),
        hp={"eps": 1e-5, "top_k": 2, "routed_scale": 1.0, "renorm_eps": 1e-6, "theta": 1e6,
            "first": 0},
        routed=True, scales=("scale", ),
        # the published epsilon is in the numbers: DeepSeek-V3's 1e-20 is another model
        not_the_model=(({"renorm_eps": 1e-2}, {}), ),
        served=((4, 16, 1, False, False), (4, 16, 4, False, False), (4, 2, 4, False, False),
                (4, 1, 4, False, False), (8, 64, 4, True, False), (4, 16, 4, False, True),
                (8, 64, 4, True, True)),
        served_prompts=(37, 33, 34, 9), expert_layers=4, geometry="packed",
        idle={"leaves": 6}, reuse={}, refuses=tuple(STATE_REFUSES),
        refusal={"prefix": STATE_POOL, "kernels": True}, fused_reason="short_conv"),
    "tiny-ling": Twin(
        draw="serve_ling_hybrid:ling_params", reference="ling_hybrid", tree=("layer_types", ),
        hp={"eps": 1e-6, "top_k": 4, "routed_scale": 2.5, "renorm_eps": 1e-20, "n_group": 4,
            "topk_group": 2, "decay_lower_bound": -5.0, "theta": 1e4, "first": 0},
        routed=True, scales=("scale", ), engine={"chunk": 8},
        # a decay a head, no group limit: other models
        not_the_model=(({}, {"head_decay": True}), ({}, {"group_limit": False})),
        served_fields="slots, chunk, steps, kernels",
        served=((4, 8, 4, False), (4, 16, 1, False), (2, 1, 4, False), (8, 8, 4, True)),
        served_prompts=(37, 33, 34, 9), expert_layers=6, geometry="latent",
        refuses=((dict(spec_tokens=2), "recurrent state cannot roll back"),
                 (dict(kv_cache_dtype="int8"), "an int8 KV pool"),
                 (dict(spec_tokens=1, spec_draft="module"), "a latent pool")),
        refusal={"in_config": True, "raises": (ValueError, NotImplementedError)}),
    "tiny-falcon-h1": Twin(
        draw="serve_falcon_h1:falcon_params", reference="falcon_h1", tree=("num_layers", ),
        hp="tiny-falcon-h1.json", biases=("conv_bias", ), scales=("scale", "D"),
        served=((4, 12, 4, False, False), (8, 64, 4, True, True)), geometry="split",
        idle={"leaves": 16}, reuse={"one_pool": True},
        refuses=(({"spec_tokens": 2}, "speculative verify"),
                 ({"kv_cache_dtype": "int8"}, "an int8 KV pool"),
                 ({"max_extents": 2}, "extent chains"),
                 ({"adapter_store": object()}, "adapters")),
        refusal={"prefix": STATE_POOL, "kernels": True}),
}


@functools.lru_cache(maxsize=None)
def built(name):
    """``(model, params)`` of a twin, built once a process."""
    model = get_model(name, dtype=jnp.float32)
    return model, params_of(name, model)


def params_of(name, model, seed=7):
    """The twin's draw (the benchmark job's, perturbed) for ``model``, a stack
    of the twin's kinds."""
    twin = TWINS[name]
    return _serving.params(model, twin.draw, seed, twin.biases, twin.scales)


@functools.lru_cache(maxsize=None)
def reference(name):
    """``(module, hyper-parameters, float32 limit)`` of a twin's plain reference."""
    twin = TWINS[name]
    ref = importlib.import_module("chipbench.references." + twin.reference)
    hp = twin.hp
    if isinstance(hp, str):  # as the rehearsal fixture publishes them
        with open(os.path.join(os.path.dirname(chipbench.__file__), "tests", "fixtures",
                               "configs", hp)) as f:
            hp = ref.kwargs_for(json.load(f))
    return ref, hp, ref.TOL["float32"]


def engine(name, slots=4, chunk=None, steps=4, kernels=None, *, twin=None, **kw):
    """``_serving.engine`` over a twin (``twin``: another ``(model, params)``
    of its kind) with the twin's own defaults where it has any."""
    own = TWINS[name].engine
    chunk = own.get("chunk", 16) if chunk is None else chunk
    kernels = own.get("kernels", False) if kernels is None else kernels
    kw["config"] = dict(own.get("config", {}), **kw.get("config", {}))
    return _serving.engine(twin or built(name), slots, chunk, steps, kernels, **kw)


def tree_of(name, model, params):
    ref, _, _ = reference(name)
    return ref.from_tree(params, *(getattr(model.cfg, field) for field in TWINS[name].tree))


def logits_of(name, tree, ids, hp=None, **kw):
    """The reference's logits, and its routing where the twin is routed."""
    ref, own, _ = reference(name)
    out = ref.forward(tree, ids, own if hp is None else hp, **kw)
    return out if TWINS[name].routed else (out, None)


@functools.lru_cache(maxsize=None)
def _causal(model):
    """``model.apply`` as ONE program (eagerly it compiles one an operation)."""
    return jax.jit(model.apply)


def agrees(name, model, params, ids, hp=None, **kw):
    """The comparison of ``model``'s causal forward with the reference's."""
    ref, _, tol = reference(name)
    with jax.default_matmul_precision("highest"):
        got = _causal(model)(params, ids)
    want, _ = logits_of(name, tree_of(name, model, params), ids, hp, **kw)
    return ref.compare(got.reshape(-1, VOCAB), want.reshape(-1, VOCAB), tol=tol)


class Ladder:
    twin = None  # a key of TWINS

    def __init_subclass__(cls):
        entry = TWINS[cls.twin]
        for rung, data in (("test_a_span_0_slot_is_bit_for_bit_unchanged", entry.idle),
                           ("test_a_reused_slot_gives_a_fresh_pools_logits", entry.reuse),
                           ("test_what_the_pool_refuses", entry.refuses),
                           ("test_the_other_refusals", entry.fused_reason)):
            if data is None and rung not in cls.__dict__:
                setattr(cls, rung, None)

    def pytest_generate_tests(self, metafunc):
        entry = TWINS[self.twin]
        if "case" in metafunc.fixturenames:
            metafunc.parametrize("case", entry.served,
                                 ids=["-".join(map(str, case)) for case in entry.served])
        if "overrides" in metafunc.fixturenames:
            metafunc.parametrize("overrides, message", entry.refuses)

    # ---------------------------------------------------------------- the rungs
    def test_full_forward_matches_the_reference(self):
        """The causal forward without a cache against the reference's, at a
        length that crosses the mixers' chunks and windows several times and
        ends inside one; the models the entry says the program is not do not
        pass."""
        entry, (model, params) = TWINS[self.twin], built(self.twin)
        _, hp, _ = reference(self.twin)
        ids = jax.random.randint(jax.random.key(1), (2, entry.forward_len), 0, VOCAB)
        res = agrees(self.twin, model, params, ids)
        assert res["ok"], res["error"]
        for moved, keywords in entry.not_the_model:
            assert not agrees(self.twin, model, params, ids, dict(hp, **moved), **keywords)["ok"]

    def test_served_path_matches_the_reference(self, case):
        """Prefill in chunks (sizes that do not divide the prompts, partial
        last ones), then decode through the pool at every position, neighbours
        live in other slots, in the whole-block program and in the live-rows
        split, in XLA and through the paged kernels (interpreted): LOGITS
        against the reference, which is given the program's routing and
        follows none of it. The case lists are each model's own."""
        entry = TWINS[self.twin]
        case = dict(zip(entry.served_fields.split(", "), case))
        ref, _, tol = reference(self.twin)
        eng = engine(self.twin, case["slots"], case["chunk"], case["steps"], case.get("kernels"),
                     fresh=True)  # the tallies below are of what THIS scheduler built
        sched = eng.scheduler()
        if "kernels" in case:
            assert eng.model_config.attention_impl == ("flash" if case["kernels"] else "xla")
        if "split" in case:
            assert sched._splits_chunk(("fused", False, True, case["chunk"], case["steps"])) \
                is case["split"]
        assert entry.geometry in (None, sched.kv_pool_geometry)
        asked = prompts(entry.served_prompts)
        handles = [sched.submit(p, max_new_tokens=entry.served_new, collect_logits=True)
                   for p in asked]
        sched.drain()
        tree = tree_of(self.twin, eng.module, eng.params)
        for p, h in zip(asked, handles):
            ids = jnp.asarray([p + [int(t) for t in h.result()[:-1]]], jnp.int32)
            kw = {"first": len(p) - 1}
            if entry.routed:
                kw["choice"] = h.result_choice()[:, None, :ids.shape[1]]
                assert kw["choice"].shape[0] == entry.expert_layers  # a dense layer chooses nothing
            want, routing = logits_of(self.twin, tree, ids, **kw)
            res = ref.compare(h.result_logits(), want[0], *(
                (routing["followed"], routing["refused"]) if entry.routed else ()), tol=tol)
            assert res["ok"] and res["rows"] == entry.served_new, res["error"]
            if entry.routed:
                assert res["routing_margin_rows"] == res["routing_refused_rows"] == 0
            self.served_request(case, p, h, tree, ids, kw)
        assert sched.state_slots_reset == len(asked) and sched.radix is None
        self.served_pool(case, sched)

    def served_request(self, case, prompt, handle, tree, ids, kw):
        """What a model's file asserts of one served request besides."""

    def served_pool(self, case, sched):
        """What a model's file asserts of the scheduler when all is served."""

    def test_a_span_0_slot_is_bit_for_bit_unchanged(self):
        """A sync that advances other slots leaves an idle slot's every leaf
        (state, window, ring, rows) exactly as it was: slot 1's, once its
        request has ended, through a neighbour's chunked prefill and both
        neighbours' decode."""
        idle = TWINS[self.twin].idle
        sched = engine(self.twin, slots=4, chunk=16, steps=4).scheduler()
        a, b, c = prompts((20, 50, idle.get("late", 100)))
        long_one = sched.submit(a, max_new_tokens=60)
        short = sched.submit(b, max_new_tokens=6)  # still live when the third is admitted
        late = sched.submit(c, max_new_tokens=8)
        while not short.done:
            sched.step()
        assert sched.cache.state[1] == "free" and late._req.slot == 2 and not late.done
        leaves = lambda: jax.tree_util.tree_leaves(sched.cache.pool)
        assert idle.get("leaves") in (None, len(leaves()))
        slot1 = lambda: [np.asarray(leaf[1]) for leaf in leaves()]
        before = slot1()
        assert all(np.any(x != 0) for x in before)
        syncs = 0
        while not (long_one.done and late.done):
            sched.step()
            syncs += 1
        assert syncs >= idle.get("syncs", 6) and sched.cache.state[1] == "free"
        for x, y in zip(before, slot1()):
            np.testing.assert_array_equal(x, y)

    def test_a_reused_slot_gives_a_fresh_pools_logits(self):
        """A new request in a slot that held another starts from a zero state
        and window and sees none of the old rows: its logits are a fresh
        pool's, bit for bit, whatever the slots held since and whatever the
        neighbours; one prompt again is served cold again, and counted."""
        reuse = TWINS[self.twin].reuse
        prompt = prompts((40, ), seed=5)[0]
        first = engine(self.twin, slots=2, chunk=16).scheduler()
        want = first.submit(prompt, max_new_tokens=8, collect_logits=True)
        first.drain()
        used = first if reuse.get("one_pool") else engine(self.twin, slots=2, chunk=16).scheduler()
        for p in prompts((33, 61), seed=6):  # both slots are written over
            used.submit(p, max_new_tokens=10)
        used.drain()
        self.used_pool(used)
        repeats = reuse.get("repeats", 2)
        for _ in range(repeats):
            got = used.submit(prompt, max_new_tokens=8, collect_logits=True)
            used.drain()
            np.testing.assert_array_equal(got.result_logits(), want.result_logits())
        served = 2 + repeats + bool(reuse.get("one_pool"))
        assert used.state_slots_reset == used.prefix_cache_state_bypass == served

    def used_pool(self, sched):
        """What a model's file asserts of a pool whose slots were written over."""

    def test_what_the_pool_refuses(self, overrides, message):
        """The features a pool of this kind does not serve, each refused by
        name when a scheduler is asked for it."""
        how = TWINS[self.twin].refusal
        in_config = overrides if how.get("in_config") else {}
        eng = engine(self.twin, kernels=how.get("kernels"), **in_config)
        with pytest.raises(how.get("raises", ValueError), match=how.get("prefix", "") + message):
            eng.scheduler(**({} if how.get("in_config") else overrides))

    def test_the_other_refusals(self):
        """Migration between replicas, the static-batch cache, int8 weights,
        a tensor-parallel pool; the fused decode gate declines by kind."""
        model, params = built(self.twin)
        eng = engine(self.twin)
        sched = eng.scheduler()
        with pytest.raises(ValueError, match="cannot migrate between replicas"):
            sched.migrate_out(None, "key", None)
        with pytest.raises(ValueError, match="continuous-batching scheduler"):
            eng.generate([[1, 2, 3]], max_new_tokens=2)
        assert any(TWINS[self.twin].fused_reason in r for r in sched._fused_block_reasons)
        with pytest.raises(ValueError, match="served in its float dtype"):
            deepspeed_tpu.init_inference(model, config={"dtype": "int8"}, params=params)
        with pytest.raises(NotImplementedError, match="span programs"):
            model.apply_with_cache(params, jnp.zeros((2, 4), jnp.int32), model.init_cache(2, 64), 0)
        self.more_refusals(eng, sched)
        comm._state["mesh"] = None
        comm.initialize_mesh(tensor=2)
        tp = deepspeed_tpu.init_inference(model, config={
            "dtype": "float32", "continuous_batching": {"enabled": True, "num_slots": 2}},
            params=params)
        with pytest.raises(ValueError, match="a tensor-parallel pool"):
            tp.scheduler()

    def more_refusals(self, eng, sched):
        """What a model's file adds to the other refusals."""

    # ------------------------------------------- the rule of ``_serving.engine`` itself
    def test_shared_programs_give_what_a_schedulers_own_give(self):
        """A scheduler on its shape's shared programs and one that built its
        own (one program each: a prompt inside a chunk, the sync's four
        tokens): the same tokens and the same logits, bit for bit."""
        prompt = prompts((9, ), seed=11)[0]
        runs = []
        for fresh in (False, True):
            sched = engine(self.twin, slots=2, chunk=16, fresh=fresh).scheduler()
            handle = sched.submit(prompt, max_new_tokens=4, collect_logits=True)
            sched.drain()
            runs.append((sched, handle.result().tolist(), handle.result_logits()))
        (shared, tokens, logits), (own, own_tokens, own_logits) = runs
        held = lambda sched: any(sched._compiled is d for d in _serving._PROGRAMS.values())
        assert held(shared) and not held(own) and len(own._compiled) == 1
        assert tokens == own_tokens and len(tokens) == 4
        np.testing.assert_array_equal(logits, own_logits)

    def test_a_program_built_under_a_patched_predicate_is_not_handed_on(self, monkeypatch):
        """What a trace reads from a module is part of the programs' key: a
        scheduler made while any of ``TRACE_READS`` is patched takes another
        dict than one made with it as it is, even where the case forgot to ask
        for programs of its own, and what it builds there reaches no scheduler
        made after the patch is gone."""
        plain = engine(self.twin, slots=2, chunk=16).scheduler()
        before = dict(plain._compiled)
        for module, name in _serving.TRACE_READS:
            real = getattr(module, name)
            with monkeypatch.context() as patched:  # another object that does the same
                patched.setattr(module, name, (lambda *a, _real=real, **kw: _real(*a, **kw))
                                if callable(real) else real + 1)
                sched = engine(self.twin, slots=2, chunk=16).scheduler()
                assert sched._compiled is not plain._compiled, name
                if name == "dense_held_pays":
                    sched.submit(prompts((5, ))[0], max_new_tokens=4)
                    sched.drain()
                    built = set(map(id, sched._compiled.values()))
                    assert built
        after = engine(self.twin, slots=2, chunk=16).scheduler()
        assert after._compiled is plain._compiled and plain._compiled == before
        assert not built & set(map(id, plain._compiled.values()))

