"""A ``falcon_h1`` stack (``layer_types`` of ``parallel_hybrid``: every block a
Mamba-2 mixer AND grouped-query attention on one normed input, each scaled by
a published constant, then a SwiGLU; TII Falcon-H1's kind) at a small size on
the CPU in float32: the program against the plain reference
(``chipbench/references/falcon_h1.py``: one causal forward, no cache, the
recurrence token by token), each published constant seen, the slot pool's
span programs over K/V rows AND Mamba-2 state in the SAME layer, the
refusals, the counters of both mixers' required work, the parameter trees and
the sizes of the published preset.

Weights: the benchmark's own draw (``serve_falcon_h1.falcon_params``) with
biases and norm scales moved off 0 and 1, so that a dropped bias or scale
shows. ``TOL``: the reference's float32 limit, 1e-5; the served path reads
1e-6 at worst; a wrong state, span, weight or constant gives 1e-3 and up."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench
import deepspeed_tpu
from chipbench.references import falcon_h1 as ref
from deepspeed_tpu.models import available_models, get_model

TOL = ref.TOL["float32"]
VOCAB = 256
# the tiny twin's sizes and constants, as the rehearsal fixture publishes them:
# every constant off 1, no two alike
with open(os.path.join(os.path.dirname(chipbench.__file__), "tests", "fixtures", "configs",
                       "tiny-falcon-h1.json")) as _f:
    HP = ref.kwargs_for(json.load(_f))


def _params(model, seed=7):
    """The benchmark's draw, biases and norm scales perturbed."""
    from chipbench.jobs.serve_falcon_h1 import falcon_params
    root = jax.random.key(seed)

    def perturb(path, leaf):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, int(hashlib.sha256(name.encode()).hexdigest()[:7], 16))
        if name.endswith("['conv_bias']"):
            return 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if name.endswith("['scale']") or name.endswith("['D']"):
            return 1.0 + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(perturb,
                                            falcon_params(model, seed, jnp.dtype("float32")))


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny-falcon-h1", dtype=jnp.float32)
    return model, _params(model)


def _engine(tiny, slots=4, chunk=16, steps=4, kernels=False, **cb):
    model, params = tiny
    return deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "kernel_inject": kernels, "max_out_tokens": 128,
        "continuous_batching": dict({"enabled": True, "num_slots": slots,
                                     "steps_per_sync": steps, "prefill_chunk": chunk}, **cb)},
        params=params)


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, VOCAB, n)] for n in lengths]


def _compared(model, params, ids, hp=HP):
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, ids)
    want = ref.forward(ref.from_tree(params, model.cfg.num_layers), ids, hp)
    return ref.compare(got.reshape(-1, VOCAB), want.reshape(-1, VOCAB), tol=TOL)


def test_full_forward_matches_the_reference(tiny):
    """70 positions: eight Mamba-2 chunks of 8 and a partial one; 5 query
    heads read ONE key/value head (the published odd group of five)."""
    model, params = tiny
    assert (model.cfg.num_heads, model.cfg.kv_heads) == (5, 1)
    res = _compared(model, params, jax.random.randint(jax.random.key(1), (2, 70), 0, VOCAB))
    assert res["ok"], res["error"]


# each published constant, moved alone: the program with that ONE constant at
# another value must leave the reference's limit (fourteen numbers: seven
# scalars, the MLP's two, ssm_multipliers' five)
_MOVED = [(name, {name: 2.0 if name == "attention_in_multiplier" else 1.0}) for name in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "mlp_gate_multiplier", "mlp_down_multiplier")] + [
    (f"ssm_multipliers[{i}]", {"ssm_multipliers": tuple(
        1.0 if j == i else m for j, m in enumerate(HP["ssm_multipliers"]))}) for i in range(5)]


_IDS = np.random.RandomState(2).randint(0, VOCAB, (1, 24))


@pytest.fixture(scope="module")
def one_layer():
    """The twin one block deep: every constant acts in it, and a case costs a
    quarter of the stack's."""
    model = get_model("tiny-falcon-h1", dtype=jnp.float32, num_layers=1,
                      layer_types=("parallel_hybrid", ))
    params = _params(model, seed=11)
    assert _compared(model, params, _IDS)["ok"]  # unmoved, the program agrees
    return model, params


@pytest.mark.parametrize("moved", [m for _, m in _MOVED], ids=[n for n, _ in _MOVED])
def test_each_published_constant_is_seen(one_layer, moved):
    model, params = one_layer
    assert all(getattr(model.cfg, k) != v for k, v in moved.items())
    other = type(model)(dataclasses.replace(model.cfg, **moved))
    res = _compared(other, params, _IDS)
    assert not res["ok"] and res["error"] > 3 * TOL, res["error"]


@pytest.mark.parametrize("slots, chunk, steps, split, kernels", [
    (4, 12, 4, False, False), (8, 64, 4, True, True)])
def test_served_path_matches_the_reference(tiny, slots, chunk, steps, split, kernels):
    """Prefill in chunks (a prompt of 70 crosses five chunk boundaries with a
    partial last; chunks of 12 end inside a Mamba-2 chunk of 8), then 16
    decode steps through the pool at every position, neighbours live in other
    slots, in the whole-block program and in the live-rows split, in XLA and
    through the paged kernels (interpreted: the decode column through
    ``dstpu_decode_attn`` and ``dstpu_kv_commit`` in groups of five)."""
    eng = _engine(tiny, slots, chunk, steps, kernels)
    sched = eng.scheduler()
    assert eng.model_config.attention_impl == ("flash" if kernels else "xla")
    assert sched._splits_chunk(("fused", False, True, chunk, steps)) is split
    prompts = _prompts((37, 70, 9))
    handles = [sched.submit(p, max_new_tokens=16, collect_logits=True) for p in prompts]
    sched.drain()
    tree = ref.from_tree(eng.params, 4)
    for p, h in zip(prompts, handles):
        ids = jnp.asarray([p + [int(t) for t in h.result()[:-1]]], jnp.int32)
        res = ref.compare(h.result_logits(), ref.forward(tree, ids, HP, first=len(p) - 1)[0],
                          tol=TOL)
        assert res["ok"] and res["rows"] == 16, res["error"]
    assert sched.state_slots_reset == 3 and sched.radix is None
    assert (sched.kv_commit_programs["inplace"] > 0) is kernels
    assert sched.kv_pool_geometry == "split"


def test_a_layers_slot_holds_rows_and_state(tiny):
    """``rows, rows, state, state`` in every layer, in the component-major
    tree; the pool's accounting takes bytes a token and bytes a slot from the
    same layers."""
    model, _ = tiny
    kinds = model.cache_kinds()
    assert [tuple(k[i] for k in kinds) for i in range(4)] == [
        ("rows", "rows", "state", "state")] * 4
    pool = model.init_cache(3, 32)
    assert [leaf.shape for leaf in (pool[0][0], pool[1][0], pool[2][0], pool[3][0])] == [
        (3, 1, 32, 16), (3, 1, 32, 16), (3, 4, 8, 16), (3, 1, 3, 96)]
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    kv = SlotKVCache(pool, 3, 32, kinds=kinds)
    assert kv.bytes_per_token() == 4 * 2 * 16 * 4
    assert kv.state_bytes_per_slot() == 4 * (4 * 8 * 16 + 3 * 96) * 4


def test_a_span_0_slot_is_bit_for_bit_unchanged(tiny):
    """A sync that advances other slots leaves an idle slot's FOUR leaves a
    layer (K rows, V rows, state, window) exactly as they were: slot 1's, once
    its request has ended, through a neighbour's chunked prefill and both
    neighbours' decode."""
    sched = _engine(tiny, slots=4, chunk=16, steps=4).scheduler()
    a, b, c = _prompts((20, 50, 100))
    long_one = sched.submit(a, max_new_tokens=60)
    short = sched.submit(b, max_new_tokens=6)  # still live when the third is admitted
    late = sched.submit(c, max_new_tokens=8)
    while not short.done:
        sched.step()
    assert sched.cache.state[1] == "free" and late._req.slot == 2 and not late.done
    leaves = lambda: jax.tree_util.tree_leaves(sched.cache.pool)
    assert len(leaves()) == 4 * 4
    slot1 = lambda: [np.asarray(leaf[1]) for leaf in leaves()]
    before = slot1()
    assert all(np.any(x != 0) for x in before)
    steps = 0
    while not (long_one.done and late.done):
        sched.step()
        steps += 1
    assert steps >= 6 and sched.cache.state[1] == "free"
    for x, y in zip(before, slot1()):
        np.testing.assert_array_equal(x, y)


def test_a_reused_slot_gives_a_fresh_pools_logits(tiny):
    """A new request in a slot that held another starts from a zero state and
    window AND attends from position 0: its logits are those it got from the
    fresh pool, bit for bit, whatever the slots held since; one prompt three
    times is served cold three times (every layer holds state beside its
    rows) and counted."""
    prompt = _prompts((40, ), seed=5)[0]
    sched = _engine(tiny, slots=2, chunk=16).scheduler()
    want = sched.submit(prompt, max_new_tokens=8, collect_logits=True)
    sched.drain()
    for p in _prompts((33, 61), seed=6):  # both slots are written over
        sched.submit(p, max_new_tokens=10)
    sched.drain()
    for _ in range(2):
        got = sched.submit(prompt, max_new_tokens=8, collect_logits=True)
        sched.drain()
        np.testing.assert_array_equal(got.result_logits(), want.result_logits())
    assert sched.state_slots_reset == 5 and sched.prefix_cache_state_bypass == 5


@pytest.mark.parametrize("overrides, message", [
    ({"spec_tokens": 2}, "speculative verify"),
    ({"kv_cache_dtype": "int8"}, "an int8 KV pool"),
    ({"max_extents": 2}, "extent chains"),
    ({"adapter_store": object()}, "adapters"),
])
def test_what_a_pool_with_rows_and_state_in_one_layer_refuses(tiny, overrides, message):
    """Every layer holds rows too, and the pool is still one with state:
    drafting and the int8 tier are refused by name."""
    eng = _engine(tiny, kernels=True)
    with pytest.raises(ValueError, match=r"holds recurrent state \(layer_types\).*" + message):
        eng.scheduler(**overrides)


def test_what_the_kind_refuses(tiny):
    model, params = tiny
    cfg = model.cfg
    hybrid = ("parallel_hybrid", ) * 4
    with pytest.raises(ValueError, match="composes with no other kind"):
        dataclasses.replace(cfg, layer_types=("parallel_hybrid", ) * 3 + ("full_attention", ))
    for bad in ({"layer_windows": (8, 0, 0, 0)}, {"post_norm": True},
                {"num_experts": 4, "moe_dropless": True}, {"pos_embedding": "alibi"}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, layer_types=hybrid, **bad)
    with pytest.raises(ValueError, match="float dtype"):
        dataclasses.replace(cfg, int8_weights=True)
    with pytest.raises(ValueError, match="parallel_hybrid"):
        dataclasses.replace(cfg, mtp_layers=1)
    with pytest.raises(ValueError, match="ssm_num_heads"):
        dataclasses.replace(cfg, ssm_groups=3)
    with pytest.raises(ValueError, match="five constants"):
        dataclasses.replace(cfg, ssm_multipliers=(1.0, 2.0))
    with pytest.raises(ValueError, match="applied by a stack of parallel_hybrid"):
        dataclasses.replace(get_model("tiny").cfg, key_multiplier=0.5)
    with pytest.raises(NotImplementedError, match="no int8 tier"):
        model.init_cache(2, 64, quantized=True)
    with pytest.raises(NotImplementedError, match="span programs"):
        model.apply_with_cache(params, jnp.zeros((2, 4), jnp.int32), model.init_cache(2, 64), 0)
    with pytest.raises(ValueError, match="served in its float dtype"):
        deepspeed_tpu.init_inference(model, config={"dtype": "int8"}, params=params)
    sched = _engine(tiny).scheduler()
    assert any("parallel_hybrid" in r for r in sched._fused_block_reasons)


def test_counters_count_both_mixers_of_a_layer(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 4 layers, each counted in the state family AND in the
    attended keys."""
    model, params = tiny
    eng = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "kernel_inject": True, "max_out_tokens": 128,
        "continuous_batching": {"enabled": True, "num_slots": 2, "steps_per_sync": 4,
                                "prefill_chunk": 16},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}}, params=params)
    sched = eng.scheduler()
    sched.submit(_prompts((20, ))[0], max_new_tokens=8)
    sched.drain()
    total = eng.telemetry.counter_total
    # chunk 1 (16 columns, not final, alone: K = 1); chunk 2 (4 columns, final,
    # K = 4: 3 substeps); one decode sync (K = 4): its column and 3 substeps
    assert total("serving/ssd_chunk_tokens") == 4 * (16 + 4)
    assert total("serving/ssd_state_updates") == 4 * (3 + 1 + 3)
    # keys attended: the chunks' 16 and 20, then a key more each of 7 one-token forwards
    assert total("serving/attn_keys_live") == 4 * (16 + 20 + sum(range(21, 28)))
    assert total("serving/attn_keys_walked") >= total("serving/attn_keys_live")
    gauges = eng.telemetry.snapshot()["gauges"]
    assert gauges["serving/state_bytes_per_slot"] == sched.cache.state_bytes_per_slot() == 12800
    assert sched.cache.bytes_per_token() == 512
    eng.telemetry.close()


def test_the_block_has_both_mixers_leaves(tiny):
    model, params = tiny
    for i in range(4):
        assert set(params[f"layer_{i}"]) == {"attn_norm", "attn", "mamba2", "mlp_norm", "mlp"}
    assert set(params["layer_0"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(params["layer_0"]["mamba2"]) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log",
                                                "D", "norm", "out_proj"}
    assert set(params["layer_0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    # no bias but the convolution's; the constants are no parameters
    assert all(set(v) == {"kernel"} for v in params["layer_0"]["attn"].values())
    assert set(params) == {"embed", "final_norm", "lm_head"} | {f"layer_{i}" for i in range(4)}
    abstract = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == model.cfg.num_params()


def _digest(tree):
    items = [(jax.tree_util.keystr(p), tuple(getattr(leaf, "shape", ())),
              str(getattr(leaf, "dtype", leaf)))
             for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16], len(items)


# the twins of cells 7, 5 and 9, whose code this PR's shared code can move
# (``Mamba2``, ``Attention``, ``MLP``, ``Block``, ``cache_spec``): (digest,
# leaves) of the parameter tree, six logits of the last position and the mean
# magnitude, taken on the parent commit 6060c84 with the same keys
PARENT_TWINS = {
    "tiny-nemotron-h": (("7047c47155d9e362", 59), [-0.8989415, -0.3812234, 0.102492, 1.1450336,
                                                   -1.2561585, 0.4224195], 0.7843328),
    "tiny-hybrid": (("fe34f5c3f89eebab", 62), [-1.7705975, -0.6584616, 1.7142107, -1.0486724,
                                               0.9665461, -0.4132772], 0.7986161),
    "tiny-lfm2-moe": (("c723c56f5523fbc8", 61), [-0.2671085, -0.1364899, -0.3864794, -0.1567951,
                                                 -0.178684, -0.7329741], 0.2559913),
}


@pytest.mark.parametrize("name", sorted(PARENT_TWINS))
def test_the_twins_build_the_trees_they_built(name):
    model = get_model(name, dtype=jnp.float32)
    assert not model.cfg.has_multipliers
    assert _digest(jax.eval_shape(model.init_params, jax.random.key(0))) == PARENT_TWINS[name][0]


@pytest.mark.slow  # ~13 s a twin; tier-1 holds each twin's logits to its own reference already
@pytest.mark.parametrize("name", sorted(PARENT_TWINS))
def test_the_twins_give_the_logits_they_gave(name):
    _, logits, magnitude = PARENT_TWINS[name]
    model = get_model(name, dtype=jnp.float32)
    params = model.init_params(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 24), 0, model.cfg.vocab_size)
    out = model.apply(params, ids)
    out = out[0] if isinstance(out, tuple) else out
    np.testing.assert_allclose(out[1, -1, :6], logits, atol=2e-6)
    np.testing.assert_allclose(jnp.mean(jnp.abs(out)), magnitude, atol=2e-6)


def test_the_presets_are_guarded():
    assert {"falcon-h1-34b-instruct", "tiny-falcon-h1"} <= set(available_models())


def test_preset_builds_the_published_sizes():
    """33.64 B parameters in 72 two-mixer blocks of 430,120,032; the cut as
    the benchmark serves it: six whole layers and the whole vocabulary, 5.25
    B, 2,048 B a position a layer and 2,127,872 B of state and window a slot a
    layer, in the SAME layers."""
    from chipbench import cells
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("falcon-h1-34b-instruct")
    cfg = whole.cfg
    assert cfg.layer_types == ("parallel_hybrid", ) * 72
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads, cfg.head_size,
            cfg.ffn_size, cfg.rope_theta, cfg.tie_embeddings) == (
        5120, 261120, 20, 4, 128, 21504, 1e11, False)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk_size, cfg.mamba2_inner,
            cfg.mamba2_conv_channels) == (32, 128, 256, 2, 4, 128, 4096, 5120)
    assert cfg.num_params() == 33_642_516_224
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    layer = abstract["layer_0"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert (count(layer["attn"]), count(layer["mamba2"]), count(layer["mlp"]), count(layer)) == (
        31_457_280, 68_351_072, 330_301_440, 430_120_032)
    assert layer["mamba2"]["in_proj"]["kernel"].shape == (5120, 9248)
    config = cells.load_config("falcon-h1-34b-instruct")
    served = cells.build_model(config, dtype=jnp.bfloat16)
    pub = config["published"]
    assert served.cfg.layer_types == tuple(config["expect_lists"]["layer_types"])
    assert list(served.cfg.ssm_multipliers) == pub["ssm_multipliers"]
    assert [served.cfg.mlp_gate_multiplier, served.cfg.mlp_down_multiplier] == pub["mlp_multipliers"]
    for key in ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        assert getattr(served.cfg, key) == pub[key], key
    assert served.cfg.num_params() == 5_254_594_112 == config["sizes"]["parameters_here"]
    pool = jax.eval_shape(lambda: served.init_cache(64, 4096))
    kv = SlotKVCache(pool, 64, 4096, kinds=served.cache_kinds())
    assert kv.bytes_per_token() == 12_288 == config["reference"]["kv_bytes_per_token"]
    assert kv.state_bytes_per_slot() == 12_767_232 == config["reference"]["state_bytes_per_slot"]
    assert kv.capacity_bytes() == 64 * (4096 * 12_288 + 12_767_232)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert shapes.count((64, 4, 4096, 128)) == 12 and shapes.count((64, 32, 128, 256)) == 6
    assert shapes.count((64, 1, 3, 5120)) == 6 and len(shapes) == 24
