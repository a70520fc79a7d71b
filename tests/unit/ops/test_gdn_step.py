"""The in-place one-token gated-delta update (``ops/pallas/gdn_step.py``,
interpret mode) against ``gated_delta_step``, the definition, on a state at
rest in float32 and in bf16, packed two heads a lane row and plain: live
slots, fresh rows and span-0 slots in one call."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import gdn_step

PUBLISHED = (30, 96, 192)  # olmo-hybrid-7b's linear-attention heads: p = 2
SHAPES = {"published": PUBLISHED, "two_64s": (4, 32, 64), "plain_128": (3, 16, 128)}
# (live, fresh) of six slots: dead ones first, between and last; a fresh one
# beside a carried one
SPANS = {
    "mixed": ([0, 1, 1, 0, 0, 1], [0, 0, 1, 0, 0, 0]),
    "all_live": ([1] * 6, [1, 0, 0, 0, 0, 1]),
    "all_dead": ([0] * 6, [0] * 6),
    "last_alone": ([0, 0, 0, 0, 0, 1], [0] * 6),
}


def _operands(shape, dtype, slots=6, seed=0, decay="head"):
    """``decay`` "channel": a log decay a KEY CHANNEL (Kimi delta attention's
    bounded gate), spread over (-5, 0) with both ends present."""
    n, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    S = (0.3 * jax.random.normal(ks[0], (slots, n, dk, dv))).astype(dtype)
    q = l2(jax.random.normal(ks[1], (slots, n, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[2], (slots, n, dk)))
    v = jax.random.normal(ks[3], (slots, n, dv))
    g = -2.0 * jax.random.uniform(ks[4], (slots, n))
    if decay == "channel":
        g = -5.0 * jax.random.uniform(ks[4], (slots, n, dk)) ** 2
        g = g.at[:, :, 0].set(-4.999).at[:, :, 1].set(-1e-4)
    beta = 2.0 * jax.random.uniform(ks[5], (slots, n))
    return S, (q, k, v, g, beta)


def _definition(S, column, live, fresh):
    """What ``GatedDeltaNet`` computes where the kernel is not taken."""
    start = jnp.where(fresh[:, None, None, None], 0.0, S.astype(jnp.float32))
    o, new = tfm.gated_delta_step(start, *column)
    return o, jnp.where(live[:, None, None, None], new.astype(S.dtype), S)


def _flags(case, slots=6):
    live, fresh = (jnp.asarray(x[:slots], bool) for x in SPANS[case])
    return live, fresh & live


def _bits(x):
    return np.asarray(x.view(jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape, case, decay", [
    ("published", "mixed", "head"), ("published", "all_live", "head"),
    *(("two_64s", case, "head") for case in sorted(SPANS)),
    ("plain_128", "mixed", "head"), ("plain_128", "all_dead", "head"),
    ("two_64s", "mixed", "channel"), ("two_64s", "all_live", "channel"),
    ("plain_128", "mixed", "channel"), ("plain_128", "last_alone", "channel")])
def test_update_matches_the_definition(shape, case, decay, dtype):
    """float32 at rest: 1e-5 of the largest value on ``o`` and ``S'``; bf16
    at rest: ``o`` the same, the stored state within one bf16 step of the
    definition's (a float32 reassociation moves a value by 1e-6 of the
    largest before the rounding, and a tie falls either way); a span-0 slot
    bit for bit. For a decay a head and a decay a key channel (the same
    kernel, the channel's decays entering on the rows of ``k`` and ``q`` and
    as a scale a row of the block)."""
    n, dk, dv = SHAPES[shape]
    slots = 3 if shape == "published" else 6
    S, column = _operands(SHAPES[shape], dtype, slots, decay=decay)
    live, fresh = _flags(case, slots)
    p = gdn_step.state_packing(n, dv)
    assert p == (1 if shape == "plain_128" else 2)
    leaf = gdn_step.pack_state(S, p)
    assert gdn_step.tiles(leaf, n, dk, dv, decay == "channel")
    assert leaf.shape == (slots, n // p, dk, p * dv)
    o, new = jax.jit(gdn_step.gated_delta_update)(leaf, *column, live, fresh)
    assert new.shape == leaf.shape and new.dtype == leaf.dtype and o.dtype == jnp.float32
    new = gdn_step.unpack_state(new, p)
    want_o, want = _definition(S, column, live, fresh)
    alive = np.asarray(live)
    np.testing.assert_array_equal(_bits(new)[~alive], _bits(S)[~alive])
    if not alive.any():
        return
    scale = float(jnp.abs(want_o[alive]).max())
    assert float(jnp.abs(o - want_o)[alive].max()) < 1e-5 * scale
    got, want = (np.asarray(x, np.float32)[alive] for x in (new, want))
    if dtype == jnp.float32:
        assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    else:
        step = np.abs(want) * 2.0 ** -7 + 1e-6 * np.abs(want).max()  # one bf16 step, at most
        assert (np.abs(got - want) <= step).all()
        assert (got != want).mean() < 1e-3


def test_a_fresh_row_equals_a_zeroed_slot():
    """A row that starts at position 0 starts from zero whatever the slot
    held, a NaN among it: the kernel does not look at the old bytes."""
    S, column = _operands(SHAPES["two_64s"], jnp.bfloat16)
    live, fresh = _flags("all_live")
    poisoned = S.at[0, 1, 3, 5].set(jnp.nan).at[5].set(jnp.inf)
    zeroed = jnp.where(fresh[:, None, None, None], 0, S)
    run = jax.jit(gdn_step.gated_delta_update)
    o_a, new_a = run(gdn_step.pack_state(poisoned, 2), *column, live, fresh)
    o_b, new_b = run(gdn_step.pack_state(zeroed, 2), *column, live, fresh)
    np.testing.assert_array_equal(_bits(new_a), _bits(new_b))
    np.testing.assert_array_equal(np.asarray(o_a), np.asarray(o_b))
    assert np.isfinite(np.asarray(o_a)).all()


def test_the_aliased_leaf_is_the_output():
    """The state operand is the pallas call's first output: a program that
    donates or carries the pool moves nothing else of it."""
    S, column = _operands(PUBLISHED, jnp.bfloat16, slots=2)
    live, fresh = _flags("all_live", 2)
    trace = jax.make_jaxpr(functools.partial(gdn_step._update.__wrapped__, interpret=False))
    (call, ) = [e for e in trace(gdn_step.pack_state(S, 2), *column, live, fresh).jaxpr.eqns
                if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((4, 0), )
    assert call.invars[4].aval.shape == call.outvars[0].aval.shape == (2, 15, 96, 384)
    assert call.invars[4].aval.dtype == call.outvars[0].aval.dtype == jnp.bfloat16


@pytest.mark.parametrize("shape, p", [(PUBLISHED, 2), ((4, 32, 64), 2), ((3, 16, 128), 1),
                                      ((4, 8, 16), 1)])
def test_packed_and_plain_leaves_hold_the_same_values(shape, p):
    """The chunk path's conversion there and back, and where a head lands."""
    n, dk, dv = shape
    S, _ = _operands(shape, jnp.bfloat16, slots=2)
    assert gdn_step.state_packing(n, dv) == p
    packed = gdn_step.pack_state(S, p)
    assert packed.shape == (2, n // p, dk, p * dv) and packed.size == S.size
    np.testing.assert_array_equal(_bits(gdn_step.unpack_state(packed, p)), _bits(S))
    h = n - 1
    np.testing.assert_array_equal(
        _bits(packed[:, h // p, :, (h % p) * dv:(h % p + 1) * dv]), _bits(S[:, h]))


def test_update_splits_units_to_fit_vmem(monkeypatch):
    """The unit block is chosen against the VMEM budget: a smaller budget
    gives more grid steps and the same leaf; under one unit's it refuses."""
    import deepspeed_tpu.ops.pallas as pallas_pkg
    n, dk, dv = SHAPES["two_64s"]
    S, column = _operands((n, dk, dv), jnp.bfloat16)
    live, fresh = _flags("mixed")
    leaf = gdn_step.pack_state(S, 2)
    update = functools.partial(gdn_step._update.__wrapped__, interpret=True)
    whole = update(leaf, *column, live, fresh)
    one_unit = gdn_step._vmem_estimate(1, dk, 2 * dv, 2)
    assert one_unit < gdn_step._vmem_estimate(2, dk, 2 * dv, 2)
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_unit)
    split = update(leaf, *column, live, fresh)
    np.testing.assert_array_equal(_bits(whole[1]), _bits(split[1]))
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(split[0]))
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_unit - 1)
    assert not gdn_step.tiles(leaf, n, dk, dv)
    with pytest.raises(ValueError, match="VMEM"):
        update(leaf, *column, live, fresh)


def test_tiles_reads_the_leaf():
    sds = jax.ShapeDtypeStruct
    assert gdn_step.tiles(sds((64, 15, 96, 384), jnp.bfloat16), 30, 96, 192)
    assert gdn_step.tiles(sds((64, 15, 96, 384), jnp.float32), 30, 96, 192)
    assert gdn_step.tiles(sds((8, 3, 16, 128), jnp.float32), 3, 16, 128)
    assert not gdn_step.tiles(sds((64, 30, 96, 192), jnp.bfloat16), 30, 96, 192)  # plain, padded
    assert not gdn_step.tiles(sds((4, 4, 8, 16), jnp.float32), 4, 8, 16)  # tiny-hybrid's
    assert not gdn_step.tiles(sds((4, 2, 8, 128), jnp.bfloat16), 4, 8, 64)  # half a bf16 tile
    assert not gdn_step.tiles(sds((4, 2, 256, 128), jnp.bfloat16), 4, 256, 64)  # k over a lane tile
    assert not gdn_step.tiles(sds((4, 2, 32, 128), jnp.float16), 4, 32, 64)


def _layer_model(n, dk, dv, attention_impl):
    """``tiny-hybrid`` three layers deep with linear-attention heads of the
    given shape."""
    cfg = get_model("tiny-hybrid", dtype=jnp.float32).cfg
    return tfm.CausalLMModel(dataclasses.replace(
        cfg, num_layers=3, layer_types=("linear_attention", "linear_attention", "full_attention"),
        linear_num_heads=n, linear_key_head_dim=dk, linear_value_head_dim=dv,
        attention_impl=attention_impl, max_seq_len=32))


@pytest.mark.parametrize("heads, kernel", [((2, 16, 64), True), ((4, 8, 16), False)],
                         ids=["tiles", "falls_back"])
def test_the_layer_takes_the_kernel_by_shape(heads, kernel):
    """``GatedDeltaNet``'s decode column: the kernel where the heads tile and
    the layer would take the paged kernels, the definition elsewhere, the
    same numbers either way; the tally says which."""
    n, dk, dv = heads
    model, plain = _layer_model(n, dk, dv, "flash"), _layer_model(n, dk, dv, "xla")
    # (jitted: op by op, the initialisation alone compiles for seconds)
    params = jax.jit(model.init_params)(jax.random.key(0))
    pool = jax.jit(lambda: jax.tree_util.tree_map(
        lambda x: 0.1 * jax.random.normal(jax.random.key(1), x.shape, x.dtype),
        model.init_cache(3, 32)))()
    ids = jnp.asarray([[5], [7], [9]], jnp.int32)
    heads_at, spans = jnp.asarray([4, 0, 9], jnp.int32), jnp.asarray([1, 1, 0], jnp.int32)
    outs = []
    for m in (model, plain):
        before = gdn_step.traced()
        outs.append(jax.jit(lambda params, pool, m=m: m.apply_with_cache(
            params, ids, pool, 0, position_ids=heads_at[:, None], write_index=heads_at,
            q_spans=spans))(params, pool))
        took = tuple(a - b for a, b in zip(gdn_step.traced(), before))
        assert took == ((2, 0) if kernel and m is model else (0, 2))
    (logits, cache), (want_logits, want_cache) = outs
    np.testing.assert_allclose(np.asarray(logits[:2]), np.asarray(want_logits[:2]),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
