"""The in-place one-token Mamba-2 update (``ops/pallas/ssd_step.py``,
interpret mode) against ``ssd_step``, the definition, at the two published
head shapes (64 x 128 in 8 groups, 128 x 256 in 2; fewer heads a group than
published, so that a case compiles in seconds) on a state at rest in bf16 and
in float32: live slots, fresh rows and span-0 slots in one call."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model, mamba2
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import ssd_step

# (nh, hd, N, G): cell 7's heads (two a piece of 128 rows), cell 11's (a head a piece)
SHAPES = {"heads_64x128": (16, 64, 128, 8), "heads_128x256": (4, 128, 256, 2)}
# (live, fresh) of five slots: dead ones first, between and last; a fresh one
# beside a carried one
SPANS = {
    "mixed": ([0, 1, 1, 0, 1], [0, 0, 1, 0, 0]),
    "every_other": ([1, 0, 1, 0, 1], [0, 0, 0, 0, 1]),
    "none_live": ([0] * 5, [0] * 5),
    "all_live": ([1] * 5, [1, 0, 0, 0, 0]),
}
f32 = jnp.float32


def _operands(shape, dtype, slots=5, seed=0):
    nh, hd, N, G = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    S = (0.5 * jax.random.normal(ks[0], (slots, nh, hd, N))).astype(dtype)
    x = jax.random.normal(ks[1], (slots, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, nh)))
    a = -jnp.exp(jax.random.uniform(ks[3], (nh, ), minval=0.0, maxval=2.7))  # A ~ [1, 16]
    Bm, Cm = (jax.random.normal(k, (slots, G, N)) for k in ks[4:6])
    return S, (x, dt, a, Bm, Cm, jax.random.normal(ks[6], (nh, )))


def _definition(S, column, live, fresh):
    """What ``Mamba2`` computes where the kernel is not taken."""
    x, dt, a, Bm, Cm, D = column
    per_head = lambda v: jnp.repeat(v, x.shape[1] // Bm.shape[1], axis=1)
    start = jnp.where(fresh[:, None, None, None], 0.0, S.astype(f32))
    y, new = mamba2.ssd_step(start, x, dt, a, per_head(Bm), per_head(Cm), D)
    return y, jnp.where(live[:, None, None, None], new.astype(S.dtype), S)


def _stores(S, column, fresh):
    """The definition's store, ``a S + dx B`` in float32 rounded once to the
    rest dtype, the three ways a compiler may evaluate it: two roundings
    (the chip, which has no fused multiply-add: the layer-alone runs read
    the kernel's leaf equal to XLA's bit for bit there, PERF.md section 6),
    or either product fused into the sum (LLVM on the CPU contracts one or
    the other, head by head, in the kernel's body and in the definition's
    fusion alike)."""
    x, dt, a, Bm, _, _ = column
    f64 = np.float64
    S = np.where(np.asarray(fresh)[:, None, None, None], 0.0, np.asarray(S.astype(f32)))
    decay = np.asarray(jnp.exp(dt * a))[..., None, None]
    dx = np.asarray(dt[..., None] * x)[..., None]
    B = np.repeat(np.asarray(Bm), x.shape[1] // Bm.shape[1], axis=1)[:, :, None, :]
    aS, dxB = (decay * S).astype(np.float32), (dx * B).astype(np.float32)
    return (aS + dxB,
            (decay.astype(f64) * S.astype(f64) + dxB.astype(f64)).astype(np.float32),
            (dx.astype(f64) * B.astype(f64) + aS.astype(f64)).astype(np.float32))


def _flags(case, slots=5):
    live, fresh = (jnp.asarray(x[:slots], bool) for x in SPANS[case])
    return live, fresh & live


def _bits(x):
    return np.asarray(x.view(jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("shape, case, dtype", [
    *((shape, "mixed", dtype) for shape in sorted(SHAPES) for dtype in ("bf16", "f32")),
    *((shape, case, "bf16") for shape in sorted(SHAPES) for case in ("every_other", "none_live")),
    ("heads_64x128", "all_live", "f32")])
def test_update_matches_the_definition(shape, case, dtype):
    """The stored leaf EQUAL to the definition's rounded store, every element
    under one of the float32 evaluations of ``_stores`` (and, bf16 at rest,
    all but a ten-thousandth under the one XLA took here); ``y`` within 1e-5
    of the largest value: the read-out is reassociated, ``a (S C) + dx (B .
    C) + D x`` for ``(a S + dx B) C + D x``, each a float32 sum over ``N``
    terms, so the two differ by float32 rounding only; a span-0 slot bit for
    bit."""
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    nh, hd, N, G = SHAPES[shape]
    S, column = _operands(SHAPES[shape], dtype)
    live, fresh = _flags(case)
    assert ssd_step.tiles(S, nh, hd, N, G)
    y, new = jax.jit(ssd_step.ssd_update)(S, *column, live, fresh)
    assert new.shape == S.shape and new.dtype == S.dtype
    assert y.shape == (5, nh, hd) and y.dtype == f32
    alive = np.asarray(live)
    np.testing.assert_array_equal(_bits(new)[~alive], _bits(S)[~alive])
    if not alive.any():
        return
    want_y, want = _definition(S, column, live, fresh)
    assert float(jnp.abs(y - want_y)[alive].max()) < 1e-5 * float(jnp.abs(want_y[alive]).max())
    admitted = np.zeros(S.shape, bool)
    for store in _stores(S, column, fresh):
        admitted |= _bits(jnp.asarray(store).astype(dtype)) == _bits(new)
    assert admitted[alive].all()
    if dtype == jnp.bfloat16:
        assert (_bits(new) != _bits(want))[alive].mean() < 1e-4


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_fresh_row_equals_a_zeroed_slot(shape):
    """A row that starts at position 0 starts from zero whatever the slot
    held, a NaN among it: the kernel does not look at the old bytes."""
    S, column = _operands(SHAPES[shape], jnp.bfloat16)
    live, fresh = _flags("all_live")
    poisoned = S.at[0, 1, 3, 5].set(jnp.nan).at[0, -1].set(jnp.inf)
    zeroed = jnp.where(fresh[:, None, None, None], 0, S)
    run = jax.jit(ssd_step.ssd_update)
    y_a, new_a = run(poisoned, *column, live, fresh)
    y_b, new_b = run(zeroed, *column, live, fresh)
    np.testing.assert_array_equal(_bits(new_a), _bits(new_b))
    np.testing.assert_array_equal(np.asarray(y_a), np.asarray(y_b))
    assert np.isfinite(np.asarray(y_a)).all()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_aliased_leaf_is_the_output(shape):
    """The state operand is the pallas call's first output: a program that
    donates or carries the pool moves nothing else of it."""
    S, column = _operands(SHAPES[shape], jnp.bfloat16, slots=2)
    live, fresh = _flags("all_live", 2)
    trace = jax.make_jaxpr(functools.partial(ssd_step._update.__wrapped__, interpret=False))
    (call, ) = [e for e in trace(S, *column, live, fresh).jaxpr.eqns
                if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((4, 0), )
    assert call.invars[4].aval.shape == call.outvars[0].aval.shape == S.shape
    assert call.invars[4].aval.dtype == call.outvars[0].aval.dtype == jnp.bfloat16


def test_the_block_of_heads_follows_the_vmem_budget(monkeypatch):
    """The block is whole groups, chosen against the VMEM budget: a smaller
    budget gives more grid steps and the same leaf; under one group's the
    leaf does not tile and the update refuses it."""
    import deepspeed_tpu.ops.pallas as pallas_pkg
    nh, hd, N, G = shape = SHAPES["heads_128x256"]
    S, column = _operands(shape, jnp.bfloat16)
    live, fresh = _flags("mixed")
    update = functools.partial(ssd_step._update.__wrapped__, interpret=True)
    assert ssd_step._groups_a_block(nh, hd, N, G, 2) == 2
    whole = update(S, *column, live, fresh)
    one_group = ssd_step._vmem_estimate(1, nh // G, hd, N, 2)
    assert one_group < ssd_step._vmem_estimate(2, nh // G, hd, N, 2)
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_group)
    assert ssd_step._groups_a_block(nh, hd, N, G, 2) == 1
    split = update(S, *column, live, fresh)
    np.testing.assert_array_equal(_bits(whole[1]), _bits(split[1]))
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(split[0]))
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_group - 1)
    assert not ssd_step.tiles(S, nh, hd, N, G)
    with pytest.raises(ValueError, match="tile"):
        update(S, *column, live, fresh)


@pytest.mark.parametrize("leaf, dtype, heads, tiles", [
    ((192, 64, 64, 128), jnp.bfloat16, (64, 64, 128, 8), True),  # cell 7's
    ((64, 32, 128, 256), jnp.bfloat16, (32, 128, 256, 2), True),  # cell 11's
    ((64, 32, 128, 256), jnp.float32, (32, 128, 256, 2), True),
    ((4, 4, 8, 16), jnp.float32, (4, 8, 16, 2), False),  # tiny-nemotron-h's, tiny-falcon-h1's
    ((4, 4, 8, 16), jnp.bfloat16, (4, 8, 16, 2), False),
    ((4, 16, 8, 128), jnp.bfloat16, (16, 8, 128, 1), False),  # half a bf16 sublane tile
    ((4, 16, 8, 128), jnp.float32, (16, 8, 128, 1), True),  # ... a whole float32 one
    ((4, 16, 64, 64), jnp.bfloat16, (16, 64, 64, 8), False),  # N half a lane tile
    ((4, 16, 64, 128), jnp.float16, (16, 64, 128, 8), False),
    ((4, 16, 64, 128), jnp.bfloat16, (16, 64, 128, 16), False),  # a group's channels half a tile
    ((4, 16, 64, 128), jnp.bfloat16, (16, 64, 128, 3), False),  # heads no multiple of the groups
    ((4, 1, 16, 64, 128), jnp.bfloat16, (16, 64, 128, 8), False),  # not the plain leaf
], ids=lambda v: None)
def test_tiles_reads_the_leaf(leaf, dtype, heads, tiles):
    assert ssd_step.tiles(jax.ShapeDtypeStruct(leaf, dtype), *heads) is tiles


def _layer_model(nh, hd, N, G, attention_impl):
    """``tiny-nemotron-h`` cut to a Mamba-2 layer and an attention layer (no experts),
    with Mamba-2 heads of the given shape."""
    cfg = get_model("tiny-nemotron-h", dtype=jnp.float32).cfg
    return tfm.CausalLMModel(dataclasses.replace(
        cfg, num_layers=2, layer_types=("mamba2", "attention"), num_experts=0,
        ssm_num_heads=nh, ssm_head_dim=hd, ssm_state_size=N, ssm_groups=G,
        attention_impl=attention_impl, max_seq_len=32))


@pytest.mark.parametrize("heads, kernel", [((2, 64, 128, 1), True), ((4, 8, 16, 2), False)],
                         ids=["tiles", "falls_back"])
def test_the_layer_takes_the_kernel_by_shape(heads, kernel):
    """``Mamba2``'s decode column: the kernel where the leaf tiles and the
    layer would take the paged kernels, the definition elsewhere, the same
    numbers either way; the tally says which."""
    model, plain = _layer_model(*heads, "flash"), _layer_model(*heads, "xla")
    params = jax.jit(model.init_params)(jax.random.key(0))
    pool = jax.jit(lambda: jax.tree_util.tree_map(
        lambda x: 0.1 * jax.random.normal(jax.random.key(1), x.shape, x.dtype),
        model.init_cache(3, 32)))()
    ids = jnp.asarray([[5], [7], [9]], jnp.int32)
    heads_at, spans = jnp.asarray([4, 0, 9], jnp.int32), jnp.asarray([1, 1, 0], jnp.int32)
    outs = []
    for m in (model, plain):
        before = ssd_step.traced()
        outs.append(jax.jit(lambda params, pool, m=m: m.apply_with_cache(
            params, ids, pool, 0, position_ids=heads_at[:, None], write_index=heads_at,
            q_spans=spans))(params, pool))
        took = tuple(a - b for a, b in zip(ssd_step.traced(), before))
        assert took == ((1, 0) if kernel and m is model else (0, 1))
    (logits, cache), (want_logits, want_cache) = outs
    np.testing.assert_allclose(np.asarray(logits[:2]), np.asarray(want_logits[:2]),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
