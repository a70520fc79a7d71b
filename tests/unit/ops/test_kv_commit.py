"""The in-place K/V commit kernel (``ops/pallas/kv_commit.py``, interpret
mode) against the ``vmap`` scatter it stands in for: the whole pool, bit
for bit, in both geometries a per-head pool rests in: split (a K and a V
leaf) and packed (one leaf, a row's key and value side by side). And the
column kernel of the latent leaf, which rests position-last, against the
bytes the scatter left in the leaf as it was shaped until PR 55."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import kv_commit

N, NKV, S, HD = 6, 3, 128, 64


def _scatter(pools, fresh, write_index, q_spans):
    """The span write where the kernel is not taken: the model's own scatter."""
    from deepspeed_tpu.models.transformer import _commit_span_rows
    return _commit_span_rows(list(zip(pools, fresh)), write_index, q_spans,
                             paged_kernels=False)


def _pack(leaves):
    """The packed form of a split (K, V) pair: one leaf, keys then values."""
    return (jnp.concatenate(leaves, axis=-1), )


def _cases(C, rows):
    """(write heads, spans) per named case; six slots each, the untouched
    ones (span 0) among them."""
    full = [C] * N
    return {
        "span0": ([0, rows, 5, S - 1, 40, 7], [0] * N),
        "span1": ([0, rows - 1, rows, S - 1, 2 * rows + 3, 7], [1, 1, 1, 1, 0, 1]),
        "partial": ([3, rows - 1, rows, 40, 2 * rows + 1, 0],
                    [max(C // 2, 1), min(C, 5), 0, min(C, rows + 1), 1, C - 1]),
        "full": ([0, rows - 1, rows, 33, 2 * rows - 2, 1], full),
        "block_first_row": ([0, rows, 2 * rows, 3 * rows, 0, rows], full),
        "block_last_row": ([rows - 1, 2 * rows - 1, 3 * rows - 1, rows - 1, S - 1, 0],
                           [C, C, C, 1, 1, 0]),
        "straddle": ([rows - 2, 2 * rows - 1, 3 * rows - 3, rows - 1, 0, 5],
                     [min(C, 4), min(C, 2), min(C, 6), C, 0, C]),
        "past_end": ([S - 1, S - 2, S - rows, S - 1, S, S + 7],
                     [C, C, C, 0, C, min(C, 3)]),
    }


@pytest.mark.parametrize("case", ["span0", "span1", "partial", "full",
                                  "block_first_row", "block_last_row",
                                  "straddle", "past_end"])
@pytest.mark.parametrize("C", [1, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
@pytest.mark.parametrize("form", ["split", "packed"])
def test_commit_matches_scatter(form, dtype, C, case):
    rows = kv_commit.block_rows(dtype)
    rng = np.random.default_rng(C + rows)
    if dtype == jnp.int8:
        make = lambda *shape: jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
    else:
        make = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    pools = make(N, NKV, S, HD), make(N, NKV, S, HD)
    fresh = make(N, NKV, C, HD), make(N, NKV, C, HD)
    heads, spans = (jnp.asarray(x, jnp.int32) for x in _cases(C, rows)[case])
    if form == "packed":
        # ... and the packed commit leaves what the split one does, joined
        split = jax.jit(kv_commit.commit_kv_rows)(pools, fresh, heads, spans)
        pools, fresh = _pack(pools), _pack(fresh)
    got = jax.jit(kv_commit.commit_kv_rows)(pools, fresh, heads, spans)
    assert len(got) == len(pools)
    if form == "packed":
        np.testing.assert_array_equal(np.asarray(got[0].view(jnp.uint8)),
                                      np.asarray(_pack(split)[0].view(jnp.uint8)))
    for pool, out, want in zip(pools, got, _scatter(pools, fresh, heads, spans)):
        np.testing.assert_array_equal(np.asarray(out.view(jnp.uint8)),
                                      np.asarray(want.view(jnp.uint8)))
        idle = np.asarray(spans) == 0
        np.testing.assert_array_equal(np.asarray(out.view(jnp.uint8))[idle],
                                      np.asarray(pool.view(jnp.uint8))[idle])


@pytest.mark.parametrize("form", ["split", "packed"])
def test_commit_splits_heads_to_fit_vmem(monkeypatch, form):
    """A head block is chosen against the VMEM budget; a smaller budget gives
    more grid steps and the same pool. One packed leaf of 128 lanes needs
    half of what a K and a V leaf of 64 (padded to 128) need."""
    import deepspeed_tpu.ops.pallas as pallas_pkg
    rng = np.random.default_rng(0)
    make = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    pools = make(2, 4, 64, 64), make(2, 4, 64, 64)
    fresh = make(2, 4, 8, 64), make(2, 4, 8, 64)
    heads, spans = jnp.asarray([15, 60], jnp.int32), jnp.asarray([8, 8], jnp.int32)
    assert kv_commit._vmem_estimate(2, 1, 16, 8, 64, 2) == 2 * kv_commit._vmem_estimate(
        1, 1, 16, 8, 128, 2)
    if form == "packed":
        pools, fresh = _pack(pools), _pack(fresh)
    commit = functools.partial(kv_commit._commit.__wrapped__, interpret=True)
    whole = commit(pools, fresh, heads, spans)
    one_head = kv_commit._vmem_estimate(len(pools), 1, 16, 8, pools[0].shape[-1], 2)
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_head)
    split = commit(pools, fresh, heads, spans)
    for a, b in zip(whole, split):
        np.testing.assert_array_equal(np.asarray(a.view(jnp.uint8)),
                                      np.asarray(b.view(jnp.uint8)))
    monkeypatch.setattr(pallas_pkg, "VMEM_BLOCK_BUDGET", one_head - 1)
    with pytest.raises(ValueError, match="VMEM"):
        commit(pools, fresh, heads, spans)


def test_commits_in_place_reads_the_leaf():
    sds = jax.ShapeDtypeStruct
    assert kv_commit.commits_in_place(sds((4, 2, 64, 64), jnp.bfloat16))
    assert kv_commit.commits_in_place(sds((4, 2, 64, 64), jnp.int8))
    assert kv_commit.commits_in_place(sds((4, 2, 64, 128), jnp.bfloat16))  # packed
    assert not kv_commit.commits_in_place(sds((4, 2, 48, 64), jnp.int8))   # 32-row blocks
    assert not kv_commit.commits_in_place(sds((4, 2, 20, 64), jnp.float32))
    assert not kv_commit.commits_in_place(sds((4, 64, 1), jnp.float16))


# ------------------------------------------------------- the latent columns
CN, CD, CS = 5, 48, 384  # slots, values a position, positions: three lane blocks


def _column_cases(C):
    """(write heads, spans) per named case, five slots each."""
    return {
        # a decode step: one column a live slot, at any lane of any block
        "one_column": ([0, 127, 128, 200, CS - 1], [1] * CN),
        # a chunk's span: the columns straddle lane blocks from any offset
        "chunk_span": ([0, 1, 127, 129, CS - C], [C, C - 1, C, max(C // 2, 1), C]),
        # a span that runs past S: the columns past the end are dropped
        "past_S": ([CS - 1, CS - 2, CS - C + 1, CS, CS + 7], [C] * CN),
        # dead slots (span 0) beside live ones: not touched, whatever their head
        "dead_slot": ([0, 5, 130, CS - 1, CS + 3], [C, 0, 0, min(C, 2), 0]),
        # a retained prefix: slot 1 holds positions [0, 140) and rides with span
        # 0; slot 2 appends behind its own prefix of 140
        "retained_prefix": ([140, 140, 140, 0, 260], [0, 0, C, 0, 1]),
    }


def _old_scatter(leaf, fresh, heads, spans):
    """What the parent left: the span scatter on the leaf shaped ``(N, 1, S,
    D)`` (``_commit_span_rows`` where no kernel is taken), seen position-last."""
    (rows, ) = _scatter((jnp.swapaxes(leaf, 2, 3), ), (fresh, ), heads, spans)
    return jnp.swapaxes(rows, 2, 3)


@pytest.mark.parametrize("case", ["one_column", "chunk_span", "past_S", "dead_slot",
                                  "retained_prefix"])
@pytest.mark.parametrize("C", [1, 7, 200])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_column_commit_matches_the_old_scatter(dtype, C, case):
    rng = np.random.default_rng(C)
    make = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    leaf, fresh = make(CN, 1, CD, CS), make(CN, 1, C, CD)
    heads, spans = (jnp.asarray(x, jnp.int32) for x in _column_cases(C)[case])
    got = jax.jit(kv_commit.commit_kv_columns)(leaf, fresh, heads, spans)
    want = _old_scatter(leaf, fresh, heads, spans)
    bits = lambda x: np.asarray(x.view(jnp.uint8))
    np.testing.assert_array_equal(bits(got), bits(want))
    idle = np.asarray(spans) == 0
    np.testing.assert_array_equal(bits(got)[idle], bits(leaf)[idle])
    assert case == "dead_slot" or not np.array_equal(bits(got), bits(leaf))


@pytest.mark.parametrize("S", [CS, 96], ids=["kernel", "scatter"])
def test_span_columns_takes_the_kernel_where_it_tiles_the_leaf(S):
    """``_commit_span_columns`` (what ``LatentAttention`` calls): the kernel
    where ``S`` is whole 128-position blocks, the scatter elsewhere; the same
    bytes either way, and the tally says which."""
    from deepspeed_tpu.models.transformer import _commit_span_columns
    rng = np.random.default_rng(S)
    make = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    leaf, fresh = make(3, 1, CD, S), make(3, 1, 9, CD)
    heads, spans = jnp.asarray([0, S - 4, 50], jnp.int32), jnp.asarray([9, 9, 0], jnp.int32)
    before = kv_commit.traced()
    got = _commit_span_columns(leaf, fresh, heads, spans)
    in_place, scatter = (a - b for a, b in zip(kv_commit.traced(), before))
    assert (in_place, scatter) == ((1, 0) if S == CS else (0, 1))
    np.testing.assert_array_equal(np.asarray(got.view(jnp.uint8)),
                                  np.asarray(_old_scatter(leaf, fresh, heads, spans).view(jnp.uint8)))


def test_commits_columns_in_place_reads_the_leaf():
    sds = jax.ShapeDtypeStruct
    assert kv_commit.commits_columns_in_place(sds((192, 1, 576, 4096), jnp.bfloat16))  # cell 10
    assert kv_commit.commits_columns_in_place(sds((64, 1, 320, 2048), jnp.bfloat16))  # cell 4
    assert kv_commit.commits_columns_in_place(sds((2, 1, 24, 128), jnp.float32))
    assert not kv_commit.commits_columns_in_place(sds((2, 1, 24, 96), jnp.float32))
    assert not kv_commit.commits_columns_in_place(sds((2, 2, 24, 128), jnp.float32))
    assert not kv_commit.commits_columns_in_place(sds((2, 1, 24, 128), jnp.int8))
    with pytest.raises(ValueError, match="not its columns"):
        kv_commit.commit_kv_columns(jnp.zeros((2, 1, 24, 128)), jnp.zeros((2, 1, 128, 3)),
                                    jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
