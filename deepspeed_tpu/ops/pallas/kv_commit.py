"""In-place commit of fresh K/V rows into the slot-paged serving pool (TPU).

The serving step writes each slot's new keys and values at that slot's own
write head: column ``j`` of row ``i`` lands at position ``write_index[i] +
j`` when ``j < q_spans[i]`` and the position lies inside the pool; every
other byte of the pool stays as it was. As an XLA scatter
(``c.at[:, t, :].set(..., mode="drop")`` under ``vmap``) that write wants
the pool in a layout of its own, and the paged attention kernels want it
row-major, so the compiler relays the whole pool around every commit. This
kernel aliases the pool (``input_output_aliases``) and takes it row-major,
as the attention kernels do, so a step's loop carries one layout and moves
only the row blocks under the write heads.

Kernel shape: one call for a layer's K/V leaves, grid ``(slots, kv-head
blocks, row blocks)``: the split pool's K and V, two operands and two
aliased outputs, or the packed pool's ONE leaf ``(N, nkv, S, 2 * hd)`` (a
row's key in lanes ``[0, hd)``, its value after it: ``CausalLMModel.
init_cache`` at head size 64), one operand, one aliased output and blocks
that fill their 128 lanes. ``write_index`` and ``q_spans`` are scalar-prefetch
operands; the index map turns them into the row block (one sublane tile: 8
rows of f32, 16 of bf16, 32 of int8) that a step reads, patches under a
mask on the absolute position, and writes back. A span of ``C`` columns
can straddle ``(C - 2) // rows + 2`` blocks; steps past a slot's last live
block hold that block's index, so nothing more is copied and the block gets
the same bytes again. A slot with span 0 rewrites one block unchanged.

For ``C > 1`` the fresh rows are shifted to the block's rows through an f32
staging buffer (an unaligned dynamic slice of sublanes is a 32-bit
operation on the chip); bf16 and int8 values pass through f32 unchanged.

The latent leaf rests position-last, ``(N, 1, D, S)``, and has a kernel of
its own at the end of this file (:func:`commit_kv_columns`): the same write,
a position a column, 128 positions a lane block. The two share the rule that
picks a slot's blocks and the tally, nothing else.
"""

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas


# Span commits traced by this thread, (in place, scatter): a scheduler reads
# it around a dispatch to learn which commit a program was built with.
_traced = threading.local()


def tally(in_place):
    """Note one traced span commit and the path it took."""
    n = traced()
    _traced.counts = (n[0] + 1, n[1]) if in_place else (n[0], n[1] + 1)


def traced():
    return getattr(_traced, "counts", (0, 0))


def _pad(n, m):
    return -(-n // m) * m


def block_rows(dtype):
    """Rows of one sublane tile of ``dtype``: the row block a commit moves."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def commits_in_place(leaf):
    """Whether a pool leaf is one this kernel writes: ``(N, nkv, S, lanes)``
    (split K or V, or the packed pair) with ``S`` a whole number of row
    blocks."""
    if leaf.ndim != 4 or jnp.dtype(leaf.dtype).itemsize not in (1, 2, 4):
        return False
    return leaf.shape[2] % block_rows(leaf.dtype) == 0


def _vmem_estimate(n, bh, rows, C, hd, itemsize):
    """VMEM bytes of one grid step over ``n`` leaves of ``hd`` lanes (2 x
    head size, or 1 x twice the head size packed), counted as Mosaic lays
    blocks out (the last dimension pads to 128 lanes, pipelined operands are
    double-buffered): pool blocks in and out, the fresh rows, the f32
    staging buffers of a span, and about two f32 copies of a block."""
    hdp = _pad(hd, 128)
    io = n * 2 * 2 * bh * rows * hdp * itemsize
    io += n * 2 * bh * _pad(C, rows) * hdp * itemsize
    stage = n * bh * (C + 2 * rows) * hdp * 4 if C > 1 else 0
    return io + stage + n * 2 * bh * rows * hdp * 4


def _row_block(wi, span, j, rows, n_blocks):
    """The pool row block that step ``j`` of a slot works on: the block
    under the write head and the ones after it, held at the last block with
    a live target, and inside the pool (a head at or past ``S`` writes
    nothing, whichever block it is given)."""
    last = (wi + jnp.maximum(span, 1) - 1) // rows
    return jnp.clip(jnp.minimum(wi // rows + j, last), 0, n_blocks - 1)


def _commit_kernel(wi_ref, span_ref, *refs, n, rows, cols, n_blocks):
    """``refs``: the fresh rows of the ``n`` leaves, their pool blocks, the
    aliased output blocks and (for a span) their staging buffers."""
    new_refs, pool_refs, out_refs, stages = (refs[k * n:(k + 1) * n] for k in range(4))
    i = pl.program_id(0)
    j = pl.program_id(2)
    wi = wi_ref[i]
    span = span_ref[i]
    base = _row_block(wi, span, j, rows, n_blocks) * rows
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
    live = (pos >= wi) & (pos < wi + span)
    # stage row t holds fresh column t - rows, so block row r (position
    # base + r, column base + r - wi) is stage row off + r; the rows beside
    # the columns are never selected
    off = jnp.clip(base - wi + rows, 0, cols + rows)
    for new_ref, pool_ref, out_ref, stage in zip(
            new_refs, pool_refs, out_refs, stages or (None, ) * n):
        if cols == 1:
            fresh = new_ref[0]  # (bh, 1, hd): one row for every position
        else:
            @pl.when(j == 0)
            def _stage(new_ref=new_ref, stage=stage):
                stage[:, rows:rows + cols, :] = new_ref[0].astype(jnp.float32)

            fresh = stage[:, pl.ds(off, rows), :]
            if jnp.issubdtype(out_ref.dtype, jnp.integer):
                fresh = fresh.astype(jnp.int32)
            fresh = fresh.astype(out_ref.dtype)
        out_ref[0] = jnp.where(live, fresh, pool_ref[0])


def commit_kv_rows(leaves, new_rows, write_index, q_spans):
    """Write a layer's fresh K and V rows into its pool leaves, in place.

    ``leaves``: the layer's K/V leaves, ``(k_pool, v_pool)`` of ``(N, nkv,
    S, hd)`` each or the packed ``(kv_pool, )`` of ``(N, nkv, S, 2 * hd)``;
    ``new_rows``: the fresh rows in the same form, ``(N, nkv, C, lanes)``
    each (cast to the pool's dtype here; a packed row is its key and its
    value joined on the last axis); ``write_index``, ``q_spans``: ``(N,)``
    int32, heads non-negative. Row ``i``'s column ``j`` lands at
    ``write_index[i] + j`` when ``j < q_spans[i]`` and that position is
    ``< S`` — the pool the ``mode="drop"`` scatter leaves, byte for byte.
    Returns the new leaves as a tuple; the operands are aliased to them, so
    inside a program that donates or carries the pool nothing else of it
    moves.

    Jitted, so that the layers of a step program (and its first forward
    and loop body) share one trace and one lowering of the kernel: lowering
    a Mosaic call is a tenth of a second, 36 to 72 times a program."""
    return _commit(tuple(leaves), tuple(new_rows), write_index, q_spans,
                   interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _commit(leaves, new_rows, write_index, q_spans, *, interpret):
    pool = leaves[0]
    new_rows = tuple(x.astype(pool.dtype) for x in new_rows)
    n = len(leaves)
    N, nkv, S, hd = pool.shape
    C = new_rows[0].shape[2]
    rows = block_rows(pool.dtype)
    itemsize = jnp.dtype(pool.dtype).itemsize
    if (not commits_in_place(pool) or len(new_rows) != n
            or any(c.shape != pool.shape or c.dtype != pool.dtype for c in leaves)
            or any(x.shape != (N, nkv, C, hd) for x in new_rows)):
        raise ValueError(f"kv commit: pool leaves {[c.shape for c in leaves]} "
                         f"are not whole {rows}-row blocks of one shape, or the "
                         f"fresh rows {[x.shape for x in new_rows]} are not theirs")
    bh = next((b for b in range(nkv, 0, -1) if nkv % b == 0
               and _pallas.fits_vmem(_vmem_estimate(n, b, rows, C, hd, itemsize))), None)
    if bh is None:
        raise ValueError(
            f"kv commit: one kv head x {C} columns of width {hd} needs "
            f"{_vmem_estimate(n, 1, rows, C, hd, itemsize)} bytes of VMEM, over the "
            f"{_pallas.VMEM_BLOCK_BUDGET}-byte budget; narrow the query span "
            f"(prefill_chunk)")
    n_blocks = S // rows
    steps = (C - 2) // rows + 2  # row blocks a span of C can straddle

    def pool_index(i, h, j, wi_r, span_r):
        return (i, h, _row_block(wi_r[i], span_r[i], j, rows, n_blocks), 0)

    new_spec = pl.BlockSpec((1, bh, C, hd), lambda i, h, j, *_: (i, h, 0, 0))
    pool_spec = pl.BlockSpec((1, bh, rows, hd), pool_index)
    stage = pltpu.VMEM((bh, C + 2 * rows, hd), jnp.float32)
    pool_shape = jax.ShapeDtypeStruct(pool.shape, pool.dtype)
    return tuple(pl.pallas_call(
        functools.partial(_commit_kernel, n=n, rows=rows, cols=C, n_blocks=n_blocks),
        name="dstpu_kv_commit",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, nkv // bh, steps),
            in_specs=[new_spec] * n + [pool_spec] * n,
            out_specs=[pool_spec] * n,
            scratch_shapes=[stage] * n if C > 1 else [],
        ),
        out_shape=[pool_shape] * n,
        # operand numbers count the two scalar-prefetch operands
        input_output_aliases={2 + n + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(write_index.astype(jnp.int32), q_spans.astype(jnp.int32),
      *new_rows, *leaves))


# ------------------------------------------------------- the latent columns
LANES = 128  # positions of one lane block: what a column commit moves


def _fresh_offset(i, wi, block, cols):
    """Where, in the flat fresh columns (``LANES`` of padding, then slot
    ``i``'s column ``c`` at ``i * cols + c``), the column of lane 0 of the
    slot's lane block ``block`` stands: the block is the write head's or a
    later one, so the padding covers it (but for a head past the pool, which
    writes nothing: held at 0)."""
    return jnp.maximum(LANES + i * cols + block * LANES - wi, 0)


def _column_kernel(wi_ref, span_ref, lo_ref, hi_ref, pool_ref, out_ref, *, cols, n_blocks):
    """``lo_ref``, ``hi_ref``: the two aligned lane blocks of the flat fresh
    columns that the pool block's 128 columns straddle."""
    i = pl.program_id(0)
    wi = wi_ref[i]
    span = span_ref[i]
    block = _row_block(wi, span, pl.program_id(1), LANES, n_blocks)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    pos = block * LANES + lane
    live = (pos >= wi) & (pos < wi + span)
    # lane l takes flat column off + l: both blocks turned left by off mod 128
    # (a rotation of lanes is a 32-bit operation; bf16 passes through f32
    # unchanged), the low block's tail then the high block's head
    head = LANES - _fresh_offset(i, wi, block, cols) % LANES  # lanes the low block fills
    turned = lambda ref: pltpu.roll(ref[...].astype(jnp.float32), head % LANES, 1)
    fresh = jnp.where(lane < head, turned(lo_ref), turned(hi_ref))
    out_ref[0, 0] = jnp.where(live, fresh.astype(out_ref.dtype), pool_ref[0, 0])


def commit_kv_columns(leaf, new_rows, write_index, q_spans):
    """:func:`commit_kv_rows` for a leaf that rests POSITION-LAST, the latent
    leaf ``(N, 1, D, S)`` (``CausalLMModel.cache_spec``'s ``"columns"``): a
    position is a column of ``D`` values down the sublanes, and ``S`` is a
    whole number of 128-lane blocks. ``new_rows``: ``(N, 1, C, D)``, as the
    projections make them. Row ``i``'s column ``j`` lands at position
    ``write_index[i] + j`` when ``j < q_spans[i]`` and that position is ``<
    S``; every other byte stays, a dead slot's (span 0) and a retained
    prefix's among them: what ``.at[:, :, t].set(..., mode="drop")`` leaves,
    byte for byte, without the layout that scatter wants the whole leaf in.

    Kernel shape: grid ``(slots, lane blocks a span of C can straddle)``; a
    step reads the 128-column block under the write head (or a later one,
    held at the last block with a live target as the row kernel holds its
    own), sets the live lanes and writes it back through the alias. The
    fresh columns come transposed and flat, ``(D, 128 + N * C)`` and some
    padding: a block's 128 columns are 128 consecutive flat ones at an
    offset that is no multiple of 128, so a step takes the two aligned
    blocks around it and turns them."""
    return _commit_columns(leaf, new_rows, write_index, q_spans, interpret=_pallas.interpret())


def commits_columns_in_place(leaf):
    """Whether a position-last leaf is one :func:`commit_kv_columns` writes."""
    return (leaf.ndim == 4 and leaf.shape[1] == 1 and leaf.shape[3] % LANES == 0
            and jnp.dtype(leaf.dtype).itemsize in (2, 4))


@functools.partial(jax.jit, static_argnames="interpret")
def _commit_columns(leaf, new_rows, write_index, q_spans, *, interpret):
    N, _, D, S = leaf.shape
    C = new_rows.shape[2]
    if not commits_columns_in_place(leaf) or new_rows.shape != (N, 1, C, D):
        raise ValueError(f"kv commit: the leaf {leaf.shape} is not (slots, 1, width, whole "
                         f"{LANES}-position blocks), or the fresh rows {new_rows.shape} are "
                         f"not its columns")
    n_blocks = S // LANES
    steps = (C - 2) // LANES + 2  # lane blocks a span of C can straddle
    flat = new_rows.astype(leaf.dtype).reshape(N * C, D).T
    flat = jnp.pad(flat, ((0, 0), (LANES, _pad(N * C, LANES) - N * C + LANES)))
    last = flat.shape[1] // LANES - 2

    def block_of(i, j, wi_r, span_r):
        return _row_block(wi_r[i], span_r[i], j, LANES, n_blocks)

    def fresh_index(side):
        def index(i, j, wi_r, span_r):
            off = _fresh_offset(i, wi_r[i], block_of(i, j, wi_r, span_r), C)
            return (0, jnp.minimum(off // LANES, last) + side)
        return index

    def pool_index(i, j, wi_r, span_r):
        return (i, 0, 0, block_of(i, j, wi_r, span_r))

    pool_spec = pl.BlockSpec((1, 1, D, LANES), pool_index)
    # the result is HBM's: a leaf that fits VMEM (cell 4's 84 MB a layer) is
    # otherwise parked there around the steps' loop, and moved out and in
    # again every step, whole (3.5% of cell 4's window: PERF.md, PR 55)
    pool_shape = pltpu.HBM(leaf.shape, leaf.dtype)
    return pl.pallas_call(
        functools.partial(_column_kernel, cols=C, n_blocks=n_blocks),
        name="dstpu_kv_commit_columns",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, steps),
            in_specs=[pl.BlockSpec((D, LANES), fresh_index(0)),
                      pl.BlockSpec((D, LANES), fresh_index(1)), pool_spec],
            out_specs=pool_spec,
        ),
        out_shape=pool_shape,
        input_output_aliases={4: 0},  # operand numbers count the two scalar-prefetch operands
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(write_index.astype(jnp.int32), q_spans.astype(jnp.int32), flat, flat, leaf)
