"""Mamba-2's ONE-TOKEN state update, in place (TPU).

What ``models/mamba2.py: ssd_step`` defines, per head of ``hd`` channels and
``N`` states, with ``a = exp(dt a_h)``, ``dx = dt x`` and ``B``, ``C`` the
head's group's:

    S' = a S + dx (x) B ;  y = S' C + D x

over a slot pool's state leaf at rest, ``(slots, nh, hd, N)`` (``N`` in the
lanes). As XLA compiles the definition the leaf is read twice and written
once a layer call (one fusion for ``S'``, one for ``S' C``). This kernel
aliases the leaf (``input_output_aliases``), loads a live slot's state from
its rest dtype ONCE, computes in float32, rounds once on the store; a slot
with span 0 is neither read nor written, a fresh row (span > 0 at position
0) starts from zero and its old bytes are never looked at. The stored state
is the definition's, bit for bit: ``a S`` and ``dx B`` each rounded to
float32, their sum rounded to float32, one rounding to the rest dtype.

Kernel shape: grid ``(head blocks, steps)``, one step a slot, the LIVE slots
first (``order``, a scalar-prefetch operand the index maps read), as in
``gdn_step.py``. A block is a whole number of groups of heads, the largest
that divides ``nh`` and fits the VMEM budget (both published shapes: the
whole slot, 1 MB and 2 MB at rest); its body walks the block in pieces of
128 rows (``hd`` channels of 128 / ``hd`` heads), unrolled.

The read-out takes no second pass and no lane reduction: ``y = a (S C) + dx
(B . C) + D x``, and the kernel returns ``S C``. ``S`` at rest in bf16 is
exact in bf16, so with ``C`` split into three bf16 parts (8 + 8 + 8
significant bits, cut by a mask) ``[C_hi ; C_mid ; C_lo] S^T`` is a sum of
exact products accumulated in float32: one MXU product a piece, the piece
entering transposed as a key block does in decode attention, its result
three rows of 128 LANES, which is how ``y`` rests. A float32 state at rest
is split the same way. ``dx`` enters as lanes too and has to scale ROWS of
the piece: its three parts times a tile of ones, contracted over the parts,
is ``dx`` broadcast along the lanes, exactly, and that product the MXU
transposes. What is left to the VPU an element is the widening load, ``a
S`` (``a`` a scalar from SMEM), the multiply by ``B`` (a row broadcast
along sublanes), the sum and the rounding store. The other read-out, ``S'
C`` as a multiply and a lane reduction a vreg on the VPU and XLU, was not
built: with the read-out product taken out the kernel reads the same time
(below), so nothing on the VPU's side could beat it.

The layer alone at both served shapes, bf16 at rest, ms a call on one v5e
(my chip runs, PR 57, before any cell was run: a loop of 24 calls on a
donated leaf, ``x``, ``dt``, ``B``, ``C`` changing every call so that what
prepares the operands is inside, best of 5; XLA: the definition as
``Mamba2`` calls it where the kernel is not taken, which passes over every
slot whatever lives; least time = the live states' bytes once in, once out
at 819 GB/s):

    cell 7's leaf (192, 64, 64, 128), 8 groups; a slot 1,048,576 B
    slots live               16      64      192     96, every other one
    least time               0.041   0.164   0.492   0.246
    XLA, two passes          1.098   1.091   1.097   1.108
    this kernel              0.138   0.285   0.694   0.389
    ... without the read-out 0.137   0.289   0.688   0.394
    ... and without dx's     0.138   0.289   0.697   0.388

    cell 11's leaf (64, 32, 128, 256), 2 groups; a slot 2,097,152 B
    slots live               16      64      32, every other one
    least time               0.082   0.328   0.164
    XLA, two passes          0.640   0.638   0.636
    this kernel              0.169   0.471   0.262
    ... without the read-out 0.165   0.467   0.265
    ... and without dx's     0.163   0.469   0.271

With every slot live the state moves at 580 GB/s (cell 7's shape) and 570
(cell 11's), 71% and 70% of the least time's 819, where XLA's two passes
read 45% and 51%; a plain elementwise pass reads 590 (guide
``on-chip-measurement``). ISSUE 57 predicted 0.64-0.76 and 0.42-0.50 ms.
What binds it is the DMA: both products taken out, the time is the same to
a hundredth. One block a slot serves both shapes (a slot's step moves 2 or 4
MB against ~0.35 us of a grid step's own), so ``tiles`` excludes neither.
The stored leaf was XLA's bit for bit at both shapes on the chip (two thirds
of the slots live, a fifth of them fresh; the v5e has no fused
multiply-add), ``y`` within 1.6e-7 of the largest value. On the CPU LLVM
contracts one product or the other into the sum, head by head, in this
body and in the definition's fusion alike: ``tests/unit/ops/
test_ssd_step.py`` admits the three float32 evaluations.

The leaf's result is an ordinary array, not ``pltpu.HBM`` as
``kv_commit.py``'s column commit declares its own: both served leaves are
VMEM's size or more (128 MiB and 192) and the compiled syncs neither move
nor park them (``test_tpu_compile.py: _leaf_moves``), while with the
colouring the compiler's memory-space assignment ABORTS on a small aliased
leaf (8 slots of 16 heads of 8 x 128 in float32, described v5e: "Conflicting
pending required assignment ... in alternate memory space"). A leaf between
the two sizes may be parked in VMEM around a steps' loop (PERF.md, PR 55):
slower, never wrong.
"""

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas
from .gdn_step import LANES, _pad, _split3

PART_ROWS = 16  # one bf16 sublane tile: the three parts of a row operand, padded

# One-token updates traced by this thread, (kernel, xla): a scheduler reads
# it around a dispatch to learn which update a program was built with.
_traced = threading.local()


def tally(kernel):
    """Note one traced one-token update and the path it took."""
    n = traced()
    _traced.counts = (n[0] + 1, n[1]) if kernel else (n[0], n[1] + 1)


def traced():
    return getattr(_traced, "counts", (0, 0))


def _vmem_estimate(gb, gh, hd, N, itemsize):
    """VMEM bytes of one grid step over ``gb`` groups of ``gh`` heads of
    ``(hd, N)``, counted as Mosaic lays blocks out (pipelined operands are
    double-buffered): the state block in and out, the rows of ``dx`` and of
    ``S C``, the rows of ``B`` and ``C``, and about six float32 copies of ONE
    piece of 128 rows (the body walks the pieces)."""
    io = 2 * 2 * gb * gh * hd * N * itemsize
    rows = 2 * 2 * _pad(gb, 8) * gh * hd * 4
    bc = 2 * gb * 8 * N * 4
    return io + rows + bc + 6 * LANES * N * 4


def _groups_a_block(nh, hd, N, G, itemsize):
    """Groups of heads a grid step: the largest count that divides ``G`` and
    fits the VMEM budget; None where one group does not fit."""
    return next((gb for gb in range(G, 0, -1) if G % gb == 0
                 and _pallas.fits_vmem(_vmem_estimate(gb, nh // G, hd, N, itemsize))), None)


def tiles(leaf, nh, hd, N, G):
    """Whether ``leaf`` is a state leaf this kernel updates: ``(slots, nh, hd,
    N)`` in bf16 or float32, ``N`` whole 128-lane tiles, ``hd`` a whole
    number of the dtype's sublane tiles and a divisor of 128 or a multiple
    (a piece of 128 rows is whole heads or a part of one), ``nh`` a multiple
    of ``G`` groups whose ``nh / G * hd`` channels are whole lane tiles, one
    group inside the VMEM budget."""
    if leaf.ndim != 4 or leaf.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    itemsize = jnp.dtype(leaf.dtype).itemsize
    return (leaf.shape[1:] == (nh, hd, N) and N % LANES == 0
            and hd % (8 * (4 // itemsize)) == 0 and (LANES % hd == 0 or hd % LANES == 0)
            and nh % G == 0 and (nh // G * hd) % LANES == 0
            and _groups_a_block(nh, hd, N, G, itemsize) is not None)


def _part_rows(row):
    """``row`` (1, n) float32 as one bf16 tile ``(PART_ROWS, n)``: its three
    parts, then zeros."""
    parts = jnp.concatenate(_split3(row), axis=0)
    return jnp.pad(parts, ((0, PART_ROWS - 3), (0, 0))).astype(jnp.bfloat16)


def _step_kernel(order_ref, count_ref, fresh_ref, a_ref, s_ref, dx_ref, bc_ref,
                 out_ref, sc_ref, *, nh, gh, hd, gb):
    """Step ``t``'s slot ``order[t]``, a block of ``gb`` groups of it.
    ``a_ref`` (SMEM): ``a`` of every head, ``(slots nh,)``; ``dx_ref`` /
    ``sc_ref``: ``(gb, gh hd)`` rows, a group's channels in the lanes;
    ``bc_ref``: ``(gb, 2, N)``, a group's ``B`` then its ``C``."""
    t = pl.program_id(1)
    i = order_ref[t]
    base = i * nh + pl.program_id(0) * gb * gh
    N = s_ref.shape[-1]
    f32 = jnp.float32
    ones = jnp.ones((PART_ROWS, N), jnp.bfloat16)
    exact = s_ref.dtype == jnp.bfloat16
    hp = max(LANES // hd, 1)  # heads a piece
    pieces = gh * hd // LANES  # pieces a group

    def group(g, fresh):
        B = bc_ref[0, g, 0:1, :]
        dx3 = _part_rows(dx_ref[0, 0, g:g + 1, :])
        if not fresh:
            c3 = _part_rows(bc_ref[0, g, 1:2, :])
        for c in range(pieces):
            lanes = slice(c * LANES, (c + 1) * LANES)
            # dx of the piece's 128 rows along the lanes: (128, N)
            DX = jax.lax.dot_general(dx3[:, lanes], ones, (((0, ), (0, )), ((), ())),
                                     preferred_element_type=f32)
            if hd >= LANES:  # a part of one head
                head, r0 = g * gh + c * LANES // hd, c * LANES % hd
                if fresh:
                    out_ref[0, head, r0:r0 + LANES, :] = (DX * B).astype(out_ref.dtype)
                    continue
                S = s_ref[0, head, r0:r0 + LANES, :]
                new = a_ref[base + head] * S.astype(f32) + DX * B
                out_ref[0, head, r0:r0 + LANES, :] = new.astype(out_ref.dtype)
            else:  # hp whole heads
                head = g * gh + c * hp
                if fresh:
                    out_ref[0, head:head + hp] = (DX * B).reshape(hp, hd, N).astype(out_ref.dtype)
                    continue
                S = s_ref[0, head:head + hp].reshape(LANES, N)
                for j in range(hp):
                    rows = slice(j * hd, (j + 1) * hd)
                    new = a_ref[base + head + j] * S[rows].astype(f32) + DX[rows] * B
                    out_ref[0, head + j] = new.astype(out_ref.dtype)
            # [C_hi ; C_mid ; C_lo] S^T: three rows of 128 lanes
            R = sum(jax.lax.dot_general(c3, part, (((1, ), (1, )), ((), ())),
                                        preferred_element_type=f32)
                    for part in ((S, ) if exact else _split3(S, jnp.bfloat16)))
            sc_ref[0, 0, g:g + 1, lanes] = R[0:1] + R[1:2] + R[2:3]
        if fresh:
            sc_ref[0, 0, g:g + 1, :] = jnp.zeros((1, gh * hd), f32)

    live = t < count_ref[0]
    fresh = fresh_ref[i] > 0

    def walk(fresh):
        # unrolled: the pieces' loads, products and stores overlap
        # (gdn_step.py: a rolled loop took two fifths longer)
        for g in range(gb):
            group(g, fresh)

    pl.when(live & fresh)(lambda: walk(True))
    pl.when(live & ~fresh)(lambda: walk(False))

    # past the live slots every step holds the last one's blocks and does
    # nothing. With none live, step 0's block (slot 0's) would be written
    # back as it stands in VMEM: give it its own bytes
    @pl.when((t == 0) & ~live)
    def _nothing_lives():
        out_ref[...] = s_ref[...]


def ssd_update(state, x, dt, a, Bm, Cm, D, live, fresh):
    """One token of the Mamba-2 recurrence on the state leaf, in place.

    ``state``: the leaf at rest, ``(slots, nh, hd, N)`` (``tiles`` holds);
    ``x`` (slots, nh, hd), ``dt`` (slots, nh), ``a``/``D`` (nh,), float32, as
    ``ssd_step`` takes them; ``Bm``/``Cm`` (slots, G, N), a row a GROUP;
    ``live``, ``fresh``: (slots,) bool. Slot ``i`` advances when ``live[i]``
    (from zero when ``fresh[i]`` too) and is left bit for bit otherwise.
    Returns ``(y (slots, nh, hd) float32, new leaf)``; the leaf operand is
    aliased to the new one.

    Jitted, so that the layers of a step program share one trace and one
    lowering of the kernel, as ``kv_commit`` does."""
    return _update(state, x, dt, a, Bm, Cm, D, live, fresh, interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _update(state, x, dt, a, Bm, Cm, D, live, fresh, *, interpret):
    slots, nh, hd = x.shape
    G, N = Bm.shape[1:]
    itemsize = jnp.dtype(state.dtype).itemsize
    if not tiles(state, nh, hd, N, G) or state.shape[0] != slots:
        raise ValueError(f"ssd step: leaf {state.shape} {state.dtype} is not {slots} slots of "
                         f"{nh} heads of ({hd}, {N}) in {G} groups that tile")
    gh = nh // G
    gb = _groups_a_block(nh, hd, N, G, itemsize)
    f32 = jnp.float32
    x, dt, Bm, Cm = (v.astype(f32) for v in (x, dt, Bm, Cm))
    # live slots first, in slot order; every later step repeats the last
    # live slot (slot 0 if none lives), so no block changes under it
    idx = jnp.arange(slots, dtype=jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)
    rank = jnp.cumsum(live, dtype=jnp.int32) - 1
    step = jnp.minimum(idx, jnp.maximum(count - 1, 0))
    order = jnp.sum(jnp.where(live[None, :] & (rank[None, :] == step[:, None]), idx[None, :], 0),
                    axis=1)
    decay = jnp.exp(dt * a)  # (slots, nh)
    dx = dt[..., None] * x
    blocks = G // gb
    rows = dx.reshape(slots, blocks, gb, gh * hd)
    bc = jnp.stack([Bm, Cm], axis=2)  # (slots, G, 2, N)

    at_slot = lambda h, t, order_r, *_: (order_r[t], h, 0, 0)
    state_spec = pl.BlockSpec((1, gb * gh, hd, N), at_slot)
    row_spec = pl.BlockSpec((1, 1, gb, gh * hd), at_slot)
    bc_spec = pl.BlockSpec((1, gb, 2, N), at_slot)
    new, sc = pl.pallas_call(
        functools.partial(_step_kernel, nh=nh, gh=gh, hd=hd, gb=gb),
        name="dstpu_ssd_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks, slots),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state_spec, row_spec, bc_spec],
            out_specs=[state_spec, row_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(rows.shape, f32)],
        # operand numbers count the three scalar-prefetch operands
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(order, count[None], fresh.astype(jnp.int32), decay.reshape(-1), state, rows, bc)
    # a span-0 slot's rows of S C were never written
    sc = jnp.where(live[:, None, None], sc.reshape(slots, nh, hd), 0.0)
    b_dot_c = jnp.repeat(jnp.sum(Bm * Cm, axis=-1), gh, axis=1)  # (slots, nh)
    return decay[..., None] * sc + (b_dot_c[..., None] * dx + D[:, None] * x), new
