"""The gated delta rule's ONE-TOKEN state update, in place (TPU).

What ``models/transformer.py: gated_delta_step`` defines, per head of ``dk``
key and ``dv`` value dimensions, with ``a = e^g``:

    u = beta (v - a S^T k) ;  S' = a S + k u^T ;  o = S'^T q

over a slot pool's state leaf at rest. As XLA compiles the definition the
leaf is read twice a layer call (one pass for ``S^T k``, one for the update
and the read-out). This kernel aliases the leaf (``input_output_aliases``),
loads a slot's state from its rest dtype ONCE, computes in float32, rounds
once on the store and writes ``o``; a slot with span 0 is neither read nor
written, a fresh row (span > 0 at position 0) starts from zero and its old
bytes are never looked at.

Leaf at rest: ``(B, n / p, dk, p * dv)``, ``p`` heads side by side in the
lanes (``state_packing``: the smallest ``p`` dividing ``n`` that makes ``p *
dv`` a whole number of 128-lane tiles; ``p`` 1 is the plain ``(B, n, dk,
dv)``). A row of fewer than 128 lanes' multiple rests padded in HBM and
moves padded: the published 192 lanes as 256, a third more bytes a pass.
``pack_state`` / ``unpack_state`` convert (the chunk path does, for its one
slot).

Kernel shape: grid ``(unit blocks, steps)``, one step a slot, the LIVE slots
first (``order``, a scalar-prefetch operand the index maps read): their
blocks stream one behind the other whichever slots they are, and every step
past them holds the last live slot's blocks, so the pipeline moves nothing
for a span-0 slot and the body skips it. The reassociation taken is ``o = a
S^T q + (k . q) u``: both reductions run over the block as loaded, before
``u`` is known, so a unit is read from VMEM once for them and once for the
update. Both reductions and the rank-one update run on the MXU, which the
decode column leaves idle, and stay float32-exact: a bf16 state is exact in
bf16, so ``[k ; q] S`` with ``k`` and ``q`` split into three bf16 parts (8 +
8 + 8 significant bits, cut by a mask) is a sum of exact products
accumulated in float32, and ``k u^T`` is ONE product over the six pairs of a
part of ``k`` and a part of ``u`` that lie above 2^-24 of the result. A
float32 state at rest is split the same way. What is left to the VPU an
element of the block is the widening load, ``a S``, the sum and the
rounding store; with the products on the VPU (a lane broadcast of ``k`` and
``q`` a sublane tile, two multiply-adds and two reductions an element) the
estimate was three times the operations.

The layer alone, cell 5's shape (30 heads of (96, 192), bf16 at rest), ms a
call on one v5e (my chip runs, PR 42: a loop of 24 calls on a donated leaf,
``q`` and ``k`` changing every call so that what prepares the operands is
inside, best of 5; least time = the state's bytes once in, once out at 819
GB/s):

    slots                                   16      32      64    64, every other one live
    least time                             0.043   0.086   0.173
    XLA, plain leaf (64, 30, 96, 192)      0.210   0.379   0.710   0.710
    this kernel, plain leaf                0.116   0.195   0.346   0.206
    this kernel, packed (64, 15, 96, 384)  0.102   0.148   0.263   0.160

The plain leaf's rows move as 256 lanes for 192: the packed leaf pays, and
``cache_spec`` declares it. ISSUE 42 predicted 0.21-0.30 ms at 64 slots and
needed 0.37. At 64 slots the kernel moves 2.32 MB a slot (state 2 x 1.106,
the tile of ``k`` and ``q`` 0.061, ``v`` and ``o`` rows 0.049) at 565 GB/s,
two thirds of the least time's 819; a plain elementwise pass reads 590
(guide ``on-chip-measurement``). What binds it is the DMA: a rolled loop
over the units read 0.40 ms where the unrolled one read 0.29 (both with the
parts of ``k`` and ``q`` split outside and read as two bf16 tiles a unit;
splitting inside took that to 0.27), and with both products taken out the
unrolled body read the same 0.29. Before the live slots went first, a call
with every other slot live read 0.24 for 0.27: a span-0 step ends at once,
so the next slot's load had nothing to hide behind.
"""

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

LANES = 128
KQ_ROWS = 16  # one bf16 sublane tile: the terms of a unit's k u^T, six a head
# k u^T = sum over (part of k, part of u) with part indices summing to <= 2
_TERMS = ((0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0))

# One-token updates traced by this thread, (kernel, xla): a scheduler reads
# it around a dispatch to learn which update a program was built with.
_traced = threading.local()


def tally(kernel):
    """Note one traced one-token update and the path it took."""
    n = traced()
    _traced.counts = (n[0] + 1, n[1]) if kernel else (n[0], n[1] + 1)


def traced():
    return getattr(_traced, "counts", (0, 0))


def _pad(n, m):
    return -(-n // m) * m


def state_packing(n, dv):
    """Heads that share a lane row at rest: the smallest ``p`` dividing ``n``
    with ``p * dv`` a multiple of 128 lanes and the ``p`` heads' parts inside
    one operand tile; 1 (the plain leaf) where there is none."""
    return next((p for p in range(1, KQ_ROWS // len(_TERMS) + 1)
                 if n % p == 0 and (p * dv) % LANES == 0), 1)


def pack_state(S, p):
    """``(B, n, dk, dv)`` -> ``(B, n / p, dk, p * dv)``: head ``j p + h`` in
    lanes ``[h dv, (h + 1) dv)`` of unit ``j``."""
    if p == 1:
        return S
    B, n, dk, dv = S.shape
    return S.reshape(B, n // p, p, dk, dv).swapaxes(2, 3).reshape(B, n // p, dk, p * dv)


def unpack_state(S, p):
    """:func:`pack_state`'s inverse."""
    if p == 1:
        return S
    B, U, dk, L = S.shape
    return S.reshape(B, U, dk, p, L // p).swapaxes(2, 3).reshape(B, U * p, dk, L // p)


def _vmem_estimate(bu, dk, L, itemsize, kq_rows=8):
    """VMEM bytes of one grid step over ``bu`` units of ``(dk, L)``, counted
    as Mosaic lays blocks out (lanes pad to 128, pipelined operands are
    double-buffered): the state block in and out, the tiles of k and q, the
    rows of ``v`` and ``o``, and about six float32 copies of ONE unit (the
    body walks the units)."""
    Lp = _pad(L, LANES)
    io = 2 * 2 * bu * dk * Lp * itemsize
    parts = 2 * bu * kq_rows * _pad(dk, LANES) * 4
    rows = 2 * 2 * _pad(bu, 8) * Lp * 4
    return io + parts + rows + 6 * _pad(dk, LANES) * Lp * 4


def tiles(leaf, n, dk, dv, channel_decay=False):
    """Whether ``leaf`` is a state leaf this kernel updates: ``n`` heads of
    ``(dk, dv)`` at :func:`state_packing`'s packing with whole lane tiles,
    ``dk`` a whole number of the dtype's sublane tiles and at most one lane
    tile (a ``k`` is a row of its operand), one unit inside the VMEM
    budget. ``channel_decay``: the decay is one value a key channel (two
    sublane tiles of operand rows a unit)."""
    if leaf.ndim != 4 or leaf.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    p = state_packing(n, dv)
    itemsize = jnp.dtype(leaf.dtype).itemsize
    rows = KQ_ROWS if channel_decay else 8
    return (leaf.shape[1:] == (n // p, dk, p * dv) and (p * dv) % LANES == 0
            and dk % (8 * (4 // itemsize)) == 0 and dk <= LANES
            and _pallas.fits_vmem(_vmem_estimate(1, dk, p * dv, itemsize, rows)))


def _top8(x):
    """``x`` float32 cut to its leading 8 significant bits: a bf16 value, by
    a mask and not by a conversion there and back, which the compiler may
    take for the identity (``xla_allow_excess_precision``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(x, dtype=jnp.float32):
    """``x`` float32 as three bf16 values, ``x = hi + mid + lo`` exactly (a
    float32 has 24 significant bits, a part the next 8 of them), held in
    ``dtype``."""
    hi = _top8(x)
    mid = _top8(x - hi)
    return tuple(y.astype(dtype) for y in (hi, mid, x - hi - mid))


def _step_kernel(order_ref, count_ref, fresh_ref, sc_ref, s_ref, kq_ref, v_ref,
                 out_ref, o_ref, *, n, p, dk, dv, bu, channel):
    """Step ``t``'s slot ``order[t]``, a block of ``bu`` units of it.
    ``sc_ref`` (SMEM): ``a``, ``beta`` and ``k . q`` of every head, ``(3 B
    n,)``; ``kq_ref``: a unit's rows ``k`` of its ``p`` heads, then ``q`` of
    them, float32, one sublane tile; ``v_ref`` / ``o_ref``: ``(bu, L)`` rows.
    ``channel``: the decay is a vector ``a`` over ``dk`` a head (module
    docstring); ``kq_ref`` is then two sublane tiles, the rows ``k``, ``q``,
    ``a * k``, ``a * q`` and ``a`` of the ``p`` heads, and ``sc_ref``'s ``a`` is
    not read."""
    t = pl.program_id(1)
    B = pl.num_programs(1)
    i = order_ref[t]
    base = i * n + pl.program_id(0) * bu * p
    L = p * dv
    f32 = jnp.float32
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) // dv
    # row (head, term) of the k u^T product's operands
    row = jax.lax.broadcasted_iota(jnp.int32, (KQ_ROWS, L), 0)
    term = row % len(_TERMS)
    own_lanes = ((row // len(_TERMS) == jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) // dv)
                 & (row < p * len(_TERMS)))
    k_row = jax.lax.broadcasted_iota(jnp.int32, (KQ_ROWS, LANES), 0)
    exact = s_ref.dtype == jnp.bfloat16
    # row (head, part) of the operands of Diag(a)'s lane broadcast
    own_head = channel and ((row // 3 == jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) // dv)
                            & (row < 3 * p))

    def by_head(vals):
        """``vals[h]`` (a scalar or a ``(1, L)`` row) over head ``h``'s lanes."""
        out = vals[-1]
        for h in range(p - 2, -1, -1):
            out = jnp.where(lane_head == h, vals[h], out)
        return out

    def unit(j, fresh):
        heads = [base + j * p + h for h in range(p)]
        beta = by_head([sc_ref[B * n + x] for x in heads])
        kdq = by_head([sc_ref[2 * B * n + x] for x in heads])
        v = v_ref[0, 0, j:j + 1, :]
        kq3 = _split3(kq_ref[0, j])  # three (8, 128), bf16 values in float32
        if fresh:
            u = beta * v
            o = kdq * u
        else:
            if not channel:  # (read first, as the head's path always traced it)
                a = by_head([sc_ref[x] for x in heads])
            S = s_ref[0, j]
            # [k ; q] S: a tile of rows a part, over every lane
            lhs = jnp.concatenate(kq3, axis=0).astype(jnp.bfloat16)[:, :dk]
            R = sum(jnp.dot(lhs, part, preferred_element_type=f32)
                    for part in ((S, ) if exact else _split3(S, jnp.bfloat16)))
            if channel:
                # S^T (a * k) and S^T (a * q): the rows that entered scaled.
                # Diag(a) over the block: a's three parts, each on its head's
                # lanes of a row of ones, transposed by the product
                R = R[:KQ_ROWS] + R[KQ_ROWS:2 * KQ_ROWS] + R[2 * KQ_ROWS:]
                Sk = by_head([R[2 * p + h:2 * p + h + 1] for h in range(p)])
                Sq = by_head([R[3 * p + h:3 * p + h + 1] for h in range(p)])
                aa = jnp.zeros((KQ_ROWS, LANES), f32)
                for h in range(p):
                    for part in range(3):
                        aa = jnp.where(k_row == 3 * h + part,
                                       kq3[part][4 * p + h:4 * p + h + 1], aa)
                a = jax.lax.dot_general(
                    aa.astype(jnp.bfloat16), own_head.astype(jnp.bfloat16),
                    (((0, ), (0, )), ((), ())), preferred_element_type=f32)[:dk]
                u = beta * (v - Sk)
                o = Sq + kdq * u
            else:
                R = R[:8] + R[8:16] + R[16:]
                Sk = by_head([R[h:h + 1] for h in range(p)])
                Sq = by_head([R[p + h:p + h + 1] for h in range(p)])
                u = beta * (v - a * Sk)
                o = a * Sq + kdq * u
        o_ref[0, 0, j:j + 1, :] = o
        # k u^T as one product over the terms: row (head, term) holds the
        # term's part of the head's k on one side, its part of u on the
        # head's lanes on the other
        kk = jnp.zeros((KQ_ROWS, LANES), f32)
        for h in range(p):
            for idx, (part, _) in enumerate(_TERMS):
                kk = jnp.where(k_row == h * len(_TERMS) + idx, kq3[part][h:h + 1], kk)
        u3 = _split3(u)
        rhs = jnp.zeros((KQ_ROWS, L), f32)
        for idx, (_, part) in enumerate(_TERMS):
            rhs = jnp.where(term == idx, u3[part], rhs)
        rhs = jnp.where(own_lanes, rhs, 0.0)
        P = jax.lax.dot_general(kk.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16),
                                (((0, ), (0, )), ((), ())), preferred_element_type=f32)[:dk]
        new = P if fresh else a * S.astype(f32) + P
        out_ref[0, j] = new.astype(out_ref.dtype)

    live = t < count_ref[0]
    fresh = fresh_ref[i] > 0

    def walk(fresh):
        # unrolled: the units' loads, products and stores overlap (module
        # docstring: a rolled loop took two fifths longer)
        for j in range(bu):
            unit(j, fresh)

    pl.when(live & fresh)(lambda: walk(True))
    pl.when(live & ~fresh)(lambda: walk(False))

    # past the live slots every step holds the last one's blocks and does
    # nothing. With none live, step 0's block (slot 0's) would be written
    # back as it stands in VMEM: give it its own bytes
    @pl.when((t == 0) & ~live)
    def _nothing_lives():
        out_ref[...] = s_ref[...]


def gated_delta_update(state, q, k, v, g, beta, live, fresh):
    """One token of the gated delta rule on the state leaf, in place.

    ``state``: the leaf at rest, ``(B, n / p, dk, p * dv)`` (``tiles`` holds);
    ``q``/``k`` (B, n, dk), ``v`` (B, n, dv), ``g``/``beta`` (B, n), float32,
    as ``gated_delta_step`` takes them (``g`` (B, n, dk): a decay a key
    channel); ``live``, ``fresh``: (B,) bool. Slot
    ``i`` advances when ``live[i]`` (from zero when ``fresh[i]`` too) and is
    left bit for bit otherwise. Returns ``(o (B, n, dv) float32, new
    leaf)``; the leaf operand is aliased to the new one.

    Jitted, so that the layers of a step program share one trace and one
    lowering of the kernel, as ``kv_commit`` does."""
    return _update(state, q, k, v, g, beta, live, fresh, interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _update(state, q, k, v, g, beta, live, fresh, *, interpret):
    B, n, dk = k.shape
    dv = v.shape[-1]
    U, L = state.shape[1], state.shape[3]
    p = n // U
    itemsize = jnp.dtype(state.dtype).itemsize
    channel = g.ndim == 3
    kq_rows = KQ_ROWS if channel else 8
    if state.shape != (B, U, dk, p * dv) or dk > LANES or p * len(_TERMS) > KQ_ROWS:
        raise ValueError(f"gdn step: leaf {state.shape} is not {n} heads of ({dk}, {dv}) "
                         f"packed {p} a lane row")
    bu = next((b for b in range(U, 0, -1) if U % b == 0
               and _pallas.fits_vmem(_vmem_estimate(b, dk, L, itemsize, kq_rows))), None)
    if bu is None:
        raise ValueError(f"gdn step: one unit of ({dk}, {L}) needs "
                         f"{_vmem_estimate(1, dk, L, itemsize, kq_rows)} bytes of VMEM, over the "
                         f"{_pallas.VMEM_BLOCK_BUDGET}-byte budget")
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    # live slots first, in slot order; every later step repeats the last
    # live slot (slot 0 if none lives), so no block changes under it
    idx = jnp.arange(B, dtype=jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)
    rank = jnp.cumsum(live, dtype=jnp.int32) - 1
    step = jnp.minimum(idx, jnp.maximum(count - 1, 0))
    order = jnp.sum(jnp.where(live[None, :] & (rank[None, :] == step[:, None]), idx[None, :], 0),
                    axis=1)
    a = jnp.exp(g.astype(f32))
    sc = jnp.concatenate([(jnp.ones_like(beta, f32) if channel else a).reshape(-1),
                          beta.astype(f32).reshape(-1), jnp.sum(k * q, axis=-1).reshape(-1)])
    # a unit's k rows, then its q rows (a decay a channel: then a * k, a * q
    # and a): whole float32 sublane tiles, dk padded to the lane tile (the
    # kernel splits them into bf16 parts)
    unit = lambda x: x.reshape(B, U, p, dk)
    rows_in = [unit(k), unit(q)] + ([unit(a * k), unit(a * q), unit(a)] if channel else [])
    kq = jnp.pad(jnp.concatenate(rows_in, axis=2),
                 ((0, 0), (0, 0), (0, kq_rows - len(rows_in) * p), (0, LANES - dk)))
    rows = v.reshape(B, U // bu, bu, L)
    blocks = U // bu

    at_slot = lambda h, t, order_r, *_: (order_r[t], h, 0, 0)
    state_spec = pl.BlockSpec((1, bu, dk, L), at_slot)
    kq_spec = pl.BlockSpec((1, bu, kq_rows, LANES), at_slot)
    row_spec = pl.BlockSpec((1, 1, bu, L), at_slot)
    new, o = pl.pallas_call(
        functools.partial(_step_kernel, n=n, p=p, dk=dk, dv=dv, bu=bu, channel=channel),
        name="dstpu_gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks, B),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state_spec, kq_spec, row_spec],
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
    )(order, count[None], fresh.astype(jnp.int32), sc, state, kq, rows)
    # a span-0 slot's rows of o were never written
    return jnp.where(live[:, None, None], o.reshape(B, n, dv), 0.0), new
