"""Pallas decode attention over a preallocated KV cache (TPU).

TPU-native equivalent of the reference's fused KV-cache decode attention
(``softmax_context_*`` ops, csrc/transformer/inference/csrc/pt_binding.cpp:1745
-1805, and the softmax/attention kernels behind them).

GQA-native: the cache keeps ``kv_heads`` heads and each program computes
whole groups of query heads sharing one KV head — no ``jnp.repeat``
expansion of the cache.

Two cache geometries, as ``CausalLMModel.init_cache`` makes them: a K and a
V leaf ``(slots, kv_heads, S, D)``, or at head size 64 ONE packed leaf
``(slots, kv_heads, S, 2 * D)`` with a row's key in lanes ``[0, D)`` and
its value after it (every entry point takes it as ``k_cache`` with
``v_cache=None``). The packed leaf rests row-major in full 128-lane tiles,
the form these kernels read, so a program that carries the pool relays
nothing; a KV block is one operand and one DMA a grid step, half the VMEM
and half the padded bytes of the split pair.

Kernel shape: one kernel, grid ``(rows, kv-head blocks)``. A program owns
one batch row (cache slot) and a block of its kv heads; the pool stays in
HBM and the program walks that row's LIVE KV blocks itself, in a loop whose
trip count it reads from its scalar-prefetch operands: the blocks from the
row's first attendable key (``start_i``) to its causal end (``end_i + span -
1``), each copied into one of two VMEM buffers while the block before it is
computed (online softmax), its physical pool row read from the extent
table. A block past the row's OWN write head costs nothing, not a grid step
and not a descriptor, so a short row beside a long one pays for its own
context and a row with ``end_i <= start_i`` (an idle slot: the callers hand
it ``end = 0``) for one grid step and no copy. Before a program computes its
last block it starts the first block of the next program that has one (the
buffer index carries over the grid step in SMEM), so a call exposes one
copy's latency, its first, and not one a row. The kv-head block and the KV
block are sized from the operand shapes and dtypes against the chip's VMEM
budget and against the fixed cost of a loop iteration
(:func:`_pick_blocks`); the all-heads-in-one-step layout of the first
version needed 40 MB of VMEM at gpt2-large widths and was refused by the
v5e compiler, and the grid that walked the POOL's blocks (to PR 34) spent a
quarter of a chat-traffic call on steps past the rows' ends.

``start`` masks left-padding slots of batched generation; ``end`` is the
write head. Entry points:

- :func:`decode_attention` — shared scalar ``end`` (the static-batch engine
  path: prompts are left-aligned to a common write head).
- :func:`paged_decode_attention` — per-row ``ends`` (the continuous-batching
  slot pool: every slot sits at its own sequence position).
- :func:`paged_span_attention` — per-row QUERY SPANS of ``T`` columns
  (chunked prefill fused into the decode step: decode rows carry one live
  query, the in-flight prefill row carries up to a chunk of them). Query
  column ``j`` of row ``i`` sits at absolute position ``base_i + j`` and
  attends ``[start_i, base_i + j]``; the query columns fold into the
  head-group axis and the kernel adds a per-column offset to the causal end.

Both paged entry points take the long-context and sharding operands:

- ``ext`` — one request's KV spans SEVERAL pool slots ("extents") through
  a per-row extent table: logical position ``p`` of row ``i`` lives at
  physical pool row ``ext[i, p // S]``, offset ``p % S``. The kernel walks
  LOGICAL blocks (up to ``E * S/block_kv``) and reads the table for each copy,
  so the extent count stays an OPERAND (table values), never a shape — the
  O(1)-compiled-programs guard holds across any extent mix, and an identity
  table (``ext[i, 0] == i``) is bit-identical to no table. ``sink``/
  ``window`` add attention-sink + sliding-window masking (the LOSSY
  long-context mode — rows with ``window == 0`` keep the exact mask, so
  lossy and exact rows co-reside in one dispatch).
- ``mesh``/``axis`` — shard_map over the tensor axis (head-sharded pool).
- :func:`seq_sharded_span_attention` — the span kernel shard_mapped over
  the SEQUENCE mesh axis: a wide seq-parallel prefill chunk splits its
  query columns across seq shards (shard ``s`` computes columns
  ``[s*Tl, (s+1)*Tl)`` with its causal base advanced by ``s*Tl``); per-row
  softmax is per COLUMN, so the gathered output is bit-identical to the
  unsharded span call.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NO_WINDOW = 1 << 30  # window == 0 means "exact": end - _NO_WINDOW masks nothing


def _attn_kernel(ext_ref, start_ref, end_ref, sink_ref, win_ref, q_ref, *rest,
                 scale, block_kv, span, quantized, lossy, packed):
    """One (row, kv-head block) program: walks that row's LIVE KV blocks in a
    loop of its own, each block copied from the pool in HBM into one of two
    VMEM buffers while the block before it is computed.

    ``q_ref``: (1, bh, g, D) where ``g`` is the FOLDED query axis:
    head-groups x span columns, span fastest. With ``span > 1`` the row's
    ``end`` is the causal end of column 0 and column j attends j more keys
    (per-row mixed decode/prefill query spans share this one kernel).

    The walk. After ``q_ref`` come the cache leaves as they rest in HBM
    (whole, ``memory_space=pl.ANY``), for an int8 pool its two (Npool, 1, S)
    scale planes, the output block, and the scratch: a (2, ...) VMEM buffer
    and a pair of DMA semaphores for each of those operands, two scalars of
    carried state in SMEM and the online softmax's state. A row attends the
    logical blocks ``[start // block_kv, ceil((end + span - 1) / block_kv))``
    (:func:`_walk`): the trip count is the row's own, read from the scalar
    operands, so a block past a row's write head costs nothing, not a grid
    step and not a descriptor. Block ``t + 1`` is in flight while block ``t``
    is computed, and before a (row, head block)'s LAST block is computed the
    FIRST block of the next program that has one is started into the other
    buffer (the buffer index and the fact that a copy is in flight carry
    over the grid step in SMEM), so a row exposes no copy's latency but the
    call's first. A row with ``end <= start`` attends nothing: no copy, and
    zeros out.

    ``packed``: ONE leaf (Npool, nkv, S, 2 * D) where the split form has a K
    and a V leaf of (Npool, nkv, S, D): lanes ``[0, D)`` of a row are its
    key, ``[D, 2 * D)`` its value (the packed pool of
    ``CausalLMModel.init_cache``). The block is never sliced: ``q_ref``
    arrives zero-extended to 2 * D lanes, so ``q . block`` is ``q . K``
    exactly (the value lanes meet zeros), and ``p @ block`` holds ``p @ V``
    in lanes ``[D, 2 * D)``, which the flush keeps. The split form's
    arithmetic, bit for bit, with one f32 copy of a lane-dense block where it
    made two of half-empty ones.

    ``quantized``: the KV blocks are int8 with per-token-row scales (a
    (1, block_kv) block of each plane rides with its KV block). The K scale
    multiplies the scores and the V scale the probabilities — both have keys
    on the lane axis, as the scale blocks do, so dequantization needs no
    relayout and the bf16/f32 KV never exists in HBM.

    ``lossy``: ``sink_ref``/``win_ref`` carry per-row attention-sink +
    sliding-window knobs — a row with ``win > 0`` additionally masks
    logical positions in ``[sink, end - win)`` (StreamingLLM shape);
    ``win == 0`` leaves the exact mask untouched, so lossy and exact rows
    share one compiled program."""
    n_kv = 1 if packed else 2
    n_src = n_kv + (2 if quantized else 0)
    srcs, o_ref, bufs = rest[:n_src], rest[n_src], rest[n_src + 1:2 * n_src + 1]
    sems, carry, m_s, l_s, acc_s = rest[2 * n_src + 1:]
    i, h = pl.program_id(0), pl.program_id(1)
    B, n_hb = pl.num_programs(0), pl.num_programs(1)
    bh = q_ref.shape[1]
    S = srcs[0].shape[2]
    bpe = S // block_kv                    # blocks an extent
    E = ext_ref.shape[0] // start_ref.shape[0]
    start, end = start_ref[i], end_ref[i]

    def walk(r):
        r = jnp.minimum(r, B - 1)
        return _walk(start_ref[r], end_ref[r], span, block_kv, E * bpe)

    def attends(r):
        lo_r, hi_r = walk(r)
        return hi_r > lo_r

    def copies(r, hb, t, slot):
        """The copies of row ``r``'s logical block ``t`` (head block ``hb``)
        into buffer ``slot``: the extent table names the pool row."""
        pool_row = jnp.maximum(ext_ref[r * E + t // bpe], 0)
        keys = pl.ds(pl.multiple_of((t % bpe) * block_kv, block_kv), block_kv)
        views = [src.at[pool_row, pl.ds(hb * bh, bh), keys] for src in srcs[:n_kv]]
        views += [src.at[pool_row, :, keys] for src in srcs[n_kv:]]
        return [pltpu.make_async_copy(view, buf.at[slot], sems.at[n, slot])
                for n, (view, buf) in enumerate(zip(views, bufs))]

    @pl.when((i == 0) & (h == 0))
    def _reset():
        carry[0] = 0  # the buffer the next block to compute lands in
        carry[1] = 0  # whether the program before this one started that copy

    def block(t, slot):
        kv_start = t * block_kv  # LOGICAL position of this block's first key
        q = q_ref[0].astype(jnp.float32) * scale  # (bh, g, D), or 2 * D packed
        k = bufs[0][slot].astype(jnp.float32)     # (bh, bkv, D), or 2 * D packed
        v = k if packed else bufs[1][slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2, ), (2, )), ((0, ), (0, ))),
                                preferred_element_type=jnp.float32)  # (bh, g, bkv)
        if quantized:
            s = s * bufs[n_kv][slot]
        g = s.shape[1]
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (1, g, block_kv), 2)
        end_col = end
        if span > 1:
            # folded columns cycle through the span fastest: column j of a
            # row sits j positions later, so its causal end advances by j
            end_col = end + jax.lax.broadcasted_iota(
                jnp.int32, (1, g, block_kv), 1) % span
        mask = (kv_pos >= start) & (kv_pos < end_col)
        if lossy:
            win = win_ref[i]
            win = jnp.where(win == 0, _NO_WINDOW, win)
            mask = mask & ((kv_pos < sink_ref[i]) | (kv_pos >= end_col - win))
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        m_prev = m_s[...]  # (bh, g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            p = p * bufs[n_kv + 1][slot]
        pv = jax.lax.dot_general(p, v, (((2, ), (1, )), ((0, ), (0, ))),
                                 preferred_element_type=jnp.float32)  # (bh, g, D)
        acc_s[...] = acc_s[...] * alpha + pv
        m_s[...] = m_new

    lo, hi = walk(i)

    @pl.when(hi <= lo)
    def _nothing():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hi > lo)
    def _row():
        slot0 = carry[0]

        @pl.when(carry[1] == 0)
        def _first():
            for c in copies(i, h, lo, slot0):
                c.start()

        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

        # the program after this one that has a block to walk: this row's
        # next head block, or head block 0 of the next row that attends
        wraps = h + 1 == n_hb
        nxt = jax.lax.while_loop(lambda r: (r < B) & jnp.logical_not(attends(r)),
                                 lambda r: r + 1, i + 1)
        n_row, n_hd = jnp.where(wraps, nxt, i), jnp.where(wraps, 0, h + 1)
        n_lo = walk(n_row)[0]

        def step(t, slot):
            last = t + 1 == hi

            @pl.when(jnp.logical_not(last) | (n_row < B))
            def _ahead():
                for c in copies(jnp.where(last, jnp.minimum(n_row, B - 1), i),
                                jnp.where(last, n_hd, h),
                                jnp.where(last, n_lo, t + 1), 1 - slot):
                    c.start()

            for c in copies(i, h, t, slot):
                c.wait()
            block(t, slot)
            return 1 - slot

        carry[0] = jax.lax.fori_loop(lo, hi, step, slot0)
        carry[1] = (n_row < B).astype(jnp.int32)

        l = l_s[...]
        l = jnp.where(l == 0, 1.0, l)
        out = acc_s[...] / l
        if packed:
            out = out[:, :, o_ref.shape[-1]:]
        o_ref[0] = out.astype(o_ref.dtype)


def _walk(start, end, span, block_kv, n_blocks, xp=jnp):
    """``[lo, hi)``: the logical KV blocks a row attends, from its first
    attendable key's block to its causal end's (``end + span - 1`` keys: the
    last column's), inside the ``n_blocks`` its extents hold; ``hi <= lo``
    for a row with ``end <= start``, which attends nothing. The kernel's
    trip count, and with ``xp=numpy`` on the host's copies of the same
    scalars the scheduler's count of the keys a walk fetches
    (:func:`walked_keys`): one function, so the two cannot drift."""
    lo = start // block_kv
    hi = xp.minimum((end + (span - 1) + (block_kv - 1)) // block_kv, n_blocks)
    return lo, xp.where(end > start, hi, lo)


def walked_keys(start, end, span, block_kv, n_blocks):
    """Keys the kernel's walk fetches for rows attending ``[start, end +
    span - 1)`` (numpy arrays, on the host), summed: whole blocks of
    ``block_kv`` (:func:`walk_block_kv`), whatever part of each is attended."""
    lo, hi = _walk(start, end, span, block_kv, n_blocks, xp=np)
    return int(((hi - lo) * block_kv).sum())


def _pad(n, m):
    return -(-n // m) * m


def _vmem_estimate(bh, bkv, g, D, q_bytes, kv_bytes, quantized, packed=False):
    """VMEM bytes one grid step of :func:`_attn_kernel` needs, counted the
    way Mosaic lays blocks out: the last dim pads to 128 lanes (so a split
    K or V block of ``D == 64`` costs as much as one of 128, and the packed
    block of ``2 * D`` lanes costs what ONE of them does), the
    second-to-last to 8 sublanes x the dtype's packing; the pipelined q and
    output blocks are double-buffered, and so are the K/V (and scale)
    blocks, in the two buffers the kernel's walk owns. Of the in-kernel
    values the compiler keeps about one f32 K/V copy and the score and
    probability planes in VMEM. Checked against the least
    ``vmem_limit_bytes`` the v5e compiler accepts at twelve (heads, g, D,
    block) points of the split form: the estimate ran 1.4x-2.2x above it,
    never below."""
    Dp = _pad(D, 128)
    Dq = _pad(2 * D, 128) if packed else Dp        # q, the numerator and the f32 block
    g8 = _pad(g, 8)
    gq = _pad(g, 8 * (4 // q_bytes))
    io = 2 * bh * gq * (Dq + Dp) * q_bytes         # q + out, double-buffered
    io += 2 * _block_bytes(bh, bkv, D, kv_bytes, packed)  # the walk's two K/V buffers
    if quantized:
        io += 2 * 2 * 8 * _pad(bkv, 128) * 4
    scratch = bh * g8 * (2 * 128 + Dq) * 4         # m, l (lane-padded), acc
    temps = bh * bkv * Dq * 4 + 2 * bh * g8 * _pad(bkv, 128) * 4
    return io + scratch + temps


def _block_bytes(bh, bkv, D, kv_bytes, packed):
    """Bytes of one walked block in VMEM: ``bh`` heads' ``bkv`` keys and
    values, lanes padded to 128 and rows to the dtype's sublane packing."""
    lanes = _pad(2 * D, 128) if packed else 2 * _pad(D, 128)
    return bh * _pad(bkv, 8 * (4 // kv_bytes)) * lanes * kv_bytes


# A loop iteration of the walk costs ~0.3 us whatever it copies (its
# descriptors, two semaphore waits, the softmax state's round trip), so a
# block pays for itself once its copy takes a few times that: ~2 MiB at the
# v5e's 819 GB/s. Past that a larger block only reads more keys beyond a
# row's end. On the chip (PR 35, the kernel alone over ragged rows, us a
# call at 128 / 256 / 512 keys a block): 20 heads packed at 64, 1.3 MiB at
# 256: 42.6 / 41.3 / 70.8; 10 heads split at 128, S 4096, 1.3 MiB at 256:
# 915 / 858 / 898; 30 heads split at 128, 3.9 MiB at 256: 579 / 663 / 762.
_WALK_BLOCK_BYTES = 2 * 2**20


def _pick_blocks(nkv, g, D, S, block_kv, q_dtype, kv_dtype, quantized, packed=False):
    """(kv-head block, KV block) for one grid step, from the operand shapes
    and dtypes and the chip's VMEM budget (``ops.pallas.VMEM_BLOCK_BUDGET``).
    ``block_kv`` is the caller's setting and the upper bound; below it the
    candidates are the lane-aligned divisors of ``S``. Each is taken with
    the largest kv-head block that fits beside it (heads are independent, so
    a smaller head block only adds grid steps), and the largest candidate
    whose block copies at most ``_WALK_BLOCK_BYTES`` wins: the last one that
    fits at all where none is that small."""
    block_kv = min(block_kv, S)
    if S % block_kv:
        raise ValueError(f"cache length {S} must be a multiple of block_kv={block_kv}")
    kv_cands = [block_kv] + [b for b in range(block_kv - block_kv % 128, 0, -128)
                             if b < block_kv and S % b == 0]
    q_bytes = jnp.dtype(q_dtype).itemsize
    kv_bytes = jnp.dtype(kv_dtype).itemsize
    best = None
    for bkv in kv_cands:
        bh = next((b for b in range(nkv, 0, -1) if nkv % b == 0 and _pallas.fits_vmem(
            _vmem_estimate(b, bkv, g, D, q_bytes, kv_bytes, quantized, packed))), None)
        if bh is None:
            continue
        best = bh, bkv
        if _block_bytes(bh, bkv, D, kv_bytes, packed) <= _WALK_BLOCK_BYTES:
            break
    if best is None:
        raise ValueError(
            f"decode attention: one kv head x {kv_cands[-1]} keys with {g} folded "
            f"query columns of width {D} needs "
            f"{_vmem_estimate(1, kv_cands[-1], g, D, q_bytes, kv_bytes, quantized, packed)} "
            f"bytes of VMEM, over the {_pallas.VMEM_BLOCK_BUDGET}-byte budget; "
            f"narrow the query span (prefill_chunk)")
    return best


def walk_block_kv(nkv, g, D, S, block_kv, q_dtype, kv_dtype, quantized=False, packed=False):
    """Keys a block of the kernel's walk holds at these shapes (``g``: query
    heads a kv head x span columns; ``block_kv``: the caller's bound): what
    :func:`walked_keys` rounds a row's attended window out to."""
    return _pick_blocks(nkv, g, D, S, block_kv, q_dtype, kv_dtype, quantized, packed)[1]


def span_tile(rep, T, D, S, block_kv, q_dtype, kv_dtype, quantized=False, packed=False):
    """Query columns ONE kernel call takes of a span of ``T`` at ``rep`` query
    heads a kv head: ``T`` itself where a grid step's blocks fit the chip's
    VMEM (every shape served before PR 39), else ``T`` halved until they do
    (16 query heads a kv head x 512 columns of width 128 do not: the folded
    query, output and softmax state of 8,192 columns alone pass the budget).
    :func:`paged_span_attention` then walks the row's keys once a tile, each
    tile's causal end advanced by its first column."""
    t = T
    while True:
        try:
            _pick_blocks(1, rep * t, D, S, block_kv, q_dtype, kv_dtype, quantized, packed)
            return t
        except ValueError:
            if t % 2:
                raise
            t //= 2


def _decode_call(qg, kv, start, ends, *, block_kv, scale, span=1,
                 k_scale=None, v_scale=None, ext=None, sink=None, win=None):
    """Shared pallas_call builder: row ``i`` attends its own window
    ``[start_i, ends_i)`` and walks the KV blocks from its first attendable
    key to its own write head (nothing where ``ends_i <= start_i``).
    ``qg``: queries pre-folded to (B, nkv, g, D) where ``g`` =
    head-groups x ``span`` columns (span fastest). ``kv``: the cache
    leaves, ``(k_cache, v_cache)`` of (Npool, nkv, S, D) each or ONE packed
    ``(kv_cache, )`` of (Npool, nkv, S, 2 * D) with a row's key in lanes
    ``[0, D)`` and its value in ``[D, 2 * D)``: one KV operand and one copy
    a block where the split form has two. ``k_scale``/``v_scale``:
    optional (Npool, S) per-token-row dequant scales for int8 caches,
    walked in lockstep with the KV blocks.

    ``ext``: optional (B, E) int32 per-row extent chains over a pool of
    ``Npool`` slots — logical position ``p`` of row ``i`` lives at pool row
    ``ext[i, p // S]``, offset ``p % S``; ``start``/``ends`` are then
    LOGICAL (up to ``E * S``). The chain is a scalar-prefetch operand the
    kernel reads for each copy, so a block's copy names exactly its row's
    physical block and the extent count never becomes a shape. -1 marks
    unreserved/demoted extents, which must lie outside every attended
    window; they clamp to slot 0 and are masked. Without ``ext`` row ``i``
    reads pool row ``i``. ``sink``/``win``: optional (B,) int32 lossy-mode
    knobs (see :func:`_attn_kernel`).

    Jitted with its non-array parameters static, as
    ``kv_commit.commit_kv_rows`` is: the layers of a step program, its first
    forwards and its loop body share one trace and one lowering of the
    kernel a shape."""
    return _decode_jit(qg, tuple(kv), start, ends, k_scale, v_scale, ext, sink, win,
                       block_kv=block_kv, scale=scale, span=span,
                       interpret=_pallas.interpret())


@functools.partial(jax.jit, static_argnames=("block_kv", "scale", "span", "interpret"))
def _decode_jit(qg, kv, start, ends, k_scale, v_scale, ext, sink, win, *,
                block_kv, scale, span, interpret):
    B, nkv, g, D = qg.shape
    packed = len(kv) == 1
    Np, _, S, lanes = kv[0].shape
    if lanes != (2 * D if packed else D) or any(c.shape != kv[0].shape for c in kv):
        raise ValueError(f"decode attention: cache leaves {[c.shape for c in kv]} "
                         f"do not hold heads of width {D}")
    scale = scale if scale is not None else 1.0 / (D**0.5)
    quantized = k_scale is not None
    lossy = sink is not None or win is not None
    bh, block_kv = _pick_blocks(nkv, g, D, S, block_kv, qg.dtype, kv[0].dtype,
                                quantized, packed)
    if ext is None:
        ext = jnp.arange(B, dtype=jnp.int32)[:, None]
    E = ext.shape[1]

    zeros = jnp.zeros((B, ), jnp.int32)
    scalars = (ext.reshape(B * E).astype(jnp.int32), start.astype(jnp.int32),
               ends.astype(jnp.int32),
               zeros if sink is None else sink.astype(jnp.int32),
               zeros if win is None else win.astype(jnp.int32))

    if packed:
        # zero lanes against the block's value lanes (see _attn_kernel)
        qg = jnp.pad(qg, ((0, 0), ) * 3 + ((0, D), ))
    Dq = qg.shape[-1]
    q_spec = pl.BlockSpec((1, bh, g, Dq), lambda i, h, *_: (i, h, 0, 0))
    o_spec = pl.BlockSpec((1, bh, g, D), lambda i, h, *_: (i, h, 0, 0))
    # the pool stays where it rests: the kernel copies the blocks it walks
    operands = [qg, *kv]
    buffers = [pltpu.VMEM((2, bh, block_kv, lanes), kv[0].dtype)] * len(kv)
    if quantized:
        operands += [k_scale.reshape(Np, 1, S), v_scale.reshape(Np, 1, S)]
        buffers += [pltpu.VMEM((2, 1, block_kv), jnp.float32)] * 2
    in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1)

    kernel = functools.partial(_attn_kernel, scale=scale, block_kv=block_kv,
                               span=span, quantized=quantized, lossy=lossy,
                               packed=packed)
    return pl.pallas_call(
        kernel,
        name="dstpu_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, nkv // bh),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((len(buffers), 2)),
                pltpu.SMEM((2, ), jnp.int32),          # buffer index, copy in flight
                pltpu.VMEM((bh, g, 1), jnp.float32),   # running max
                pltpu.VMEM((bh, g, 1), jnp.float32),   # running denom
                pltpu.VMEM((bh, g, Dq), jnp.float32),  # running numerator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, g, D), qg.dtype),
        # in order: a program starts the first copy of the one after it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*scalars, *operands)


def _group(q, nkv):
    B, H, D = q.shape
    return q.reshape(B, nkv, H // nkv, D)


def _row_scales(k_scale, v_scale, B, S):
    """(B, 1, S, 1) stored per-token-row scale leaves (fp16 in the pool) ->
    the f32 (B, S) layout the kernel walks (lane axis = S, so scale blocks
    stay lane-aligned; widened here because the pool is KB-sized and the
    kernel then needs no fp16 support from the chip)."""
    if k_scale is None:
        return None, None
    return (k_scale.reshape(B, S).astype(jnp.float32),
            v_scale.reshape(B, S).astype(jnp.float32))


def _optional_operands(k_cache, k_scale, v_scale, ext, sink, window):
    """(keyword names, arrays) of the optional :func:`_decode_call` operands
    that are present — they ride through shard_map positionally."""
    Np, _, S, _ = k_cache.shape
    ks, vs = _row_scales(k_scale, v_scale, Np, S)
    opt = {"ext": ext, "sink": sink, "win": window, "k_scale": ks, "v_scale": vs}
    names = [n for n, v in opt.items() if v is not None]
    return names, [opt[n] for n in names]


def _kv_leaves(k_cache, v_cache):
    """The cache leaves of a public entry point as :func:`_decode_call`
    takes them: ``v_cache=None`` says ``k_cache`` is the packed leaf."""
    return (k_cache, ) if v_cache is None else (k_cache, v_cache)


def _tp_shard_map(fn, mesh, axis, n_head, n_rep):
    """shard_map wrapper for the paged kernels over the ``axis`` (tensor)
    mesh dim: q and the KV cache leaves (the first ``n_head`` operands)
    split on their HEAD axes, the ``n_rep`` operands after them — window
    scalars, extent table, lossy knobs, per-token-row scale leaves — stay
    replicated. Each shard's kernel then
    walks ONLY its local KV-head blocks (shard-local block walk — DMA and
    compute scale down tp-fold), and because every (batch, kv-head) pair is
    computed independently by the same kernel, the gathered output is
    BIT-identical to the unsharded call."""
    from jax.sharding import PartitionSpec as SP
    head = SP(None, axis, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(head, ) * n_head + (SP(), ) * n_rep,
                         out_specs=head, check_vma=False)


def _paged(qg, kv, start, ends, *, span, block_kv, scale,
           k_scale, v_scale, ext=None, sink=None, window=None, mesh=None,
           axis=None):
    """Common tail of the public entry points: scale layout and the
    optional tensor-axis shard_map around :func:`_decode_call`.
    ``qg``: (B, nkv, g, D) folded queries, or (B, H, T, D) span queries
    when sharded (the (head-group, column) fold then happens INSIDE each
    shard, so per-column causal offsets see only local heads). ``kv``: the
    cache leaves (:func:`_kv_leaves`)."""
    names, optional = _optional_operands(kv[0], k_scale, v_scale, ext, sink,
                                         window)
    n = len(kv)

    def call(q, *rest):
        kvl, (st, en), rest = rest[:n], rest[n:n + 2], rest[n + 2:]
        nkv_l = kvl[0].shape[1]
        out = _decode_call(q.reshape(q.shape[0], nkv_l, -1, q.shape[-1]), kvl,
                           st, en, block_kv=block_kv, scale=scale, span=span,
                           **dict(zip(names, rest)))
        return out.reshape(q.shape)

    args = (qg, *kv, start, ends, *optional)
    if mesh is None:
        return call(*args)
    return _tp_shard_map(call, mesh, axis, 1 + n, len(args) - 1 - n)(*args)


def decode_attention(q, k_cache, v_cache, start, end, *, block_kv=256, scale=None):
    """q: (B, H, D) one query token per sequence; k_cache/v_cache:
    (B, kv_heads, S, D), or the packed (B, kv_heads, S, 2 * D) leaf as
    ``k_cache`` with ``v_cache=None``; start: (B,) int32 first attendable
    cache slot per row; end: scalar int32, one past the last written slot
    (shared). Returns (B, H, D)."""
    B, H, D = q.shape
    return paged_decode_attention(q, k_cache, v_cache, start,
                                  jnp.full((B, ), end, jnp.int32),
                                  block_kv=block_kv, scale=scale)


def paged_decode_attention(q, k_cache, v_cache, start, ends, *, block_kv=256,
                           scale=None, k_scale=None, v_scale=None, ext=None,
                           sink=None, window=None, mesh=None, axis=None):
    """Slot-pool variant: per-row ends. q: (B, H, D); k_cache/v_cache:
    (B, kv_heads, S, D) where B indexes cache SLOTS, or the pool's packed
    (B, kv_heads, S, 2 * D) leaf (key lanes, then value lanes: the head-
    size-64 geometry of ``CausalLMModel.init_cache``) as ``k_cache`` with
    ``v_cache=None``; ``ends``: (B,) int32 one
    past each slot's last written position (rows with ``ends == 0`` attend
    nothing and return zeros). Each row's KV-block walk stops at its own
    write head, so compute and DMA scale with the LIVE context, not the
    pool capacity S. ``k_scale``/``v_scale``: optional (B, 1, S, 1)
    per-token-row dequant scales for int8 caches — dequantization fuses
    into the kernel. ``mesh``/``axis``: shard_map the call over that
    (tensor) mesh axis — the KV pool stays head-sharded in HBM and each
    shard walks only its local heads' blocks; both head counts must divide
    the axis size.

    ``ext``: optional (B, E) int32 extent table for multi-extent KV — row
    ``i``'s logical position ``p`` lives at pool row ``ext[i, p // S]``
    offset ``p % S``, ``start``/``ends`` are then LOGICAL (up to ``E * S``)
    and -1 marks unreserved/demoted extents (which must lie entirely outside
    every attended window — the scheduler's detect-miss-and-restore
    guarantees it in exact mode, the sink/window mask in lossy mode). With
    an identity table the result is bit-identical to the call without one.
    ``sink``/``window``: optional (B,) int32 attention-sink +
    sliding-window knobs (the LOSSY long-context mode). Returns (B, H, D)."""
    B, H, D = q.shape
    out = _paged(_group(q, k_cache.shape[1]), _kv_leaves(k_cache, v_cache), start, ends,
                 span=1, block_kv=block_kv, scale=scale, k_scale=k_scale,
                 v_scale=v_scale, ext=ext, sink=sink, window=window, mesh=mesh,
                 axis=axis)
    return out.reshape(B, H, D)


def paged_span_attention(q, k_cache, v_cache, start, base, *, block_kv=256,
                         scale=None, k_scale=None, v_scale=None, ext=None,
                         sink=None, window=None, mesh=None, axis=None):
    """Fused chunked-prefill/decode variant: per-row query SPANS. q:
    (B, H, T, D) — row ``i``'s query column ``j`` sits at absolute cache
    position ``base_i + j`` and attends keys in ``[start_i, base_i + j]``
    (its own freshly-written KV included). Decode rows put their one live
    token in column 0; the in-flight prefill row fills up to a chunk; columns
    past a row's live span compute garbage that the caller never reads.
    ``base``: (B,) int32 per-row write heads (== column 0's position).
    The (head-group, column) pair folds into one query axis, column
    fastest — the kernel recovers the per-column causal offset from
    ``idx % span``. Other arguments as :func:`paged_decode_attention`.
    Returns (B, H, T, D)."""
    B, H, T, D = q.shape
    kv = _kv_leaves(k_cache, v_cache)
    tile = span_tile(H // kv[0].shape[1], T, D, kv[0].shape[2], block_kv, q.dtype,
                     kv[0].dtype, k_scale is not None, len(kv) == 1)
    call = functools.partial(_paged, span=tile, block_kv=block_kv, scale=scale,
                             k_scale=k_scale, v_scale=v_scale, ext=ext, sink=sink,
                             window=window, mesh=mesh, axis=axis)
    if tile == T:
        return call(q, kv, start, base + 1)
    # a row that attends nothing (end <= start) attends nothing in any tile
    return jnp.concatenate([
        call(q[:, :, t0:t0 + tile], kv, start,
             jnp.where(base + 1 > start, base + 1 + t0, base + 1))
        for t0 in range(0, T, tile)], axis=2)


# ----------------------------------------------------------- seq-parallel span
def seq_sharded_span_attention(q, k_cache, v_cache, start, base, *, mesh, axis,
                               block_kv=256, scale=None, k_scale=None,
                               v_scale=None, ext=None, sink=None, window=None):
    """Span attention shard_mapped over the SEQUENCE mesh axis: the wide
    seq-parallel prefill chunk splits its ``T`` query columns across the
    ``axis`` shards — shard ``s`` computes columns ``[s*Tl, (s+1)*Tl)``
    against the REPLICATED pool with its causal base advanced by ``s*Tl``
    (``Tl = T / shards``). Every (row, head-group, column) softmax is
    independent and each shard's kernel runs the exact span math of the
    single-shard call at span ``Tl``, so the gathered (B, H, T, D) output
    is bit-identical to :func:`paged_span_attention` column for column.
    ``ext`` switches to the multi-extent walk (long prompts whose earlier
    chunks landed in other extents); tensor sharding does NOT compose here
    — the scheduler gates seq-parallel prefill to tp == 1."""
    from jax.sharding import PartitionSpec as SP
    B, H, T, D = q.shape
    kv = _kv_leaves(k_cache, v_cache)
    nkv = k_cache.shape[1]
    n = mesh.shape[axis]
    if T % n:
        raise ValueError(f"span width {T} must divide by the seq axis size {n}")
    Tl = T // n
    names, optional = _optional_operands(k_cache, k_scale, v_scale, ext, sink,
                                         window)

    def body(qs, *rest):
        kvl, (st, bs), rest = rest[:len(kv)], rest[len(kv):len(kv) + 2], rest[len(kv) + 2:]
        bl = bs + jax.lax.axis_index(axis) * Tl  # this shard's columns start Tl*sh later
        out = _decode_call(qs.reshape(B, nkv, (H // nkv) * Tl, D), kvl, st,
                           bl + 1, block_kv=block_kv, scale=scale, span=Tl,
                           **dict(zip(names, rest)))
        return out.reshape(B, H, Tl, D)

    seq_q = SP(None, None, axis, None)
    args = (q, *kv, start, base, *optional)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(seq_q, ) + (SP(), ) * (len(args) - 1),
                         out_specs=seq_q, check_vma=False)(*args)
