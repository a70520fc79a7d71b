"""Pallas flash attention (TPU).

TPU-native replacement for the reference's fused CUDA attention kernels
(training: ``csrc/transformer/softmax_kernels.cu`` + strided-batch-GEMM
attention in ``csrc/transformer/ds_transformer_cuda.cpp``; the Triton
block-sparse path in ``deepspeed/ops/sparse_attention/matmul.py``).

FlashAttention-2-style online softmax: O(T) memory, fp32 accumulators in
VMEM, bf16 MXU matmuls — operands stay in the input dtype (bf16) and every
``dot_general`` accumulates in fp32 via ``preferred_element_type``; softmax
probabilities are cast back to the operand dtype before the P·V / Pᵀ·dO
matmuls (the MXU contracts bf16×bf16→fp32 natively; an fp32 operand path
would run at ~1/4 rate). Operates natively on the model's ``(B, H, T, D)``
("bhtd") layout — blocks are carved by BlockSpec index maps over the
sequence dim, so no transposes/copies appear around the kernel (those
copies cost ~7% of a train step in the packed ``(B*H, T, D)`` formulation
this replaces; the model computes attention in bhtd end-to-end).

Grouped-query attention is native: K/V keep their ``kv_heads`` dimension and
the index maps point query head ``h`` at KV head ``h // group``; nothing is
repeated in HBM. The backward dk/dv kernel accumulates per *query* head and
the group-sum is folded outside (a cheap reduce over the group dim).

Backward follows the standard two-kernel split (dq; dkv) with the saved
softmax log-sum-exp and delta = rowsum(dO * O).

The loops follow the causal structure (:func:`plan`). A grid step holds one
whole (batch, head) — q, k, v and the outputs of a head are 128-512 KB each
at training lengths — and walks it in sub-blocks of scores with STATIC trip
counts: the blocks that lie wholly inside the mask with no padded row or
column run a body with no mask at all, the blocks the diagonal crosses (and
the padded edge) run the same body with its ``masked`` flag set. The mask's
granularity is the sub-block, chosen from the shapes; the caller's
``block_q`` / ``block_kv`` are upper limits on it. A head that does not fit
the VMEM estimate, or is no longer than one sub-block, keeps the single
masked body over ``block_q`` x ``block_kv`` grid blocks (``Plan.fallback``
says why). The dk/dv kernel computes its scores transposed (``k q^T``).

The layer alone (PR 44; TPU v5 lite, bf16, causal, ``block_q = block_kv =
512``; device time of the Pallas call in a profiler trace, mean of 10 calls,
ms; the parent is the kernel before PR 44: 512 x 512 grid blocks, every
block masked, rolled loops)::

                                 forward        dq             dk/dv        three calls
    (B, H, T, D)                 parent change  parent change  parent change  parent change
    (4, 20, 1024, 64)  cell 1    0.377  0.207   0.365  0.231   0.541  0.325   1.283  0.763
    (2, 32, 2048, 64)  cell 3    0.880  0.489   0.910  0.625   1.469  0.839   3.259  1.953
    (4, 32/8, 1024, 128) GQA     0.615  0.334   0.582  0.372   0.859  0.525   2.056  1.231

``plan`` chose, at all three and for all three kernels: the whole head a grid
step, sub-blocks of 256 x 256; scores computed / scores inside the mask 124.9%
at T = 1024 and 112.4% at 2048 (the parent's 512 x 512: 149.9% and 124.9%),
scores under the masked body / scores computed 40.0% and 22.2% (100%).

Where the time went, by what was tried (same runs; cell 1 / cell 3, ms):

- *Static loops are most of it.* The parent's ``fori_loop`` has a traced
  trip count; with the whole head a grid step every bound is a Python int,
  short loops are written out and long ones go four blocks an iteration, and
  the compiler lays one block's vector work beside the next block's
  products. Whole head, 512 x 512 blocks, EVERY block masked: forward 0.214 /
  0.536, dq 0.262 / 0.672, dk/dv (transposed) 0.350 / 0.898. The same 256 x
  256 walk ROLLED (one block an iteration): 0.396 / 1.207, 0.368 / 1.117,
  0.527 / 1.610, no better than the parent; two blocks an iteration at T =
  2048: 0.915, 0.834, 1.196. Mosaic unrolls a loop wholly or not at all
  (``unroll=2`` is refused), so the partial unroll is by hand (``_loop``).
- *The transposed dk/dv.* With ``p`` and ``ds`` as (q, kv) blocks, ``p^T do``
  and ``ds^T q`` each put a block of scores through a transpose; as (kv, q)
  blocks all four products are plain. Wall clock of the call with its XLA
  neighbours, 256 x 256 two blocks an iteration: 0.805 -> 0.507 / 2.230 ->
  1.504. lse and delta then lie along the lanes, as they rest (below).
- *The split and the granularity* give the rest: 256 x 256 with the mask-free
  body against 512 x 512 masked everywhere, forward 0.214 -> 0.207 / 0.536 ->
  0.489, dq 0.262 -> 0.231 / 0.672 -> 0.625, dk/dv 0.350 -> 0.325 / 0.898 ->
  0.839.
- *Set-up.* Written-out loops are traced and lowered block by block: a
  layer's three calls took 0.36 s of tracing and lowering at T = 1024 and
  0.76 s at 2048 where the parent's rolled loops took 0.12 (on this
  sandbox's CPU), 36 and 24 times a training program. The calls are
  therefore jitted (:func:`attn`): one trace and one lowering a distinct
  shape, 0.01-0.03 s a layer after the first.
- *Dropped:* a grid block of 512 rows walked in 256 x 256 sub-blocks (traced
  bounds again: forward 0.73 / 1.76 by wall clock where the parent read 0.52 /
  1.21); 128-wide kv sub-blocks in the forward and dq (0.240 / 0.903, 0.254 /
  0.961); 128 x 128 everywhere (0.220 / 0.916, 0.276 / 0.811, 0.325 / 0.821
  written out: 136 blocks a step at T = 2048 compile in 4-8 s a kernel); 128 x
  256 (forward 0.202 / 0.478 but dq 0.263 / 0.769); 512-row sub-blocks
  (0.241 / 0.540, 0.270 / 0.678, 0.372 / 0.913).

What binds it now: at head size 64 every product half-fills the MXU (a
64-deep contraction or a 64-wide result), so a causal-halved product of
2 T^2 d a head takes 0.0545 ms in cell 1 (twice its 197 TFLOP/s time), and
at the 62.5% of the square the 256 x 256 walk computes the forward's two
products need 0.136 ms and the backward's seven 0.477: the forward runs at
66% of that bound and the backward at 86%. What is left is the MXU's fill,
the computed share, and a backward of five products (one fused kernel).

The statistics rest along the lanes (PR 49). ``lse`` (forward out; dq and
dk/dv in) and ``delta`` (dq and dk/dv in) cross HBM as ``(B, H, n_q, 1,
sub_q)``, a q block's values a row (:func:`_stats`):
``f32[4,20,4,1,256]{4,3,2,1,0:T(1,128)}`` in cell 1's compiled step, the
bytes of their values. As ``(B, H, T, 1)`` columns they rested under an
(8, 128) tile at 512 B a value, 41.9 MB each where the values are 0.33 MB,
and the saved ``flash_lse`` of cell 1's 36 layers was 1.51 GB that put the
4 x 1024 step over the chip's memory: the compiler bought the space back by
running 30 of the 36 ``up_proj`` products (with their layer norms) a second
time in the backward, 9.1 ms of a 195.4 ms step (:func:`stats_bytes`;
``PERF.md`` section 6, PR 49). The forward and dq kernels use the statistics
as ``(sub_q, 1)`` columns and turn a column into a row (:func:`_row`) or two
rows into two columns (:func:`_columns`) once a q block, one (128, 128)
transpose a 128 rows (every kernel's ``sub_q`` is a multiple of 128 for it);
the dk/dv kernel loads a q block's row by the block's number. All three take
ONE shape, so the forward's ``lse`` reaches both backward calls as it is and
``delta``, still XLA's reduction ``bhtd,bhtd->bht`` with T in the lanes as it
comes, through one relayout of 0.33 MB where two copies a layer wrote it as
a column. (With rows ``(B, H, 1, T)`` for the forward and dq and the blocks
for dk/dv alone, the same bytes, each backward call had its own relayout
chain and cell 3's step read 340.64 ms against the parent's 339.55; with one
shape 338.07.)

The layer alone again (my chip runs, PR 49; same machine, same operands,
device time of the Pallas calls, mean of 10, ms; every output of every kernel
is the parent's bit for bit; "beside" is the XLA operations a backward runs
beside its two calls: delta, the relayouts, the copies of the operands)::

                                 forward        dq             dk/dv        three calls    beside
    (B, H, T, D)                 PR 48  PR 49   PR 48  PR 49   PR 48  PR 49   PR 48  PR 49   PR 48  PR 49
    (4, 20, 1024, 64)  cell 1    0.207  0.203   0.231  0.244   0.325  0.301   0.763  0.747   0.261  0.191
    (2, 32, 2048, 64)  cell 3    0.489  0.493   0.625  0.635   0.839  0.818   1.954  1.945   0.499  0.339
    (4, 32/8, 1024, 128) GQA     0.334  0.338   0.372  0.396   0.525  0.487   1.232  1.221   0.376  0.190

dk/dv loses its transposes (-3 to -7%); dq gains two a q block (+2 to +6%;
with a transpose a statistic, four a q block, it read 0.254 / 0.643 / 0.412);
the forward trades 32 one-lane stores a q block for two transposes and two
row stores. The three calls together are within 2% of the parent's: what
this layout buys is memory, not kernel time.

Three forms that do NOT move the layout, because layout assignment and not
the ``jnp`` shape decides how a ``(..., T, 1)`` array rests (ISSUE 49,
compiled for a described v5e): saving ``lse[..., 0]`` and restoring
``[..., None]`` in the backward (the compiler cancels the pair: the parent's
program instruction for instruction); the same through a transpose and
``+ 1.0`` / ``- 1.0`` (folded, the same program); the same with a runtime
scalar it cannot fold (the saved buffer is still
``f32[4,20,1024,1]{...T(8,128)}``). The layout has to come from the kernels'
own operand and result shapes.

Kernels run interpreted on CPU (tests) and compiled on TPU.
"""

import functools
import math
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .. import pallas as _pallas

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128
# The sub-block of scores (q rows, kv columns) the three kernels walk inside
# a grid step where the caller's ``block_q`` / ``block_kv`` allow it: the
# granularity of the causal mask. One size won for all three at every shape
# measured (the layer-alone table above).
SUB_BLOCK = (256, 256)
# A static loop of at most INLINE_BLOCKS blocks is written out; a longer one
# runs UNROLL blocks an iteration (the layer-alone table: rolled loops lose
# a third).
INLINE_BLOCKS = 8
UNROLL = 4


class KernelPlan(NamedTuple):
    """One kernel's sizes and the shares that follow from them."""
    grid: int  # rows of a grid step's block: q rows (forward, dq), kv rows (dk/dv)
    sub_q: int  # q rows of a block of scores
    sub_kv: int  # kv columns of it
    split: bool  # blocks wholly inside the mask run the mask-free body
    computed_pct: float  # scores computed / scores inside the mask
    masked_pct: float  # scores under the masked body / scores computed


class Plan(NamedTuple):
    fwd: KernelPlan
    dq: KernelPlan
    dkv: KernelPlan
    fallback: str  # why the single masked body was kept; "" where it was not

    @property
    def computed_pct(self):
        """Over the three kernels: each computes every score it visits once."""
        return sum(k.computed_pct for k in self[:3]) / 3

    @property
    def masked_pct(self):
        return (sum(k.masked_pct * k.computed_pct for k in self[:3])
                / sum(k.computed_pct for k in self[:3]))


def stats_bytes(shape):
    """``(at rest, values)``: the bytes a float32 array of ``shape`` takes as
    the TPU tiles its last two axes, and the bytes of its values. A tile is
    128 lanes by 8 sublanes, or by the power of two that holds an axis
    shorter than 8: ``(T, 1)`` rests at 128 times its values, ``(1, T)`` at
    its values."""
    *outer, rows, lanes = shape
    n = math.prod(outer)
    sublanes = min(8, 1 << (rows - 1).bit_length())
    return n * _pad_to(rows, sublanes) * _pad_to(lanes, LANES) * 4, n * rows * lanes * 4


class TracedCall(NamedTuple):
    """One traced flash call: its plan, and what the softmax statistics its
    kernels exchange through HBM (lse and delta) take there."""
    plan: Plan
    stats_bytes_at_rest: int
    stats_bytes_values: int


# The flash calls traced by this thread: the training engine reads it around
# a step's first dispatch to say what its kernels compute.
_traced = threading.local()


def tally(plan_, stats_shape):
    """Note one traced flash call: its plan and the shape lse (and delta)
    cross HBM in."""
    at_rest, values = stats_bytes(stats_shape)
    _traced.calls = traced() + (TracedCall(plan_, 2 * at_rest, 2 * values), )


def traced():
    return getattr(_traced, "calls", ())


# ------------------------------------------------------------------ the loops
def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _min(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _max(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _kv_blocks(q_start, sub_q, sub_kv, n_kv, kv_len, causal, split):
    """The kv blocks a q block at ``q_start`` walks, ``(n_inside, n_visited)``:
    blocks ``[0, n_inside)`` lie wholly inside the mask with no padded column,
    ``[n_inside, n_visited)`` need a mask (the diagonal crosses them, or they
    hold the padded edge). Python ints in, ints out; a traced ``q_start``
    gives traced bounds."""
    n_visited = _min(n_kv, (q_start + sub_q + sub_kv - 1) // sub_kv) if causal else n_kv
    if not split:
        return 0, n_visited
    n_inside = kv_len // sub_kv
    if causal:
        n_inside = _min(n_inside, (q_start + 1) // sub_kv)
    return n_inside, n_visited


def _q_blocks(kv_start, sub_q, sub_kv, n_q, q_len, kv_len, causal, split):
    """The q blocks a kv block at ``kv_start`` walks, ``(start, inside_from,
    inside_to)``: ``[start, inside_from)`` and ``[inside_to, n_q)`` need a
    mask (the diagonal, the padded rows), ``[inside_from, inside_to)`` do not.
    A kv block with padded columns masks every q block."""
    start = _min(kv_start // sub_q, n_q) if causal else 0
    if not split:
        return start, n_q, n_q
    inside_to = _max(q_len // sub_q, start)
    inside_from = (kv_start + sub_kv - 1 + sub_q - 1) // sub_q if causal else 0
    inside_from = _max(start, _min(inside_from, inside_to))
    if kv_len % sub_kv:  # the last kv block holds the padded columns
        padded = kv_start + sub_kv > kv_len
        if _static(kv_start):
            inside_from = inside_to if padded else inside_from
        else:
            inside_from = jnp.where(padded, inside_to, inside_from)
    return start, inside_from, inside_to


def _loop(lo, hi, body, carry):
    """``fori_loop`` over blocks. A static range of up to ``INLINE_BLOCKS`` is
    written out and a longer one goes ``UNROLL`` blocks an iteration, so that
    one block's vector work has the next block's products beside it (Mosaic
    unrolls a loop wholly or not at all)."""
    if not _static(lo, hi):
        return jax.lax.fori_loop(lo, hi, body, carry)
    n = hi - lo
    rolled = 0 if n <= INLINE_BLOCKS else n // UNROLL

    def several(i, carry):
        for r in range(UNROLL):
            carry = body(lo + i * UNROLL + r, carry)
        return carry

    if rolled:
        carry = jax.lax.fori_loop(0, rolled, several, carry)
    for j in range(lo + rolled * UNROLL, hi):
        carry = body(j, carry)
    return carry


def _dot(a, b, ca, cb):
    return jax.lax.dot_general(a, b, (((ca, ), (cb, )), ((), ())),
                               preferred_element_type=jnp.float32)


def _prescaled(scale):
    """Whether ``scale`` is a power of two: ``q * scale`` is then exact in any
    float dtype and takes the place of a pass over every block of scores."""
    return math.frexp(scale)[0] == 0.5


def _block_mask(masked, iq, ik, q_start, kv_start, causal, q_left, kv_left):
    """The mask of one block of scores; None under the mask-free body and
    where nothing in the block can be masked. ``q_left`` / ``kv_left``: the
    rows / columns of the block inside the logical length, None where that
    side has no padding."""
    if not masked:
        return None
    terms = []
    if kv_left is not None:
        terms.append(ik < kv_left)
    if q_left is not None:
        terms.append(iq < q_left)
    if causal:
        terms.append(ik - iq <= q_start - kv_start)
    return functools.reduce(jnp.logical_and, terms) if terms else None


def _row(col):
    """A (128, 1) column as a (1, 128) row: one (128, 128) transpose of its
    broadcast. The statistics cross HBM with the sequence in the lanes; the
    forward and dq kernels use them as columns."""
    return jnp.broadcast_to(col, (LANES, LANES)).T[:1]


def _columns(lse_ref, delta_ref, a):
    """Q block ``a`` of the two statistics blocks ``(1, 1, n, 1, sub_q)`` as
    two (sub_q, 1) columns. One (128, 128) transpose a 128 values turns
    both: lse's row on sublane 0 and delta's on the others come out as column
    0 and column 1."""
    first = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) == 0
    both = [jnp.where(first, lse_ref[0, 0, a, :, pl.ds(c, LANES)],
                      delta_ref[0, 0, a, :, pl.ds(c, LANES)]).T
            for c in range(0, lse_ref.shape[-1], LANES)]
    return (jnp.concatenate([t[:, :1] for t in both], axis=0),
            jnp.concatenate([t[:, 1:2] for t in both], axis=0))


# ---------------------------------------------------------------- the kernels
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, sub_q, sub_kv, causal, split,
                q_len, kv_len, one_step):
    """Grid: (B, H, q grid blocks). Blocks: q/o (1, 1, gq, D); k/v
    (1, 1, Tkv, D), the full (padded) KV head in VMEM; lse (1, 1, gq / sub_q,
    1, sub_q), a q block's rows along the lanes. The step walks its q rows in sub-blocks of
    ``sub_q`` and, for each, the kv blocks of ``sub_kv`` columns the mask
    leaves."""
    gq, d = q_ref.shape[2], q_ref.shape[3]
    base = 0 if one_step else pl.program_id(2) * gq
    n_kv = k_ref.shape[2] // sub_kv
    prescale = _prescaled(scale)
    kv_padded = bool(kv_len % sub_kv)
    zero_masked = kv_padded or bool(q_len % sub_q)
    iq = jax.lax.broadcasted_iota(jnp.int32, (sub_q, sub_kv), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (sub_q, sub_kv), 1)

    def q_block(a):
        q_start = base + a * sub_q
        rows = pl.ds(a * sub_q, sub_q)
        q = q_ref[0, 0, rows, :]  # operand dtype; accumulation is fp32
        if prescale:
            q = q * scale

        def body(masked, j, carry):
            m, l, acc = carry
            kv_start = j * sub_kv
            k = k_ref[0, 0, pl.ds(kv_start, sub_kv), :]
            v = v_ref[0, 0, pl.ds(kv_start, sub_kv), :]
            s = _dot(q, k, 1, 1)  # (sub_q, sub_kv)
            if not prescale:
                s = s * scale
            mask = _block_mask(masked, iq, ik, q_start, kv_start, causal, None,
                               kv_len - kv_start if kv_padded else None)
            if mask is not None:
                s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            # rows whose every visited entry is masked exist only when the
            # sequence is padded (causal rows always see the diagonal): only
            # then pay for the explicit zero that yields l=0 -> zero output,
            # -inf lse (otherwise exp(MASK - m_new) underflows to 0 itself)
            if mask is not None and zero_masked:
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(v.dtype), v, 1, 0)
            return m_new, l, acc

        carry = (jnp.full((sub_q, 1), -jnp.inf, jnp.float32), jnp.zeros((sub_q, 1), jnp.float32),
                 jnp.zeros((sub_q, d), jnp.float32))
        n_inside, n_visited = _kv_blocks(q_start, sub_q, sub_kv, n_kv, kv_len, causal, split)
        carry = _loop(0, n_inside, functools.partial(body, False), carry)
        m, l, acc = _loop(n_inside, n_visited, functools.partial(body, True), carry)

        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0, -jnp.inf, m + jnp.log(l_safe))  # (sub_q, 1)
        for c in range(0, sub_q, LANES):
            lse_ref[0, 0, a, :, pl.ds(c, LANES)] = _row(lse[c:c + LANES])

    for a in range(gq // sub_q):
        q_block(a)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, sub_q, sub_kv,
                   causal, split, kv_len, one_step):
    """The forward's grid and walk. lse / delta: the forward's lse blocks,
    turned into the columns the scores take once a q block."""
    gq, d = q_ref.shape[2], q_ref.shape[3]
    base = 0 if one_step else pl.program_id(2) * gq
    n_kv = k_ref.shape[2] // sub_kv
    prescale = _prescaled(scale)
    kv_padded = bool(kv_len % sub_kv)
    iq = jax.lax.broadcasted_iota(jnp.int32, (sub_q, sub_kv), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (sub_q, sub_kv), 1)

    def q_block(a):
        q_start = base + a * sub_q
        rows = pl.ds(a * sub_q, sub_q)
        q = q_ref[0, 0, rows, :]
        if prescale:
            q = q * scale
        do = do_ref[0, 0, rows, :]
        # -inf marks attended-nothing (padding) rows; neutralize so exp(s - lse)
        # stays finite — their dq is sliced away / masked out downstream
        lse, delta = _columns(lse_ref, delta_ref, a)  # (sub_q, 1)
        lse = jnp.where(jnp.isfinite(lse), lse, 0.0)

        def body(masked, j, dq):
            kv_start = j * sub_kv
            k = k_ref[0, 0, pl.ds(kv_start, sub_kv), :]
            v = v_ref[0, 0, pl.ds(kv_start, sub_kv), :]
            s = _dot(q, k, 1, 1)
            if not prescale:
                s = s * scale
            p = jnp.exp(s - lse)
            mask = _block_mask(masked, iq, ik, q_start, kv_start, causal, None,
                               kv_len - kv_start if kv_padded else None)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            ds = p * (_dot(do, v, 1, 1) - delta)
            # the softmax scale folds into ds before the cast (dq =
            # scale·ds·k), or into dq once where it is a power of two
            if not prescale:
                ds = ds * scale
            return dq + _dot(ds.astype(k.dtype), k, 1, 0)

        n_inside, n_visited = _kv_blocks(q_start, sub_q, sub_kv, n_kv, kv_len, causal, split)
        dq = _loop(0, n_inside, functools.partial(body, False), jnp.zeros((sub_q, d), jnp.float32))
        dq = _loop(n_inside, n_visited, functools.partial(body, True), dq)
        if prescale:
            dq = dq * scale
        dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)

    for a in range(gq // sub_q):
        q_block(a)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, sub_q,
                    sub_kv, causal, split, q_len, kv_len, one_step):
    """Grid: (B, H, kv grid blocks). k/v blocks (1, 1, gkv, D) come from the
    (possibly grouped) KV head for query head h; dk/dv are written per
    *query* head (into (B, H, Tkv, D)) and group-summed by the caller. q / do:
    the full (padded) head; lse / delta: (1, 1, n_q, 1, sub_q), a q block's
    values a row.

    The scores are computed TRANSPOSED (``k q^T``, a block is (sub_kv,
    sub_q)), so that ``p^T do`` and ``ds^T q`` are plain products and no
    block of scores goes through a transpose. lse and delta then ride along
    the lanes, as they rest."""
    gkv, d = k_ref.shape[2], k_ref.shape[3]
    base = 0 if one_step else pl.program_id(2) * gkv
    n_q = q_ref.shape[2] // sub_q
    prescale = _prescaled(scale)
    q_padded, kv_padded = bool(q_len % sub_q), bool(kv_len % sub_kv)
    ik = jax.lax.broadcasted_iota(jnp.int32, (sub_kv, sub_q), 0)
    iq = jax.lax.broadcasted_iota(jnp.int32, (sub_kv, sub_q), 1)

    def kv_block(b):
        kv_start = base + b * sub_kv
        cols = pl.ds(b * sub_kv, sub_kv)
        k = k_ref[0, 0, cols, :]
        v = v_ref[0, 0, cols, :]

        def body(masked, i, carry):
            dk, dv = carry
            q_start = i * sub_q
            q = q_ref[0, 0, pl.ds(q_start, sub_q), :]
            if prescale:
                q = q * scale
            do = do_ref[0, 0, pl.ds(q_start, sub_q), :]
            lse, delta = lse_ref[0, 0, i], delta_ref[0, 0, i]  # (1, sub_q)
            # -inf marks attended-nothing (padding) rows; neutralize so
            # exp(s - lse) stays finite: their p is masked out below
            lse = jnp.where(jnp.isfinite(lse), lse, 0.0)

            s = _dot(k, q, 1, 1)  # (sub_kv, sub_q)
            if not prescale:
                s = s * scale
            p = jnp.exp(s - lse)
            mask = _block_mask(masked, iq, ik, q_start, kv_start, causal,
                               q_len - q_start if q_padded else None,
                               kv_len - kv_start if kv_padded else None)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dv = dv + _dot(p.astype(do.dtype), do, 1, 0)
            ds = p * (_dot(v, do, 1, 1) - delta)
            # scale folds into ds (dk = scale·dsᵀq), or rides on q where it
            # is a power of two
            if not prescale:
                ds = ds * scale
            dk = dk + _dot(ds.astype(q.dtype), q, 1, 0)
            return dk, dv

        start, inside_from, inside_to = _q_blocks(kv_start, sub_q, sub_kv, n_q, q_len, kv_len,
                                                  causal, split)
        zero = jnp.zeros((sub_kv, d), jnp.float32)
        carry = _loop(start, inside_from, functools.partial(body, True), (zero, zero))
        carry = _loop(inside_from, inside_to, functools.partial(body, False), carry)
        dk, dv = _loop(inside_to, n_q, functools.partial(body, True), carry)
        dk_ref[0, 0, cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, cols, :] = dv.astype(dv_ref.dtype)

    for b in range(gkv // sub_kv):
        kv_block(b)


# ------------------------------------------------------------------- the plan
def _pad_to(n, m):
    return -(-n // m) * m


def _vmem_estimate(kernel, T_q, T_kv, grid, sub_q, sub_kv, D, itemsize):
    """VMEM bytes of one grid step as Mosaic lays its blocks out (lanes pad to
    128, the one row of a statistics block to a sublane tile at most,
    pipelined operands are double-buffered), and about eight float32 copies
    of one block of scores with the accumulators."""
    wide = _pad_to(D, LANES)
    row = lambda t: t * wide * itemsize  # (t, D) in the operand dtype
    stat = lambda t: stats_bytes((1, t))[0]  # (1, t) float32
    if kernel == "fwd":
        blocks = 2 * row(grid) + 2 * row(T_kv) + stat(grid)
    elif kernel == "dq":
        blocks = 3 * row(grid) + 2 * row(T_kv) + 2 * stat(grid)
    else:
        blocks = 2 * row(T_q) + 4 * row(grid) + 2 * stat(T_q)
    scores = 8 * sub_q * _pad_to(sub_kv, LANES) * 4
    acc = 3 * max(sub_q, sub_kv) * wide * 4
    return 2 * blocks + scores + acc


def _shares(kernel, T, T_kv, Tq, Tkv, sub_q, sub_kv, causal, split):
    """``(computed, masked)`` scores of one (batch, head), walked as the
    kernel walks them."""
    computed = masked = 0
    if kernel == "dkv":
        for kv_start in range(0, Tkv, sub_kv):
            start, lo, hi = _q_blocks(kv_start, sub_q, sub_kv, Tq // sub_q, T, T_kv, causal, split)
            computed += Tq // sub_q - start
            masked += Tq // sub_q - start - (hi - lo)
    else:
        for q_start in range(0, Tq, sub_q):
            n_inside, n_visited = _kv_blocks(q_start, sub_q, sub_kv, Tkv // sub_kv, T_kv, causal,
                                             split)
            computed += n_visited
            masked += n_visited - n_inside
    return computed * sub_q * sub_kv, masked * sub_q * sub_kv


@functools.lru_cache(maxsize=None)
def plan(T, T_kv, D, dtype, causal, block_q=512, block_kv=512):
    """The sizes the three kernels run ``(B, H, T, D)`` x ``(B, Hkv, T_kv, D)``
    at and the shares that follow from them, from the shapes alone.

    Each kernel's grid step takes the whole (padded) head and walks it in
    sub-blocks of :data:`SUB_BLOCK` scores, no larger than ``block_q`` x
    ``block_kv``; blocks wholly inside the mask run the mask-free body. A
    head of one sub-block (nothing lies wholly inside), or one whose blocks
    do not fit :func:`_vmem_estimate` in the budget, keeps the single masked
    body over grid blocks of ``block_q`` / ``block_kv`` rows, and ``fallback``
    says which."""
    itemsize = jnp.dtype(dtype).itemsize
    inside = sum(min(r + 1, T_kv) for r in range(T)) if causal else T * T_kv

    def kernel_plan(kernel, grid_rows, sub_q, sub_kv, split):
        Tq, Tkv = _pad_to(T, sub_q), _pad_to(T_kv, sub_kv)
        grid = grid_rows or (Tkv if kernel == "dkv" else Tq)
        computed, masked = _shares(kernel, T, T_kv, Tq, Tkv, sub_q, sub_kv, causal, split)
        return KernelPlan(grid, sub_q, sub_kv, split, 100.0 * computed / inside,
                          100.0 * masked / computed)

    bq, bkv = min(block_q, T), min(block_kv, T_kv)

    def sub(preferred, limit, length):
        """Of the preferred size and its half the one that pads the length
        less, no larger than the caller's limit."""
        size = min(preferred, limit)
        half = size // 2
        return half if half % LANES == 0 and _pad_to(length, half) < _pad_to(length, size) else size

    # a block's q rows lie along the lanes of the statistics (and of the
    # dk/dv kernel's scores): whole lane tiles of them
    sq, skv = _pad_to(sub(SUB_BLOCK[0], bq, T), LANES), sub(SUB_BLOCK[1], bkv, T_kv)
    bq = _pad_to(bq, LANES)
    whole = {name: kernel_plan(name, 0, sq, skv, True) for name in ("fwd", "dq", "dkv")}
    fallback = ""
    if all(k.masked_pct == 100.0 for k in whole.values()):
        fallback = "no block lies wholly inside the mask"
    else:
        for name, k in whole.items():
            need = _vmem_estimate(name, _pad_to(T, k.sub_q), _pad_to(T_kv, k.sub_kv), k.grid,
                                  k.sub_q, k.sub_kv, D, itemsize)
            if not _pallas.fits_vmem(need):
                fallback = (f"a whole head in the {name} kernel takes {need} bytes of VMEM, "
                            f"over the {_pallas.VMEM_BLOCK_BUDGET}-byte budget")
                break
    if not fallback:
        return Plan(whole["fwd"], whole["dq"], whole["dkv"], "")
    return Plan(kernel_plan("fwd", bq, bq, bkv, False), kernel_plan("dq", bq, bq, bkv, False),
                kernel_plan("dkv", bkv, bq, bkv, False), fallback)


def _pad_seq(x, block):
    t = x.shape[2]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_pallas.VMEM_LIMIT_BYTES)


def _fit(x, rows):
    """``x`` (B, H, t, ...) cut or zero-padded to ``rows`` along its third
    axis (the forward's and the backward's sub-blocks may pad a length
    differently)."""
    t = x.shape[2]
    if t >= rows:
        return x[:, :, :rows]
    return jnp.pad(x, [(0, 0), (0, 0), (0, rows - t)] + [(0, 0)] * (x.ndim - 3))


def _stats(x, B, H, Tq, sub_q):
    """A statistic as the three kernels exchange it: ``(B, H, n_q, 1,
    sub_q)``, a q block's values a row. ``x`` holds a value a q row in any
    shape behind (B, H)."""
    return _fit(x.reshape(B, H, -1), Tq).reshape(B, H, Tq // sub_q, 1, sub_q)


def _fwd_call(q, k, v, *, kp, causal, scale, interpret):
    """The forward kernel's call on unpadded operands: ``(out, lse)`` at the
    padded length, lse as :func:`_stats` gives it."""
    B, H, T, D = q.shape
    T_kv, g = k.shape[2], H // k.shape[1]
    qp, kp_, vp = _pad_seq(q, kp.sub_q), _pad_seq(k, kp.sub_kv), _pad_seq(v, kp.sub_kv)
    Tq, Tkv, gq = qp.shape[2], kp_.shape[2], kp.grid
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sub_q=kp.sub_q, sub_kv=kp.sub_kv,
                          causal=causal, split=kp.split, q_len=T, kv_len=T_kv,
                          one_step=gq == Tq),
        grid=(B, H, Tq // gq),
        in_specs=[
            pl.BlockSpec((1, 1, gq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, gq // kp.sub_q, 1, kp.sub_q), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq // kp.sub_q, 1, kp.sub_q), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qp, kp_, vp)


def _bwd_operands(q, k, v, do, lse, delta, kp):
    B, H, T = q.shape[:3]
    Tq = _pad_to(T, kp.sub_q)
    return (_pad_seq(q, kp.sub_q), _pad_seq(k, kp.sub_kv), _pad_seq(v, kp.sub_kv),
            _fit(do, Tq), _stats(lse, B, H, Tq, kp.sub_q), _stats(delta, B, H, Tq, kp.sub_q))


def _dq_call(q, k, v, do, lse, delta, *, kp, causal, scale, interpret):
    """The dq kernel's call: dq at q's length. lse / delta: a value a q row."""
    B, H, T, D = q.shape
    T_kv, grp = k.shape[2], H // k.shape[1]
    args = _bwd_operands(q, k, v, do, lse, delta, kp)
    Tq, Tkv, gq = args[0].shape[2], args[1].shape[2], kp.grid
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, sub_q=kp.sub_q, sub_kv=kp.sub_kv,
                          causal=causal, split=kp.split, kv_len=T_kv, one_step=gq == Tq),
        grid=(B, H, Tq // gq),
        in_specs=[
            pl.BlockSpec((1, 1, gq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // grp, 0, 0)),
            pl.BlockSpec((1, 1, Tkv, D), lambda b, h, i: (b, h // grp, 0, 0)),
            pl.BlockSpec((1, 1, gq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, gq // kp.sub_q, 1, kp.sub_q), lambda b, h, i: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, gq // kp.sub_q, 1, kp.sub_q), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return dq[:, :, :T]


def _dkv_call(q, k, v, do, lse, delta, *, kp, causal, scale, interpret):
    """The dk/dv kernel's call: ``(dk, dv)`` per QUERY head at k's length."""
    B, H, T, D = q.shape
    T_kv, grp = k.shape[2], H // k.shape[1]
    args = _bwd_operands(q, k, v, do, lse, delta, kp)
    Tq, Tkv, gkv = args[0].shape[2], args[1].shape[2], kp.grid
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, sub_q=kp.sub_q, sub_kv=kp.sub_kv,
                          causal=causal, split=kp.split, q_len=T, kv_len=T_kv,
                          one_step=gkv == Tkv),
        grid=(B, H, Tkv // gkv),
        in_specs=[
            pl.BlockSpec((1, 1, Tq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, gkv, D), lambda b, h, j: (b, h // grp, j, 0)),
            pl.BlockSpec((1, 1, gkv, D), lambda b, h, j: (b, h // grp, j, 0)),
            pl.BlockSpec((1, 1, Tq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tq // kp.sub_q, 1, kp.sub_q), lambda b, h, j: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, Tq // kp.sub_q, 1, kp.sub_q), lambda b, h, j: (b, h, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gkv, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, gkv, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tkv, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tkv, D), v.dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return dk[:, :, :T_kv], dv[:, :, :T_kv]


_CALLS = {"fwd": _fwd_call, "dq": _dq_call, "dkv": _dkv_call}


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("kp", "causal", "scale", "interpret"))
def attn(kernel, *operands, kp, causal, scale, interpret):
    """One of the three kernels' calls, jitted: every layer of a model makes
    the same three calls, and unjitted each is traced and lowered again for
    each layer (the written-out loops make that 0.36 s a layer at T = 1024
    and 0.76 s at 2048 where the rolled ones took 0.12; 36 and 24 layers of
    it were 9 and 16 s of a training cell's set-up). The device trace names a
    Pallas call after the innermost jit around it, and this one keeps the
    name the layer's ``attn`` scope gave the calls before it."""
    return _CALLS[kernel](*operands, kp=kp, causal=causal, scale=scale, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """q: (B, H, T, D); k/v: (B, Hkv, T, D) with H divisible by Hkv (GQA
    native — no pre-expansion). Returns (B, H, T, D). ``block_q`` /
    ``block_kv``: upper limits on a block of scores (:func:`plan`)."""
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_kv, scale)
    return out


def _flash_call(q, k, v, causal, block_q, block_kv, scale, plan_=None):
    """``(out, lse)`` at the forward's padded length."""
    H, T, D = q.shape[1:]
    Hkv, T_kv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, f"query heads {H} not a multiple of kv heads {Hkv}"
    if plan_ is None:
        plan_ = plan(T, T_kv, D, jnp.dtype(q.dtype), causal, block_q, block_kv)
        tally(plan_, (q.shape[0], H, -(-T // plan_.fwd.sub_q), 1, plan_.fwd.sub_q))
    return attn("fwd", q, k, v, kp=plan_.fwd, causal=causal,
                scale=scale if scale is not None else 1.0 / (D**0.5),
                interpret=_pallas.interpret())


def _flash_fwd(q, k, v, causal, block_q, block_kv, scale):
    from jax.ad_checkpoint import checkpoint_name
    T = q.shape[2]
    out_p, lse = _flash_call(q, k, v, causal, block_q, block_kv, scale)
    # name the kernel outputs so a remat policy can pin them: re-running the
    # forward kernel inside backward costs ~6% of step time under plain
    # dots_saveable (the custom-call is not a "dot"). Pair with
    # jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    # (models.transformer exposes it as policy "dots_and_attn_saveable").
    out_p = checkpoint_name(out_p, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # residuals keep the UNPADDED operands: backward re-pads (cheap) and the
    # logical q/kv lengths stay statically derivable from the shapes
    return out_p[:, :, :T], (q, k, v, out_p, lse)


def _flash_bwd(causal, block_q, block_kv, scale, res, g_out):
    return _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out)


def _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out, delta_shift=None, plan_=None):
    q, k, v, out_p, lse = res
    B, H, T, D = q.shape
    Hkv, T_kv = k.shape[1], k.shape[2]
    grp = H // Hkv
    if plan_ is None:
        plan_ = plan(T, T_kv, D, jnp.dtype(q.dtype), causal, block_q, block_kv)

    # delta over the logical rows: the XLA reduction it was, T in the lanes
    delta = jnp.einsum("bhtd,bhtd->bht", g_out.astype(jnp.float32),
                       out_p[:, :, :T].astype(jnp.float32))  # (B, H, T)
    if delta_shift is not None:
        delta = delta - delta_shift.astype(jnp.float32)

    static = dict(causal=causal, scale=scale if scale is not None else 1.0 / (D**0.5),
                  interpret=_pallas.interpret())
    dq = attn("dq", q, k, v, g_out, lse, delta, kp=plan_.dq, **static)
    dk, dv = attn("dkv", q, k, v, g_out, lse, delta, kp=plan_.dkv, **static)
    if grp > 1:  # group-sum per-query-head dk/dv back onto the shared KV head
        dk = dk.reshape(B, Hkv, grp, T_kv, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, grp, T_kv, D).sum(axis=2)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """Flash attention that also returns the per-row log-sum-exp —
    the merge currency of ring attention (``ops/pallas/ring_attention.py``):
    two attention results over disjoint KV sets combine exactly from their
    (out, lse) pairs. lse shape (B, H, T); rows that attend nothing are -inf.
    """
    out, lse = _flash_lse_fwd(q, k, v, causal, block_q, block_kv, scale)[0]
    return out, lse


def _flash_lse_fwd(q, k, v, causal, block_q, block_kv, scale):
    T = q.shape[2]
    out_p, lse = _flash_call(q, k, v, causal, block_q, block_kv, scale)
    return (out_p[:, :, :T], lse.reshape(*lse.shape[:2], -1)[:, :, :T]), (q, k, v, out_p, lse)


def _flash_lse_bwd(causal, block_q, block_kv, scale, res, g):
    """The lse cotangent folds into the existing dq/dkv kernels: with
    s-gradient ds = p∘(dp − delta), and dlse/ds = p, the combined cotangent
    is ds = p∘(dp − (delta − g_lse)) — so shifting delta by −g_lse reuses
    both kernels unchanged."""
    g_out, g_lse = g
    return _flash_bwd_impl(causal, block_q, block_kv, scale, res, g_out,
                           delta_shift=g_lse)


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def sharded_flash_attention(q, k, v, causal=True, block_q=512, block_kv=512, scale=None):
    """Mesh-aware flash attention: q (B, H, T, D), k/v (B, Hkv, T, D) with
    full (or head-gathered) sequence per shard.

    A ``pallas_call`` cannot be split by the automatic SPMD partitioner, so on
    a non-trivial mesh the kernel runs inside ``shard_map``: batch over the
    data axes and heads over (seq, tensor) — the head-parallel placement
    Ulysses-style sequence parallelism hands us (DeepSpeed-Ulysses; the
    v0.9.2 reference's long-sequence surface is block-sparse attention,
    ``deepspeed/ops/sparse_attention/``). Falls back to a direct call on a
    trivial mesh or inside an enclosing manual region. When the KV head count
    doesn't divide the head-axis degree, KV is expanded to full heads first —
    every shard_map input must be sharded (a replicated input's cotangent
    would need a psum that check_vma=False disables).
    """
    from ...comm import comm as dist

    if not dist.has_mesh() or dist.in_manual_region():
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)
    mesh = dist.get_mesh()
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    dp_axes, head_axes = dist.attention_partition_axes(B, H)
    if not dp_axes and not head_axes:
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)

    head_degree = int(np.prod([mesh.shape[a] for a in head_axes])) if head_axes else 1
    qspec = P(dp_axes or None, head_axes or None, None, None)
    if head_degree > 1 and Hkv % head_degree != 0:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    kvspec = qspec

    def fn(q, k, v):  # positional: custom_vjp rejects kwargs
        return flash_attention(q, k, v, causal, block_q, block_kv, scale)

    # Mosaic refuses a kernel under any automatic axis, so the size-1 axes go
    # manual too (trivially: nothing is split over them). An unused axis of
    # size > 1 stays automatic — a manual axis the specs don't mention would
    # have its cotangents psum'd (check_vma=False) — and the chip's compiler
    # then refuses the layout by name.
    manual = (set(dp_axes) | set(head_axes)
              | {a for a in mesh.axis_names if mesh.shape[a] == 1})
    with dist.manual_axes(manual):
        # replication checking off: pallas_call out_shapes carry no vma
        # annotations
        return jax.shard_map(fn, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                             out_specs=qspec, check_vma=False,
                             axis_names=manual)(q, k, v)
