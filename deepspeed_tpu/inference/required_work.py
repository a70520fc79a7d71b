"""The work a dispatched step program is REQUIRED to do, counted on the host:
what the serving benchmark's rooflines and live shares divide by.

:class:`RequiredWork` observes each dispatch of the serving pump
(:meth:`~deepspeed_tpu.inference.scheduler.DecodeScheduler._dispatch`, one
call, and only where the sink is on) and counts, from the program's key and the
host's copies of the lengths and spans alone:

- ``serving/step_rows_run`` / ``serving/step_rows_live``: the rows the
  program's forwards compute and those among them inside a row's span;
- ``serving/attn_rows_shared`` / ``serving/attn_rows_window`` /
  ``serving/cross_decoder_rows_unread``: the K/V positions the attention of a
  model with windowed or shared-row layers has to read;
- ``serving/ssd_state_updates`` / ``serving/ssd_chunk_tokens``: the one-token
  updates and chunk positions of a model's Mamba-2 mixers (a ``mamba2``
  layer's, and a ``parallel_hybrid`` layer's beside its attention: such a
  layer counts in this family AND in the attended keys below);
- ``serving/short_conv_updates`` / ``serving/short_conv_chunk_tokens`` /
  ``serving/short_conv_layer_calls``: the same two of a model's gated
  short-convolution layers, and the forwards that run them (each reads the
  operator's weights once);
- ``serving/attn_keys_live`` / ``serving/attn_keys_walked``: the keys inside
  the rows' attended windows and the same rounded out to the blocks the paged
  decode kernel's walk fetches.

It lives beside the scheduler and not under ``telemetry/``: it reads the
model's layer declarations and takes the kernel's own tile choice from
``ops/pallas/decode_attention.py``, and ``telemetry/`` is below both. A model
configuration that needs a new counter adds it here; the pump does not change.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.decode_attention import span_tile, walk_block_kv, walked_keys
from ..telemetry.capacity import program_shape


def layer_mixers(cfg, i):
    """The mixers layer ``i``'s block runs (``TransformerConfig.layer_mixers``:
    a ``parallel_hybrid`` block runs attention AND a Mamba-2 mixer, and both
    are counted); plain attention for a configuration without layer kinds."""
    return cfg.layer_mixers(i) if hasattr(cfg, "layer_mixers") else ("full_attention", )


def attention_walks(model, tp):
    """The layers whose attention over the slot pool runs the paged decode
    kernel (``ops/pallas/decode_attention.py``), grouped by what its walk
    depends on: ``(layers, ring rows, window, (kv heads, query heads a kv
    head, head size, packed))`` for the layers that read rows that grow (ring
    rows and window 0; a cross-attention layer reads the full layer's), a
    windowed layer's ring (the ring's rows), and a plain layer with a sliding
    window (which raises a column's first key). The geometry is the pool
    leaf's, as the model's code hands it to the kernel. Empty where no layer
    does: XLA's attention, ALiBi, a latent pool."""
    from ..models.transformer import kv_packs
    cfg = model.cfg
    if (getattr(cfg, "attention_impl", "xla") != "flash" or getattr(cfg, "latent_width", 0)
            or getattr(cfg, "pos_embedding", None) == "alibi"
            or not hasattr(model, "cache_spec")):
        return []
    carries = getattr(cfg, "carries_across_layers", False)
    if carries and tp > 1:
        return []
    shard = tp if (tp > 1 and getattr(cfg, "bitwise_tp", False)
                   and cfg.kv_heads % tp == 0 and cfg.num_heads % tp == 0) else 1
    groups = collections.Counter()
    for i in range(cfg.num_layers):
        kind = cfg.layer_type(i) if hasattr(cfg, "layer_type") else "full_attention"
        attends = "full_attention" in layer_mixers(cfg, i)
        window = cfg.layer_window(i) if hasattr(cfg, "layer_window") else 0
        if carries:
            if kind == "diff_attention" and window:
                if cfg.ring_rows(i) != window:
                    continue  # such a ring is read through XLA's attention
                groups[(window, 0, cfg.kv_heads // 2, 2 * cfg.head_size, False)] += 1
            elif kind in ("diff_attention", "cross_attention"):
                groups[(0, 0, cfg.kv_heads // 2, 2 * cfg.head_size, False)] += 1
        elif attends and window and getattr(cfg, "layer_windows", ()):
            if cfg.ring_rows(i) == window and shard == 1:  # else XLA reads the ring
                groups[(window, 0, cfg.kv_heads, cfg.head_size, False)] += 1
        elif attends:
            groups[(0, window, cfg.kv_heads // shard, cfg.head_size,
                    kv_packs(cfg.head_size))] += 1
    if getattr(cfg, "mtp_layers", 0):  # the module's own rows, read while it drafts
        groups[(0, 0, cfg.kv_heads // shard, cfg.head_size,
                kv_packs(cfg.head_size))] += cfg.mtp_layers
    return [(n, ring, window, (nkv, cfg.num_heads // shard // nkv, D, packed))
            for (ring, window, nkv, D, packed), n in groups.items()]


class RequiredWork:
    """One observer of the pump's dispatches. Built once, from the sink, the
    model, the slot pool and the mesh's tensor degree; ``state_pool``: the
    pool holds state, ring rows or shared rows, so a prefill row whose chunk
    is not its last stands still in the substeps; ``drafts``: the programs
    are the device drafter's verify-and-draft programs
    (``inference/device_draft.py``), two columns a row a step."""

    def __init__(self, telemetry, model, cache, tp, state_pool=False, drafts=False):
        self.telemetry = telemetry
        self.cache = cache
        self.cfg = cfg = model.cfg
        self.state_pool = state_pool
        self.drafts = drafts
        # (windowed layers, their window, layers that read the shared rows):
        # what the counters of attended rows multiply by
        layers = range(cfg.num_layers) if (getattr(cfg, "carries_across_layers", False)
                                           or any(getattr(cfg, "layer_windows", ()))) else ()
        windows = [cfg.layer_window(i) for i in layers]
        self.attn_layers = (sum(w > 0 for w in windows), max(windows, default=0),
                            sum(not w and cfg.layer_type(i) in ("diff_attention", "cross_attention")
                                for i, w in zip(layers, windows)))
        # Mamba-2 layers: what the counters of state updates multiply by
        mixers = [layer_mixers(cfg, i) for i in range(cfg.num_layers)]
        self.ssd_layers = sum("mamba2" in ms for ms in mixers)
        self.conv_layers = sum("short_conv" in ms for ms in mixers)
        self.attn_walks = attention_walks(model, tp)
        self._walk_blocks = {}

    def dispatched(self, key, split, spans, lens, chunk=None):
        """One step program is being handed to the device. ``key``: its
        compiled-program key (what it is, its width and steps); ``split``:
        whether its first forward runs as two over the live rows
        (``DecodeScheduler._splits_chunk``); ``spans``, ``lens``: the host's
        copies of its spans and lengths operands; ``chunk``: ``(slot,
        final)`` of a chunk sync's prefill row."""
        tel = self.telemetry
        if not tel.enabled:
            return
        width, ksteps = program_shape(key)
        N = self.cache.num_slots
        if self.drafts:
            # the rows the STACK's forwards compute and the live ones among
            # them; the attended keys as the first forward's span gives them,
            # each later step's as one column's (it has two, and advances by
            # 1 or 2: the host cannot know which)
            first = 2 * N + (width if chunk is not None else 0)
            tel.counter("serving/step_rows_run", first + 2 * N * (ksteps - 1))
            stepping = int(np.count_nonzero(spans == 2)) + int(chunk is not None and chunk[1])
            tel.counter("serving/step_rows_live",
                        int(spans.sum()) + 2 * stepping * (ksteps - 1))
            self._count_attention_rows(lens, spans, ksteps, chunk)
            self._count_attention_keys(lens, spans, 2, ksteps, False, chunk, False)
            return
        tel.counter("serving/step_rows_run",
                    (N + width if split else N * width) + N * (ksteps - 1))
        tel.counter("serving/step_rows_live",
                    int(spans.sum()) + int(np.count_nonzero(spans)) * (ksteps - 1))
        self._count_attention_rows(lens, spans, ksteps, chunk)
        self._count_state_updates(spans, ksteps, chunk, 1 + bool(split and width > 1))
        # the programs whose attention walks a row's extent chain
        ext_walk = key is not None and key[0] in ("fused_ext", "fused_seqp")
        self._count_attention_keys(lens, spans, width, ksteps, split, chunk, ext_walk)

    def _count_state_updates(self, spans, ksteps, chunk, first_forwards):
        """For a model with Mamba-2 or gated short-convolution layers: the
        work a sync's state layers are REQUIRED to do, summed over forwards
        and such layers: ``serving/ssd_state_updates`` /
        ``serving/short_conv_updates``, the one-token updates (a live decode
        row in the first forward and in every substep it steps in), and
        ``serving/ssd_chunk_tokens`` / ``serving/short_conv_chunk_tokens``,
        the positions of a prefill chunk (a row's span past 1). Unless its
        chunk is final the prefill row stands still in the substeps. Also
        ``serving/short_conv_layer_calls``: the forwards that run a
        short-convolution layer (``first_forwards`` for the first, 2 where it
        runs as two over the live rows, and one a substep), each of which
        reads the operator's weights once."""
        if not (self.ssd_layers or self.conv_layers):
            return
        live = spans > 0
        stepping = int(np.count_nonzero(live))
        if chunk is not None and not chunk[1] and live[chunk[0]]:
            stepping -= 1
        updates = int(np.count_nonzero(spans == 1)) + stepping * (ksteps - 1)
        tokens = int(spans[spans > 1].sum())
        tel = self.telemetry
        if self.ssd_layers:
            tel.counter("serving/ssd_state_updates", self.ssd_layers * updates)
            tel.counter("serving/ssd_chunk_tokens", self.ssd_layers * tokens)
        if self.conv_layers:
            tel.counter("serving/short_conv_updates", self.conv_layers * updates)
            tel.counter("serving/short_conv_chunk_tokens", self.conv_layers * tokens)
            tel.counter("serving/short_conv_layer_calls",
                        self.conv_layers * (first_forwards + ksteps - 1))

    def _count_attention_rows(self, lens, spans, ksteps, chunk):
        """For a model with windowed or shared-row layers: the K/V positions
        a sync's attention has to read, summed over slots, forwards and
        layers. A forward that leaves a row at ``n`` positions after ``s``
        live columns reads ``n`` shared rows a reading layer (the full layer
        and every cross layer) and ``min(n, window + s - 1)`` ring rows a
        windowed layer, each once whatever the number of queries. Unless its
        chunk is final the prefill row stands still in the substeps. Also
        counts the chunk's positions that run through the cross-decoder
        layers and whose output nothing reads (all but a prompt's last)."""
        tel = self.telemetry
        n_win, window, n_shared = self.attn_layers
        if not (n_win or n_shared):
            return
        live = spans > 0
        after, sp = (lens + spans)[live].astype(np.int64), spans[live]
        shared, ring = int(after.sum()), int(np.minimum(after, window + sp - 1).sum())
        if chunk is not None and not chunk[1]:
            live[chunk[0]] = False
        base = (lens + spans)[live].astype(np.int64)
        for k in range(1, ksteps):
            shared += int((base + k).sum())
            ring += int(np.minimum(base + k, window).sum())
        tel.counter("serving/attn_rows_shared", n_shared * shared)
        tel.counter("serving/attn_rows_window", n_win * ring)
        if chunk is not None and n_shared:
            tel.counter("serving/cross_decoder_rows_unread",
                        int(spans[chunk[0]]) - int(chunk[1]))

    def _walk_block(self, group, span, ext):
        """(columns a kernel call takes of the span, keys a block, blocks a
        row's extents hold) of the kernel's walk for a layer group at a query
        span: the kernel module's own choice."""
        key = (group, span, ext)
        if key not in self._walk_blocks:
            _, ring, _, (nkv, rep, D, packed) = self.attn_walks[group]
            rows = ring or self.cache.max_len
            kv_dtype = jax.tree_util.tree_leaves(self.cache.pool)[0].dtype
            quantized = kv_dtype == jnp.int8
            shape = (D, rows, self.cfg.decode_block_kv, self.cfg.dtype, kv_dtype, quantized,
                     packed)
            tile = span_tile(rep, span, *shape)
            bkv = walk_block_kv(nkv, rep * tile, *shape)
            self._walk_blocks[key] = (tile, bkv,
                                      (self.cache.max_extents if ext else 1) * rows // bkv)
        return self._walk_blocks[key]

    def _count_attention_keys(self, lens, spans, width, ksteps, split, chunk, ext):
        """For every model whose attention runs the paged decode kernel:
        ``serving/attn_keys_live``, the keys inside the rows' attended
        windows, and ``serving/attn_keys_walked``, the same rounded out to
        the blocks the kernel's walk fetches (its own arithmetic:
        ``decode_attention.walked_keys`` at ``walk_block_kv``), summed over
        slots, a step program's forwards (``DecodeScheduler._fused_fn``: the
        first forward whole or as a column and the chunk's (1, C), then the
        substeps) and layers. A forward counts a row's keys once whatever its
        number of columns; one that runs a layer through XLA's attention (a
        windowed layer's chunk) counts nothing for it. The seq-sharded call
        counts as the whole."""
        if not self.attn_walks:
            return
        lens, spans = lens.astype(np.int64), spans.astype(np.int64)
        on = spans > 0
        # (one past each row's write head, keys of its window, the call's span)
        if width == 1 or not split:
            forwards = [(np.where(on, lens + 1, 0), np.where(on, lens + spans, 0), width)]
        else:
            column = spans == 1
            forwards = [(np.where(column, lens + 1, 0), ) * 2 + (1, )]
            if (spans > 1).any():
                ps = int(np.argmax(spans > 1))
                forwards.append((lens[ps:ps + 1] + 1, lens[ps:ps + 1] + spans[ps], width))
        stepping = on.copy()
        if self.state_pool and chunk is not None and not chunk[1]:
            stepping[chunk[0]] = False  # the row stands still (_substep_spans)
        if ksteps > 1:  # the substeps at once: (ksteps - 1, N), a key further each
            ends = np.where(stepping, lens + np.maximum(spans, 1)
                            + np.arange(1, ksteps)[:, None], 0)
            forwards.append((ends, ends, 1))
        live = walked = 0
        for g, (n, ring, window, _) in enumerate(self.attn_walks):
            for ends, keys, span in forwards:
                if span > 1 and (ring or window):
                    continue
                start = np.zeros_like(ends)
                if ring:
                    ends = keys = np.minimum(ends, ring)
                elif window:
                    start = np.maximum(ends - window, 0)
                    keys = ends - start
                tile, bkv, blocks = self._walk_block(g, span, ext)
                live += n * int(keys.sum())
                # a span wider than one kernel call takes walks the keys once a tile
                walked += n * sum(
                    walked_keys(start, np.where(ends > start, ends + t0, ends), tile, bkv, blocks)
                    for t0 in range(0, span, tile))
        self.telemetry.counter("serving/attn_keys_live", live)
        self.telemetry.counter("serving/attn_keys_walked", walked)
