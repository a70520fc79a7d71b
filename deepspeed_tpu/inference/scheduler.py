"""Continuous-batching decode scheduler (iteration-level scheduling) with
chunked prefill fused into the decode step and a radix prefix cache.

The Orca/vLLM serving loop on JAX/XLA: queued requests are admitted into
free KV-cache slots at TOKEN-ITERATION granularity — a finished sequence
evicts mid-loop and the next queued request joins the very next decode step,
without recompiling anything.

**Chunked prefill (Sarathi-Serve)**: admission never runs a whole prompt
as one forward of its own. Each scheduler iteration with a prefill in
flight dispatches ONE fixed-shape fused program whose ids are a
``(num_slots, prefill_chunk)`` block: live decode rows carry their single
next token in column 0, the (at most one) in-flight prefill row carries up
to ``prefill_chunk`` prompt tokens, and per-row query spans mask the rest —
then finishes the sync with the remaining ``steps_per_sync - 1`` decode
steps in one on-device loop, so decode keeps its K-step dispatch
amortization even while prefills chain back-to-back. On one device the
plain per-projection program and the fused int8 decode blocks run that first
forward over the live rows only, where the block is large enough for it to
pay for the bytes of the program's weights (:func:`_split_pays`: over 480
rows of block in bf16, over 240 in int8, so (24, 64) and (8, 64) split in
both and (4, 64) in int8 alone): every slot's column 0, then the chunk as a
``(1, prefill_chunk)`` forward over its own slot
(:func:`_first_forward_live_rows`); the extent, seq-parallel and adapter
variants, a sharded pool and the speculative verify run the whole block. The
fused path's three layer kernels are jitted, so the layers and forwards of a
program share one lowering of each a shape. Decode slots
therefore stall at most one chunk's compute per K tokens instead of a full
prompt, TTFT/decode-p95 trade off via ``prefill_chunk``, and the compiled
program count is O(1) in the prompt-length mix (no per-bucket prefills).

**Radix prefix cache (SGLang RadixAttention)**: finished slots are retained
(not scrubbed) and their prompts registered in a token trie
(:class:`~deepspeed_tpu.inference.kv_cache.RadixPrefixCache`). Admission
walks the trie, copies the longest matched prefix's KV rows from the donor
slot (one compiled ``copy_slot`` program), and chunk-prefills only the
suffix; matches round DOWN to a ``prefill_chunk`` multiple so hit and cold
paths run identical chunk boundaries — cache-hit logits are bit-identical
to a cold prefill. Cached slots are reclaimed LRU-first when admission
needs a slot.

Compiled programs: ONE step program (:meth:`DecodeScheduler._fused_fn`) in
a few variants — width ``prefill_chunk`` for chunk syncs and width 1 for
pure decode syncs, two step counts (K, and 1 for chunks with nothing to
decode), each x greedy/sampling x logits collection — plus the slot-copy
program. O(1) total regardless of the request mix, and fused-vs-decode
results can never diverge because they share one step body (a split chunk
program's column IS the decode program's first forward, shape and all).

Per-slot sampling parameters (do_sample / temperature / top_k / top_p) are
runtime TENSORS, so requests with different sampling configs share one
program. Sampling keys derive from ``fold_in(key(seed), step)`` per slot —
a request's tokens are reproducible no matter which slot it lands in or
what else is in flight.

Each host round trip with no prefill in flight runs ``steps_per_sync``
decode steps in one on-device loop and fetches a (K, num_slots) token block
(multi-step scheduling, the vLLM ``--num-scheduler-steps`` trick): dispatch
+ fetch amortize K-fold, at the cost of K-token admission/eviction
granularity (K=1 recovers pure iteration-level scheduling; results are
identical for any K). EOS detection, admission, and eviction are host-side
bookkeeping on the fetched block.

**The pump is one sync deep**: an iteration LAUNCHES sync N+1 (admit,
assemble, dispatch) and only then LANDS sync N (fetch its token block,
deliver it), so the device runs N+1 the moment N ends while the host
delivers N's tokens, calls the ``on_token`` hooks, admits and assembles N+2.
JAX dispatch is asynchronous already: the pool that N returns is handed to
N+1 as a future. The host's view at a launch is the state after everything
LAUNCHED: ``cache.lengths`` as a whole counts the rows written by every
launched sync, a request's ``inflight`` its tokens no landing has delivered
yet (its sampling step is ``len(out) + inflight``; a row whose budget ends
in flight is left out of the next launch; the prefill lane advances, and a
final chunk's row is booked as a decode row and its prompt registered in the
trie, when that chunk is launched). The one input the host lacks, each
decoding row's last token, stays on the device: a tiny jitted merge
(:func:`_merge_carried`) writes the last row of N's token block into column
0 of N+1's ids for the rows flagged as carrying, outside the step programs.
What a launch cannot know is an EOS or a cancellation inside N: N+1 then
computes that row once more and its landing drops it (counted:
``serving/ahead_rows_discarded``); the slot is released when N lands, one
sync later than behind a serial pump, and what N+1 wrote there lies past the
row's final length (a state leaf is reset at the next admission) and is
ordered before any later program by the device's queue. Where the next
sync's inputs need the last one's results ON THE HOST the pump lands before
it launches, by what it can observe and with no setting
(:meth:`DecodeScheduler._lands_first`): a drafter, cold-expert offload,
parked or chained rows, a migrate hook on a final
chunk, a row flagged for cancellation. ``pause`` / ``flush`` / ``drain`` /
``swap_weights`` / ``migrate_out`` / ``admit_migration`` land what is in
flight first. Counters ``serving/syncs_ahead`` (launched with the previous
sync unlanded) and ``serving/syncs_serial``. A pump that runs ahead leaves
the device no gap to measure, so with the sink on it accounts for every
landed sync from its span boundaries (``telemetry/capacity.py:
HostGapTracker``): the host's work, by span, and the wait for the device
(``serving/pump_busy_ms``, ``serving/pump_wait_ms``, ``serving/pump/*``).
Where the wait nears 0 the host sets the pace.

**Self-speculative k-token decoding** (Leviathan et al. / prompt-lookup
drafting, ``spec_tokens > 0``, ``spec_draft: "ngram"``): each pure-decode sync first asks a host-side
:class:`~deepspeed_tpu.inference.speculative.PromptLookupDrafter` for up to
``spec_tokens`` continuation proposals per live slot, then verifies ALL of
them in ONE fused span step — the same ``q_spans`` machinery chunked
prefill rides, with the draft tokens as extra query columns. Every column
is sampled with the request's own keys at its absolute step index and a
draft commits only when it EQUALS the sampled token, so the emitted stream
is bit-identical to non-speculative decode (greedy and sampled alike); the
first mismatch truncates and the garbage KV rows past the accepted prefix
sit beyond the write head until later writes reclaim them. A sync where no
slot drafts falls back to the plain ``steps_per_sync`` decode program, so
the drafter being dry costs nothing. Compiled programs gain only the spec
variant at width ``1 + spec_tokens`` — O(1) in k and acceptance mix. A host
drafter reads the accepted tokens, so its pump is serial.

**Drafting on the device** (``spec_draft: "module"``, ``spec_tokens: 1``;
``inference/device_draft.py``): a model with a multi-token-prediction module
(``mtp_layers``) drafts inside the step program. Each of a sync's steps
verifies two columns a row (its last token and the module's draft), samples
behind both, decides the advance (1 or 2) ON THE DEVICE, runs the module over
the committed pairs (its own K/V rows in the pool, behind the stack's layers)
and hands the next step its token, draft, write head and sampling step; the
same four are carried to the next sync where it is launched ahead, so the
pump stays one sync deep and the host learns advances at the landing (a row
in flight is booked at 2 tokens a step until then). Void columns roll back by
position, in rows that grow and in a windowed layer's ring alike. Bound at
construction (``_launch_chunk`` / ``_launch_decode`` / ``_land``): a pool
without such a drafter runs the programs and the pump it ran before.

**int8 paged KV** (``kv_cache_dtype: "int8"``): the slot pool stores
group-quantized K/V (per-token-row fp16 scales, ``ops/quantizer``
``quantize_kv_rows``); dequantization fuses into the paged Pallas kernels
so bf16 KV never materializes in HBM — roughly doubling resident slots per
chip at a small bounded logit error.

**Hierarchical KV tier** (``continuous_batching.hierarchical_kv``,
``deepspeed_tpu/memory/``): radix-evicted prefixes DEMOTE their slot KV to
a fleet-global host store (optional NVMe spill) through the shared
streaming layer instead of being destroyed, and admission RESTORES the
longest host match into the fresh slot ahead of chunked prefill — same
rounding as a device hit, so restored == device-hit == cold stays
bit-identical. The store is shared across the ReplicaSet, so any replica
restores a prefix any other computed. See ``benchmarks/SERVING.md``
("Hierarchical KV").

**Multi-LoRA serving** (``continuous_batching.multi_lora``,
``deepspeed_tpu/adapters/``): per-request ``adapter_id`` selects a model
variant whose (A, B) pages live in the fleet-shared rank-bucketed
:class:`~deepspeed_tpu.adapters.PagedAdapterStore`; heterogeneous-adapter
batches decode through ONE fused program that gathers each row's pages by a
runtime slot index (``base(x) + (x @ A_row) @ B_row`` per projection site),
so compile count is O(1) in adapter count, mix, and load/evict churn.
Base-only dispatches run the byte-identical pre-adapter program variant.
Radix/host-tier prefix registrations carry the adapter uid (per-adapter
trie roots + negative-sentinel store namespaces): cross-adapter KV reuse is
structurally impossible, and a page eviction or adapter reload queues an
invalidation this scheduler drains on its own pump thread.

**Weight-swap protocol** (RLHF hybrid engine, ``deepspeed_tpu/rlhf/``):
``pause()`` gates admission, ``flush()`` drains in-flight rows under the
weights that prefilled them, ``swap_weights(params)`` invalidates the radix
trie and ALL retained KV (weights-version stamps make cross-version reuse a
structural error) and installs the new tree, ``resume()`` re-opens
admission. All host bookkeeping on the scheduler thread; zero new XLA
programs per cycle. See ``benchmarks/RLHF.md``.

**MoE serving**: models with routed experts decode through the SAME step
programs — gating + per-token capacity-free top-k dispatch run inside the
compiled step (``moe/sharded_moe.top_k_serving_weights``: no capacity
buffers, so a request's logits never depend on co-resident slots), expert
kernels shard over the ``expert`` mesh axis with an all-gather combine
(ep>1 bit-identical to the ep=1 replicated program, composed freely with
tp>1), and ``continuous_batching.expert_offload`` pages cold expert
kernels through per-(layer, expert) LRU device pools
(``moe/expert_store.py``) with detect-miss-and-replay dispatch + a
backoff ladder (:meth:`_call_step`) — exact at any residency, compile
count O(1) in expert count, routing mix, and churn (every reachable
variant warms at build via :meth:`warm_programs`). See
``benchmarks/SERVING.md`` ("MoE serving").

**Recurrent state beside rows**: a model with linear-attention layers
(``layer_types``) holds, for those layers, a per-slot recurrent state and
convolution window in the pool and no rows (``CausalLMModel.cache_spec``;
:class:`~deepspeed_tpu.inference.kv_cache.SlotKVCache` counts rows and state
apart). The same step programs serve it: a row's state advances over exactly
its live columns, a span-0 slot's leaves come out bit for bit as they went
in, a span that starts at position 0 starts from zero. Such a pool's
programs take one more operand, the rows' substep spans
(:meth:`DecodeScheduler._substep_spans`). Prefix reuse is off for it (counted:
``serving/prefix_cache_state_bypass``) and speculative verify, extent chains,
sequence-parallel prefill, tiering, migration, lossy windows, an int8 pool,
adapters and a tensor-parallel pool are refused at build: a state has no
rows to mask, copy by length or roll back (a RING does roll back the one void
column of a device drafter's step, and only that: "Drafting on the device"). Gauges
``serving/state_bytes_per_slot``, ``serving/state_slots_live``; counter
``serving/state_slots_reset``. See ``benchmarks/SERVING.md`` ("Recurrent
state beside K/V rows").

**Ring rows and shared rows**: the scheduler follows the model's declaration
(``cache_kinds``), not a layer kind's name: a pool with any leaf that is not
``"rows"`` (``"state"``; ``"ring"``: a windowed layer's K and V in a ring of
about its window's rows, position ``p`` in row ``p mod R``: a differential
layer's, or a plain attention layer's with its keys rotated at rest) or with a layer
that declares nothing (a gated memory unit; a cross-attention layer that
reads the rows of the full layer below it) is such a pool: same substep-spans
operand (a ring does not forgive a garbage substep either), same refusals,
same bypass counter. Gauge ``serving/window_bytes_per_slot``. See
``benchmarks/SERVING.md`` ("Ring rows, shared rows and SSM state").

**One-sublayer blocks** (``nemotron_h``: a Mamba-2 mixer, an expert FFN or
attention alone in each layer): a Mamba-2 layer declares ``"state"``, an
attention layer ``"rows"``, an FFN-only layer nothing, and holds nothing; the
pool is a state pool with the same operand, refusals and bypass counter. See
``benchmarks/SERVING.md`` ("One-sublayer blocks").

**Two mixers in one block** (``falcon_h1``: ``parallel_hybrid`` layers, a
Mamba-2 mixer AND attention on one normed input): every layer declares
``"rows", "rows", "state", "state"`` and its slot holds both kinds; the pool is
a state pool with the same operand, refusals and bypass counter although every
layer also holds rows. See ``benchmarks/SERVING.md`` ("Two mixers in one
block").

**Gated short convolutions** (``lfm2_moe``: ``short_conv`` layers beside
attention): such a layer declares ONE ``"state"`` leaf, the gated inputs of a
slot's last ``short_conv_kernel - 1`` positions, and an attention layer its
``"rows"``, packed at head size 64; the pool is a state pool with the same
operand, refusals and bypass counter. See ``benchmarks/SERVING.md`` ("Gated
short convolutions beside attention").

**Required work**: what a dispatched step program HAS to do (the rows its
forwards compute and the live ones among them, the K/V positions and keys its
attention reads, its Mamba-2 layers' state updates), which the serving
benchmark's rooflines and live shares divide by, is counted from the program's
key and the host's lengths and spans by ONE observer of a dispatch,
:class:`~deepspeed_tpu.inference.required_work.RequiredWork`, built only where
the sink is on. Its module lists the ``serving/step_rows_*``, ``attn_rows_*``,
``attn_keys_*``, ``ssd_*`` and ``cross_decoder_rows_unread`` counters; a model
configuration that needs another adds it there, not here.

Telemetry (PR-1 sink): gauges ``serving/slot_occupancy``,
``serving/batch_efficiency``, ``serving/kv_token_utilization``,
``serving/prefix_cache_hit_rate``, ``serving/spec_acceptance_rate``,
``serving/kv_bytes_per_token``, ``serving/kv_cache_capacity_bytes``,
``serving/kv_bytes_live``; counters ``serving/admitted``,
``serving/evicted``, ``serving/decode_steps``, ``serving/decode_tokens``,
``serving/prefix_cache_{hit,miss,evict}``,
``serving/prefix_cache_{demote,restore,restore_tokens,spill}`` (+ gauges
``serving/kv_host_tier_bytes``, ``serving/kv_tier_hit_rate``) on the
hierarchical tier, ``serving/spec_steps``,
``serving/spec_draft_tokens``, ``serving/spec_accepted_tokens``;
histograms ``serving/ttft_ms``, ``serving/step_ms``,
``serving/tokens_per_step``, ``serving/spec_tokens_per_step``. Multi-LoRA adds
``serving/adapter_{loads,evicts}`` + per-adapter
``serving/adapter/<id>/{loads,evicts,requests,tokens}`` (256-label cap),
``serving/adapter_swap_ms``, ``serving/adapter_kv_invalidated_tokens``, and
gauges ``serving/adapters_resident``, ``serving/adapter_pool_bytes``,
``serving/adapter_hit_rate``.
"""

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import comm as dist
from ..telemetry.capacity import program_shape
from .config import check_prefill_chunk
from .device_draft import DeviceDraft
from .engine import _round_up
from .kv_cache import RadixPrefixCache, SlotKVCache, copy_slot, slot_slice, slot_update
from .required_work import RequiredWork
from .sync import _CARRIED, _Flight, _Operands, _merge_carried, _replicate_logits, _sampler
from .speculative import PromptLookupDrafter

# Guards COMPILED-PROGRAM CACHE INSERTION only (replica sets share one
# program cache across per-replica pump threads; two threads racing the
# same missing key would each jit their own closure — two XLA programs
# where the O(1)-compile contract promises one). Step dispatch itself is
# unlocked: each scheduler stays single-threaded within its own pump.
_PROGRAM_LOCK = threading.RLock()

# Host-store namespace for mid-decode extent demotion: parked extent entries
# key as ``(_EXT_NS, rid, extent_idx)`` — a negative sentinel no prompt
# token-tuple or adapter namespace can collide with (same convention as the
# adapter store's negative-uid namespaces). Entries are pinned and held by
# the owning scheduler; probes can never surface them.
_EXT_NS = -0x10C7E57

# Rows of one forward below which its time is the weight stream's, for each
# byte a weight's element takes: a v5e's ridge is 197e12 / 819e9 = 240
# operations a byte and a row does 2 operations on an element, so a bf16 weight
# (2 bytes) has its ridge at 240 rows and an int8 weight (1 byte) at 120. A
# forward over r rows then costs about max(1, r / ridge) weight streams.
_RIDGE_ROWS_PER_BYTE = 120


def _split_pays(n, c, weight_bytes=2):
    """Whether a chunk sync's first forward is cheaper as two forwards over
    its live rows (``n`` decode rows as one column, the chunk's ``c`` columns
    over its own slot: two weight streams) than as one over the ``(n, c)``
    block (one stream, n * c rows of compute). ``weight_bytes``: the bytes of
    a weight's element in the program (2: bf16, the ridge at 240 rows; 1: the
    int8 weights of the fused decode blocks, 120 rows). Both split (64, 256),
    (24, 64) and (8, 64) and keep (4, 16) and any block of one slot; they
    differ between 240 and 480 rows of block: int8 splits (4, 64), (8, 32) and
    (2, 128), which bf16 keeps."""
    streams = lambda rows: max(1.0, rows / (_RIDGE_ROWS_PER_BYTE * weight_bytes))
    return streams(n * c) > streams(n) + streams(c)


def _first_forward_live_rows(forward, pool, ids, lengths, spans):
    """A chunk sync's first forward as two over its live rows, in place of one
    over the ``(N, C)`` block: every slot's column 0 (the decode program's own
    first forward; the chunk's row rides with span 0 and writes nothing), then
    the chunk's columns as a (1, C) forward over its own slot's rows of the
    pool. The chunk's row is the one whose span is over 1; with none (a
    warm-up, a final chunk of one token, which rides the column) the second
    forward runs slot 0 with span 0. ``forward(pool, ids, positions,
    write_index, spans)`` returns (logits, pool, stats or None, choice or
    None). Returns each row's last live logits, the pool, the two forwards'
    summed stats and the choice in the block's (L, N, C, k) shape."""
    C = ids.shape[1]
    wide = spans > 1
    ps = jnp.argmax(wide)
    lg, pool, cnt, ch = forward(pool, ids[:, :1], lengths[:, None], lengths,
                                jnp.where(wide, 0, spans))
    take = jnp.where(wide[ps], spans[ps], 0)
    start = lengths[ps]
    lgc, rows, cntc, chc = forward(slot_slice(pool, ps), ids[ps][None],
                                   (start + jnp.arange(C))[None], start[None], take[None])
    pool = slot_update(pool, ps, rows)
    last = jnp.where(wide[:, None], lgc[0, jnp.maximum(take - 1, 0)][None], lg[:, 0])
    if cnt is not None:
        cnt = cnt + cntc
    if ch is not None:
        ch = jnp.pad(ch, ((0, 0), (0, 0), (0, C - 1), (0, 0)))
        own = jax.lax.dynamic_slice_in_dim(ch, ps, 1, axis=1)
        ch = jax.lax.dynamic_update_slice_in_dim(ch, jnp.where(wide[ps], chc, own), ps, axis=1)
    return last, pool, cnt, ch


class _ExpertOverflow(Exception):
    """A cold-expert dispatch routed more experts into some layer than the
    resident pool holds — the step cannot run in one dispatch at this
    shape. Carries the (donated-through) pool so the caller's state stays
    consistent before it backs off to a smaller step."""

    def __init__(self, pool):
        super().__init__("per-layer expert demand exceeds resident_experts")
        self.pool = pool


class _Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id", "do_sample",
                 "temperature", "top_k", "top_p", "seed", "slot", "out", "logits",
                 "done", "cancelled", "submit_ts", "first_token_ts", "collect_logits",
                 "on_token", "trace", "adapter_id", "adapter_ref", "handle",
                 "migrating", "error", "kv_window", "row_budget", "choice", "inflight",
                 "draft", "draft_logits")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id, do_sample,
                 temperature, top_k, top_p, seed, collect_logits, submit_ts,
                 on_token=None, trace=None, adapter_id=None, kv_window=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("scheduler requires at least one prompt token")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF  # device-side key seed is uint32
        self.collect_logits = bool(collect_logits)
        self.slot = None
        self.out = []      # generated token ids (host ints)
        # tokens of this request that a launched sync computes and no landing
        # has delivered yet: THE place that says how far the device is ahead of
        # ``out`` (the row's sampling step, whether its budget ends in flight
        # and whether its next id is still on the device all derive from it)
        self.inflight = 0
        self.logits = []   # per-step (V,) logits when collect_logits
        self.choice = []   # (L, columns, k) expert ids per forward (MoE, collect_logits)
        # a device drafter's (device_draft.py): its draft of the token after
        # ``out[-1]``, as the last landing left it, and, collecting, the
        # module's (V,) logits beside each entry of ``logits``
        self.draft = 0
        self.draft_logits = []
        self.done = False
        self.cancelled = False
        self.submit_ts = submit_ts
        self.first_token_ts = None
        self.on_token = on_token
        self.trace = trace  # optional telemetry.tracing.RequestTrace
        # multi-LoRA serving: the requested model variant and, once
        # admitted, the pinned AdapterRef its rows gather pages through
        self.adapter_id = adapter_id
        self.adapter_ref = None
        # disaggregated serving: the handle issued at submit (re-pointed
        # when the request migrates schedulers) and the in-handoff flag
        # (True between migrate-out on the prefill replica and admission
        # on a decode replica — the request is then owned by NO scheduler)
        self.handle = None
        self.migrating = False
        # terminal error (migration failures): done=True with this set
        # means the request FAILED, not completed — the gateway answers
        # 500 and SchedulerHandle.result() raises instead of returning a
        # silently truncated stream
        self.error = None
        # lossy long-context mode: (sink, recent) sliding-window knob — the
        # request attends only its first ``sink`` and last ``recent`` tokens
        # (StreamingLLM), which BREAKS bit-identity and is gated behind the
        # scheduler's allow_lossy_kv. None = lossless (the default)
        self.kv_window = kv_window
        # KV rows reserved past the prompt (multi-step/spec overshoot
        # rounding, stamped at submit): admission sizes extent chains from
        # prompt + row_budget so a chain can never stall mid-decode
        self.row_budget = 0


class SchedulerHandle:
    """Future-like handle for one scheduled request. ``result()`` pumps the
    shared scheduler loop (serving every in-flight request, not just this
    one) until this request finishes."""

    __slots__ = ("_sched", "_req")

    def __init__(self, sched, req):
        self._sched = sched
        self._req = req

    @property
    def done(self):
        return self._req.done

    def cancel(self):
        """Flag the request for eviction. Pure host bookkeeping — safe to
        call from GC/__del__: the single-threaded scheduler loop frees the
        slot (or drops the queued request) at its next iteration, so
        nothing mutates mid-decode-step."""
        self._req.cancelled = True

    def result(self):
        while not self._req.done:
            self._sched.step()
        if self._req.error is not None:
            # a silently truncated array would be indistinguishable from a
            # normal EOS completion — fail loudly instead
            raise RuntimeError(self._req.error)
        return np.asarray(self._req.out, np.int32)

    def result_logits(self):
        """(T, V) per-generated-token logits (requires ``collect_logits``)."""
        self.result()
        if not self._req.collect_logits:
            raise ValueError("request was not submitted with collect_logits=True")
        if self._req.logits:
            return np.stack(self._req.logits)
        V = self._sched.engine.model_config.vocab_size
        return np.zeros((0, V), np.float32)

    def result_draft_logits(self):
        """(T, V) logits of the drafting module beside :meth:`result_logits`'s
        rows (a device drafter, ``collect_logits``): row ``j`` is the module's
        prediction of generated token ``j + 1`` from the stack's state that
        chose token ``j``, and token ``j``."""
        self.result()
        if not self._req.draft_logits:
            raise ValueError("no draft logits were collected for this request")
        return np.stack(self._req.draft_logits)

    def result_choice(self):
        """(L, T, k) expert ids every layer's router chose at the positions
        the step programs ran for this request, in order: its prompt, then
        each token it was fed (``collect_logits`` on an MoE model; positions
        a prefix hit copied were not run). What a reference follows where
        routing is a near tie."""
        self.result()
        if not self._req.choice:
            raise ValueError("no routing choice was collected for this request")
        return np.concatenate(self._req.choice, axis=1)


class _PrefillState:
    """The (at most one) in-flight chunked prefill: ``pos`` is the next
    prompt position to feed — rows ``[0, pos)`` of the slot already hold KV
    (prefix-cache copy and/or earlier chunks)."""

    __slots__ = ("req", "pos", "seq_parallel")

    def __init__(self, req, pos):
        self.req = req
        self.pos = pos
        # sequence-parallel chunked prefill: this prefill's wide forwards
        # run at the seq-parallel chunk width (sharded over the seq mesh
        # axis when it has more than one device)
        self.seq_parallel = False


class DecodeScheduler(DeviceDraft):
    """Continuous-batching serving loop over an :class:`InferenceEngine`.

    ``num_slots`` fixes the decode batch (the pool shape XLA compiles
    against); ``max_len`` is the per-slot KV capacity. Requests whose
    ``prompt + max_new_tokens`` exceed ``max_len`` are rejected at submit.

    ``prefill_chunk`` (at least 1) is how many prompt tokens of the one
    in-flight prefill ride each sync (see module docstring).
    ``prefix_cache`` retains finished prefixes for cross-request KV reuse
    (matches round down to chunk boundaries, which keeps hit and cold paths
    bit-identical).
    """

    def __init__(self, engine, num_slots=8, max_len=None,
                 collect_logits=False, steps_per_sync=4, prefill_chunk=64,
                 prefix_cache=True, spec_tokens=0, spec_ngram_max=3,
                 spec_ngram_min=1, spec_draft="ngram", kv_cache_dtype="auto",
                 compiled_cache=None,
                 prefix_store=None, restore_min_tokens=0, adapter_store=None,
                 expert_store=None, max_extents=1, seq_parallel_min_tokens=0,
                 seq_parallel_degree=0, allow_lossy_kv=False):
        self.engine = engine
        # raw constructor args, so a replica set can clone this scheduler's
        # exact configuration for its sibling replicas (normalization —
        # max_len rounding, chunk clamping — re-runs identically).
        # ``prefix_store``, ``adapter_store`` AND ``expert_store`` ride
        # along BY REFERENCE: every replica's tier client binds the same
        # fleet-global host store / paged pools, which is what makes a
        # prefix (or an adapter/expert page) computed/loaded on replica A
        # servable on replica B
        self._init_kwargs = dict(
            num_slots=num_slots, max_len=max_len,
            collect_logits=collect_logits, steps_per_sync=steps_per_sync,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
            spec_tokens=spec_tokens, spec_ngram_max=spec_ngram_max,
            spec_ngram_min=spec_ngram_min, spec_draft=spec_draft,
            kv_cache_dtype=kv_cache_dtype, prefix_store=prefix_store, restore_min_tokens=restore_min_tokens,
            adapter_store=adapter_store, expert_store=expert_store,
            max_extents=max_extents,
            seq_parallel_min_tokens=seq_parallel_min_tokens,
            seq_parallel_degree=seq_parallel_degree,
            allow_lossy_kv=allow_lossy_kv)
        model = engine.module
        cfg = engine._config
        if max_len is None:
            max_len = min(model.cfg.max_seq_len, cfg.max_out_tokens)
        # pool length: multiple of the decode KV block (same rule as the
        # static path) so the paged kernel's block walk tiles evenly; when
        # the model's max_seq_len caps it, round DOWN so the tiling holds
        # (the kernel needs S % block only when S exceeds one block)
        block = cfg.decode_block_kv
        S = int(_round_up(max_len, 64))
        if S > block:
            S = int(_round_up(S, block))
        if S > model.cfg.max_seq_len:
            S = model.cfg.max_seq_len
            if S > block:
                S = (S // block) * block
        if S < 1:
            raise ValueError(f"model max_seq_len {model.cfg.max_seq_len} leaves no "
                             f"room for a KV slot")
        self.max_len = S
        self.collect_logits = bool(collect_logits)
        # multi-step scheduling (vLLM --num-scheduler-steps): K decode steps
        # per host round trip. The K-step program is ONE compiled XLA loop,
        # so dispatch + device_get amortize K-fold; admission/eviction
        # granularity becomes K tokens (K=1 recovers pure iteration-level
        # scheduling). Token/logits results are IDENTICAL for any K:
        # sampling keys fold in the absolute step index.
        self.steps_per_sync = max(1, int(steps_per_sync))
        # chunked prefill: clamp the chunk to the slot capacity (a chunk
        # wider than a slot could never land a full write)
        self.prefill_chunk = min(check_prefill_chunk(prefill_chunk), S)
        # ---- long-context serving: multi-extent paged KV, seq-parallel
        # chunked prefill, mid-decode cold-range demotion ------------------
        me = max(1, int(max_extents))
        # a chain's logical positions are bounded by the model's rope/mask
        # horizon — extents past max_seq_len could never hold a valid row
        me = max(1, min(me, model.cfg.max_seq_len // S))
        self.allow_lossy_kv = bool(allow_lossy_kv)
        self.seq_parallel_min_tokens = max(0, int(seq_parallel_min_tokens))
        seq_on = self.seq_parallel_min_tokens > 0
        seq_ax = int(engine.mesh.shape[dist.SEQ_AXIS])
        tp_ax = int(engine.mesh.shape[dist.TENSOR_AXIS])
        self._seq_shards = seq_ax if (seq_on and seq_ax > 1) else 1
        if self._seq_shards > 1 and tp_ax > 1:
            raise ValueError(
                "sequence-parallel prefill composes with tp=1 only: the "
                "seq-sharded span kernel gathers over the seq axis while "
                "tensor parallelism already shards the attention heads")
        if seq_on:
            # seq-parallel chunk width: the configured degree (default: the
            # seq mesh axis) times the base chunk, clamped to the extent and
            # rounded to a shard multiple (the sharded kernel splits the
            # query block evenly across the seq axis)
            deg = max(1, int(seq_parallel_degree) or seq_ax)
            Cs = min(deg * self.prefill_chunk, S)
            Cs = max((Cs // self._seq_shards) * self._seq_shards,
                     self.prefill_chunk)
            self._seq_chunk = Cs
        else:
            self._seq_chunk = 0
        if ((me > 1 or self._seq_chunk or self.allow_lossy_kv)
                and getattr(model.cfg, "attention_impl", "xla") != "flash"):
            raise ValueError(
                "long-context serving (max_extents > 1 / seq-parallel "
                "prefill / lossy KV windows) requires "
                "attention_impl='flash': the extent block walk and the "
                "seq-sharded span kernel live in the paged Pallas path")
        # KV storage tier: "auto" rides the model compute dtype; "int8" is
        # the group-quantized paged tier (int8 K/V leaves, joint per-token-
        # row scales); explicit float names force that precision
        kvd = str(kv_cache_dtype or "auto").lower()
        if kvd in ("auto", "model", "none"):
            kv_arg = None
        elif kvd == "int8":
            kv_arg = "int8"
        else:
            from .config import _DTYPE_MAP
            if kvd not in _DTYPE_MAP or _DTYPE_MAP[kvd] == jnp.int8:
                raise ValueError(f"kv_cache_dtype must be 'auto', 'int8', or a float "
                                 f"dtype name, got {kv_cache_dtype!r}")
            kv_arg = _DTYPE_MAP[kvd]
        self.kv_quantized = kv_arg == "int8"
        if getattr(model.cfg, "latent_width", 0):
            # the latent pool (one leaf a layer, see CausalLMModel.init_cache)
            # is served as it is or not at all
            unsupported = [name for name, on in (
                ("an int8 KV pool (kv_cache_dtype)", self.kv_quantized),
                ("extent chains (max_extents > 1)", int(max_extents) > 1),
                ("sequence-parallel prefill", bool(self._seq_chunk)),
                ("lossy KV windows", self.allow_lossy_kv),
                ("a tensor-parallel pool", tp_ax > 1),
                ("tier demotion (prefix_store)", prefix_store is not None),
                ("adapters (adapter_store)", adapter_store is not None)) if on]
            if unsupported:
                raise ValueError("the latent KV pool does not support "
                                 + ", ".join(unsupported) + " yet")
        # who drafts (spec_tokens > 0): the host-side n-gram drafter, or the
        # model's own multi-token-prediction module inside the step program
        # (device_draft.py), decided here once and for all
        if spec_draft not in ("ngram", "module"):
            raise ValueError(f"spec_draft must be 'ngram' or 'module', got {spec_draft!r}")
        self._device_draft = spec_draft == "module" and int(spec_tokens) > 0
        if self._device_draft:
            unsupported = [name for name, on in (
                ("a model without a multi-token-prediction module (mtp_layers)",
                 not getattr(model.cfg, "mtp_layers", 0)),
                ("spec_tokens other than 1 (the module drafts one token a step)",
                 int(spec_tokens) != 1),
                ("a prefill_chunk under 3", self.prefill_chunk < 3),
                ("a latent pool", bool(getattr(model.cfg, "latent_width", 0))),
                ("extent chains (max_extents > 1)", int(max_extents) > 1),
                ("sequence-parallel prefill", bool(self._seq_chunk)),
                ("lossy KV windows", self.allow_lossy_kv),
                ("tier demotion (prefix_store)", prefix_store is not None),
                ("adapters (adapter_store)", adapter_store is not None),
                ("cold-expert offload (expert_store)", expert_store is not None),
                ("a sharded pool", tp_ax > 1 or int(engine.mesh.shape[dist.EXPERT_AXIS]) > 1),
            ) if on]
            if unsupported:
                raise ValueError("spec_draft='module' (drafting on the device) does not "
                                 "support " + "; ".join(unsupported))
        # per-slot STATE or a RING of rows beside the rows that grow, or rows
        # that layers share, as the model declares them (cache_kinds): a
        # state has no rows for per-slot ends to mask and no past to roll
        # back to, a ring forgets what a copy by prefix would need, so
        # everything that copies, truncates, moves or re-reads a slot's rows
        # is refused here, by name. A ring does roll back by position, the ONE
        # void column of a device drafter's step (_ring_attention); a host
        # drafter's wider verify would displace keys a later query still needs
        kinds = model.cache_kinds() if hasattr(model, "cache_kinds") else None
        declared = set(jax.tree_util.tree_leaves(kinds))
        # a layer that declares nothing reads rows a layer below it wrote,
        # unless its block has no mixer at all (an FFN alone: nemotron_h)
        mixer_of = getattr(model.cfg, "layer_parts", lambda i: ("full_attention", "mlp"))
        shared = kinds is not None and any(
            not layer and mixer_of(i)[0] is not None
            for i, layer in enumerate(model.cache_spec(1, 1)))
        held = [name for name, on in (("recurrent state", "state" in declared),
                                      ("ring rows", "ring" in declared),
                                      ("rows that layers share", shared)) if on]
        self._state_pool = bool(held)
        if self._state_pool:
            unsupported = [name for name, on in (
                ("speculative verify (spec_tokens): a recurrent state cannot roll back the "
                 "rejected columns" if "state" in declared else
                 "speculative verify by a host drafter (spec_tokens with spec_draft='ngram'): "
                 "a ring rolls back ONE void column, the device drafter's (spec_draft="
                 "'module')", int(spec_tokens) > 0
                 and ("state" in declared or not self._device_draft)),
                ("extent chains (max_extents > 1)", int(max_extents) > 1),
                ("sequence-parallel prefill", bool(self._seq_chunk)),
                ("tier demotion (prefix_store)", prefix_store is not None),
                ("lossy KV windows", self.allow_lossy_kv),
                ("an int8 KV pool (kv_cache_dtype)", self.kv_quantized),
                ("adapters (adapter_store)", adapter_store is not None),
                ("a tensor-parallel pool", tp_ax > 1)) if on]
            if unsupported:
                raise ValueError("a slot pool that holds " + ", ".join(held)
                                 + " (layer_types) does not support "
                                 + "; ".join(unsupported) + " yet")
        self.cache = SlotKVCache(engine._init_cache(int(num_slots), S, kv_dtype=kv_arg),
                                 int(num_slots), S, page_size=min(block, S),
                                 max_extents=me, kinds=kinds)
        # self-speculative decoding: spec_tokens drafted columns verified
        # per pure-decode sync (clamped so a full verify block always fits
        # one slot alongside at least one row of decode headroom)
        self.spec_tokens = max(0, min(int(spec_tokens), max(0, S - 2)))
        self._spec_width = 1 + self.spec_tokens
        # the host-side drafter; a device drafter is no object of the host's
        self.drafter = (PromptLookupDrafter(self.spec_tokens, spec_ngram_max,
                                            spec_ngram_min)
                        if self.spec_tokens > 0 and not self._device_draft else None)
        self.spec_steps = 0       # spec verify dispatches
        self.spec_row_steps = 0   # (live row, spec step) pairs
        self.spec_drafted = 0     # draft tokens submitted to verification
        self.spec_accepted = 0    # draft tokens that committed
        self.spec_delivered = 0   # tokens delivered by spec steps
        # radix prefix cache: reuse rounds matches to chunk boundaries so a
        # hit replays the cold path's exact programs
        # A pool with state leaves serves every prompt cold: the radix copy
        # trusts per-slot ends to mask the donor's rows past the match, and a
        # state has no rows to mask (a hit at any prefix but the donor's own
        # end would be silently wrong). The lookups not made are counted.
        # A device drafter's pool serves cold too: its rows past a match hold
        # the void columns of the donor's steps
        cold = self._state_pool or self._device_draft
        self.radix = RadixPrefixCache(self.cache) if prefix_cache and not cold else None
        self._state_bypass = bool(prefix_cache) and cold
        self.prefix_cache_state_bypass = 0  # lookups not made
        self.state_slots_reset = 0  # requests begun from a zero state
        # hierarchical KV tier: a shared GlobalPrefixStore turns radix
        # eviction into demotion (device -> host/NVMe) and admission into
        # restoration — LRU pressure stops destroying reuse, and the store
        # being fleet-global means ANY replica restores what any other
        # computed. Needs the radix cache (restores replay the hit path).
        self.kv_tier = None
        if prefix_store is not None and self.radix is not None:
            from ..memory.kv_tier import KVTier
            self.kv_tier = KVTier(self, prefix_store,
                                  min_restore_tokens=restore_min_tokens)
            self.radix.tier = self.kv_tier
        # multi-LoRA serving (deepspeed_tpu/adapters/): per-request model
        # variants gathered from the shared paged adapter store inside the
        # step programs. The store's invalidation listeners queue adapter
        # uids here; step() drains them on THIS pump thread, so trie surgery
        # never races a dispatch (the same single-threaded discipline as
        # cancellation).
        self.adapters = adapter_store
        self._adapter_invalidations = collections.deque()
        if adapter_store is not None:
            if self.radix is not None:
                self.radix.adapter_ns = adapter_store.namespace
            adapter_store.add_listener(self._adapter_invalidations.append)
        # MoE serving: per-token capacity-free dispatch rides the same step
        # programs; `expert_stats` makes them return per-layer routed-token
        # counts (the cold-expert residency signal + load-balance telemetry)
        self._moe = getattr(engine.model_config, "num_experts", 0) > 0
        self.experts = expert_store
        if expert_store is not None:
            if not self._moe:
                raise ValueError("expert_store on a dense model (num_experts == 0)")
            topk = int(getattr(engine.model_config, "moe_top_k", 1))
            if expert_store.resident < topk:
                raise ValueError(
                    f"expert_offload.resident_experts={expert_store.resident} < "
                    f"moe_top_k={topk}: a single token routes to top_k experts "
                    f"per layer, so the backoff ladder could never terminate")
        self._moe_stats = self._moe and (expert_store is not None
                                         or engine.telemetry.enabled)
        self.expert_replays = 0
        self.expert_dispatch_tokens = 0
        # fused decode blocks (ops/pallas/decode_block.py): when the
        # engine's structured gate passes, the fused/spec step programs
        # dispatch THREE resident kernels per layer (fused_paged_step)
        # instead of the per-projection apply_with_cache path — same pool,
        # same write-index/q_spans threading, same O(1) program count.
        # LoRA program variants stay per-projection regardless (adapter
        # deltas hook the projection intermediates the fused kernels never
        # materialize), which is a per-DISPATCH choice: base-only batches
        # on an adapter-serving scheduler still fuse.
        if hasattr(engine.model_config, "int8_weights"):
            elig = engine._fused_decode_eligible()
            self._fused_block = bool(elig)
            self._fused_block_reasons = list(elig.reasons)
        else:
            self._fused_block = False
            self._fused_block_reasons = [
                "model family without fused decode-block support"]
        # step programs built so far, by the K/V commit their trace took
        self.kv_commit_programs = {"inplace": 0, "scatter": 0}
        # ... and, for gated-delta layers, by their one-token state update
        self.gdn_step_programs = {"kernel": 0, "xla": 0}
        # ... and, for Mamba-2 mixers, by theirs
        self.ssd_step_programs = {"kernel": 0, "xla": 0}
        # ... and the geometry init_cache gave the pool they carry: "packed"
        # (K beside V in one 128-lane leaf a layer: head size 64), "split"
        # or "latent"
        from ..models.transformer import kv_pool_geometry
        self.kv_pool_geometry = kv_pool_geometry(model.cfg, self.cache.pool)
        # ... and, for MoE models, by the expert dispatch it took
        self.moe_dispatch_programs = {"sparse": 0, "dense": 0, "dense_held": 0}
        self._prefill = None  # at most one in-flight _PrefillState
        # long-context paging: slots whose chained extents are (partly)
        # host-demoted sit in ``_parked`` — excluded from every dispatch
        # until step()'s paging pump restores them; the pinned host-store
        # entries park in ``_ext_parked`` keyed (rid, extent_idx)
        self._parked = set()
        self._ext_parked = {}
        self.longctx_demotes = 0
        self.longctx_restores = 0
        self.queue = collections.deque()
        self.active = {}  # slot -> _Request
        # disaggregated prefill/decode (serving/replica.py): when set by the
        # ReplicaSet, called with (self, req) the moment a chunked prefill's
        # final fused sync finishes with budget left — returning True means
        # the fleet took the request for migration to a decode replica (the
        # hook drove migrate_out; this scheduler is done with it). None (or
        # a mixed-role fleet returning False) leaves the request decoding
        # here, byte-identical to the pre-disaggregation path.
        self.migrate_hook = None
        self.migrations_out = 0
        self.migrations_in = 0
        # ``compiled_cache``: an externally-shared program dict (the replica
        # set passes one dict to every replica's scheduler, so N replicas of
        # the same shape share ONE compiled program set — replica count adds
        # zero XLA programs; jit's own shape cache handles any shape skew)
        self._compiled = {} if compiled_cache is None else compiled_cache
        # effective tensor/expert parallelism: with tp>1 (or an expert axis
        # live for MoE serving) the step programs pin the pool's OUTPUT
        # sharding to the layout _init_cache materialized (head-axis shard
        # over `tensor`, replicated elsewhere) — leaving it to propagation
        # lets GSPMD re-layout the donated pool between program variants
        # (e.g. slot axis over `data`/`expert`), churning reshards across
        # the step mix. At tp=ep=1 nothing is pinned: the programs are
        # byte-identical to the unsharded scheduler's.
        self.tp_size = int(engine.mesh.shape[dist.TENSOR_AXIS])
        self.ep_size = int(engine.mesh.shape[dist.EXPERT_AXIS])
        if self.tp_size > 1 or self.ep_size > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            self._pool_sharding = jax.tree_util.tree_map(
                lambda leaf: leaf.sharding, self.cache.pool)
            self._host_sharding = NamedSharding(engine.mesh, PartitionSpec())
        else:
            self._pool_sharding = None
            self._host_sharding = None
        # sampling logits replicate before the draw under ANY live shard
        # axis (jax.random bit-gen is not sharding-invariant)
        self._shard_deg = max(self.tp_size, self.ep_size)
        self._rid = 0
        self._steps = 0
        self._choice = None  # the last fetched block's routing choice (MoE, collecting)
        # the pump is one sync deep: the sync launched and not yet landed
        # (:class:`_Flight`), or None. Its depth at each sync follows from
        # state the scheduler can see (:meth:`_lands_first`); there is no
        # setting.
        self._flight = None
        # where every ids block is placed: a token block that is still on the
        # device is a committed array and so is whatever is merged from it, and
        # JAX lowers a committed operand with its sharding and an uncommitted
        # one without. So that a step program is built once, whether its ids
        # came from the host alone or through the merge, all of them are
        # committed, replicated over the engine's mesh
        from jax.sharding import NamedSharding, PartitionSpec
        self._ids_sharding = NamedSharding(engine.mesh, PartitionSpec())
        self._merge = jax.jit(_merge_carried, out_shardings=self._ids_sharding)
        # what an iteration launches and how a sync lands: the plain step
        # programs, or the device drafter's (device_draft.py). Bound here, so
        # a pool without one runs what it ran before it existed
        self._launch_chunk, self._launch_decode, self._land = (
            self._fused_chunk_step, self._decode_step, self._land_block)
        if self._device_draft:
            self._init_device_draft()
            self._launch_chunk, self._launch_decode, self._land = (
                self._draft_chunk_step, self._draft_decode_step, self._land_draft)
        self._landed_ts = 0.0  # sink clock at the last landing
        self.syncs_ahead = 0   # launched while the previous sync was unlanded
        self.syncs_serial = 0  # launched (or run whole) with nothing in flight
        self.ahead_rows_discarded = 0  # rows landed for a request that had ended
        # weight-swap protocol (RLHF hybrid engine): pause gates ADMISSION
        # only — in-flight rows keep decoding under the weights that
        # prefilled them until flush() drains the pool
        self._paused = False
        self.published_version = None  # publisher's tag for the live weights
        # request tracing: per-sync "sched/step" spans (on the pump thread's
        # track) collect flow ids minted by the request phases they executed
        # — the connective tissue between one request's span tree and the
        # shared iteration timeline. Active only while the sink is enabled
        # AND request tracing is on.
        self._iter = 0
        self._iter_links = None  # list while a traced sync is in flight
        self.telemetry = engine.telemetry
        self.telemetry.gauge("serving/kv_pool_packed",
                             int(self.kv_pool_geometry == "packed"))
        if self._state_pool:
            self.telemetry.gauge("serving/state_bytes_per_slot",
                                 self.cache.state_bytes_per_slot())
            self.telemetry.gauge("serving/window_bytes_per_slot",
                                 self.cache.window_bytes_per_slot())
        # set by serving/replica.py when this scheduler serves in a fleet;
        # request traces stamp it so the migration-aware trace_summary view
        # can pair prefill and decode replicas per request
        self.replica_idx = None
        # OPTIONAL ``on_landing()``: called, with no arguments, once a
        # landing's tokens have all been through their ``on_token`` hooks
        # (:meth:`_landing_delivered`); whoever batches tokens sets it
        self.on_landing = None
        # serving capacity accounting (telemetry/capacity.py): per-program
        # roofline registry + the pump's account, which also times the device.
        # Only built on an enabled sink — the disabled path allocates
        # nothing and every hook below gates on `self.capacity is None`.
        self.capacity = None
        self._work = None  # the observer of required work (required_work.py)
        self._gap = None
        # the last program handed to the device, as the capacity gauges price
        # it: (key, split, spans, lens), or None (sink off, unkeyed program)
        self._dispatched = None
        self._goodput_spec_seen = 0
        if self.telemetry.enabled:
            from ..accelerator import get_accelerator
            from ..telemetry.capacity import (CapacityMeter, CapacityModel,
                                              HostGapTracker)
            from ..utils import compile_cache
            accel = get_accelerator()
            n_dev = max(1, int(np.prod(list(engine.mesh.shape.values()))))
            self.capacity = CapacityMeter(
                self.telemetry,
                CapacityModel(engine.model_config, self.cache.bytes_per_token(),
                              int(num_slots), tp_size=self.tp_size,
                              ep_size=self.ep_size),
                peak_flops=accel.peak_flops(),
                peak_hbm_bw=accel.peak_hbm_bandwidth(),
                n_devices=n_dev)
            self._work = RequiredWork(self.telemetry, model, self.cache, self.tp_size,
                                      state_pool=self._state_pool, drafts=self._device_draft)
            # the account needs to know a program was built under a span
            compile_cache.listen()
            self._gap = HostGapTracker(self.telemetry,
                                       unlanded=lambda: self._flight is not None,
                                       compiles=compile_cache.programs)
            # the KV tier's HBM price tag: int8 should show ~half the bytes
            # per resident token of an "auto" bf16 pool
            self.telemetry.gauges([
                ("serving/kv_bytes_per_token", self.cache.bytes_per_token(), None),
                ("serving/kv_cache_capacity_bytes", self.cache.capacity_bytes(), None)])
        if (self.experts is not None or me > 1 or self._seq_chunk
                or self.allow_lossy_kv):
            # cold-expert serving warms EVERY variant the replay/backoff
            # ladder can reach, at build — before any gateway recompile
            # watch arms — so residency churn never compiles mid-stream.
            # Long-context serving warms for the same reason: the extent /
            # seq-parallel program variants must exist before the first
            # spilling request arrives, so a fresh length/extent mix adds
            # ZERO XLA programs mid-stream
            self.warm_programs()

    # ------------------------------------------------------------------ API
    def submit(self, prompt, max_new_tokens=64, eos_token_id=None, do_sample=False,
               temperature=1.0, top_k=0, top_p=1.0, seed=0, collect_logits=None,
               on_token=None, trace=None, adapter_id=None, kv_window=None):
        """Enqueue one request; returns a :class:`SchedulerHandle`. The
        request joins the decode batch as soon as a slot frees up.

        ``trace`` is an OPTIONAL
        :class:`~deepspeed_tpu.telemetry.tracing.RequestTrace`: the
        scheduler records this request's phase tree on it (prefix-cache
        probe, prefill chunks, decode, complete/cancel), flow-linked to the
        shared per-iteration ``sched/step`` spans.

        ``on_token(token, done)`` is an OPTIONAL host-side streaming hook,
        called once per generated token from inside the scheduler loop (the
        thread pumping ``step()``/``result()``), in delivery order, with
        ``done=True`` on the request's final token. It observes tokens the
        moment the host fetches them — the serving gateway's SSE stream
        hangs off this — and is pure bookkeeping: hook presence cannot
        change logits, sampling, or the compiled-program set (it runs after
        the device step, never inside it). Hook exceptions are logged and
        swallowed so one bad consumer can't wedge the shared decode loop.
        Cancelled requests stop receiving callbacks; the hook is never
        called with a token after it has seen ``done=True``. A consumer that
        would rather hear of a landing's tokens at once sets the scheduler's
        ``on_landing`` beside its hooks (:meth:`_landing_delivered`).

        ``adapter_id``: OPTIONAL model variant (multi-LoRA serving) — the
        request's rows decode through that adapter's paged (A, B) pages
        gathered inside the shared fused programs. Requires an attached
        :class:`~deepspeed_tpu.adapters.PagedAdapterStore` with the id
        registered; None is base-model traffic (bit-identical to the
        pre-adapter programs).

        ``kv_window``: OPTIONAL ``(sink, recent)`` lossy long-context knob
        (attention sinks + sliding window, StreamingLLM-style): the request
        attends only its first ``sink`` and most recent ``recent`` tokens,
        and extents that slide entirely outside that window are dropped
        from HBM without a host copy. This CHANGES the logits — it is
        gated behind ``long_context.allow_lossy_kv`` and off by default."""
        tel = self.telemetry
        if kv_window is not None:
            if not self.allow_lossy_kv:
                raise ValueError(
                    "request sets kv_window but lossy long-context KV is not "
                    "enabled (continuous_batching.long_context.allow_lossy_kv):"
                    " sliding-window attention changes logits and must be "
                    "opted into explicitly")
            sink, recent = int(kv_window[0]), int(kv_window[1])
            if sink < 0 or recent < 1:
                raise ValueError(
                    f"kv_window must be (sink >= 0, recent >= 1), got "
                    f"{kv_window!r}")
            kv_window = (sink, recent)
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    f"request names adapter_id {adapter_id!r} but multi-LoRA "
                    f"serving is not enabled (continuous_batching.multi_lora "
                    f"/ scheduler adapter_store)")
            self.adapters.check_registered(adapter_id)
        req = _Request(self._rid, prompt, max_new_tokens, eos_token_id, do_sample,
                       temperature, top_k, top_p, seed,
                       self.collect_logits if collect_logits is None else collect_logits,
                       tel.now(), on_token=on_token, trace=trace,
                       adapter_id=adapter_id, kv_window=kv_window)
        self._rid += 1
        if trace is not None:
            trace.attrs.setdefault("sched_rid", req.rid)
        # validate the PROMPT alone up front (before any early return): a
        # prompt that can never fit a slot must fail here with a clear
        # message, not deep inside a compiled prefill
        cap = self.cache.spannable_len
        if req.prompt.size >= cap:
            raise ValueError(
                f"prompt of {req.prompt.size} tokens exceeds the per-slot KV capacity "
                f"{self.max_len} x {self.cache.max_extents} extent(s) = {cap} "
                f"spannable rows (a prompt needs at least one row of decode "
                f"headroom); raise the scheduler's max_len / the engine's "
                f"max_out_tokens / long_context.max_extents, or shorten the "
                f"prompt")
        if req.max_new_tokens <= 0:  # static-path parity: zero-budget -> no tokens
            req.done = True
            return SchedulerHandle(self, req)
        # reserve for multi-step overshoot: the K-step program writes K rows
        # per sync even when the budget ends mid-block; a speculative verify
        # block likewise writes up to spec-width rows past the final token
        budget = _round_up(req.max_new_tokens, self.steps_per_sync)
        if self.spec_tokens > 0:
            budget = max(budget, req.max_new_tokens + self._spec_width - 1)
        if self._device_draft:
            # a sync may advance a row by 2 a step, and its last step's second
            # column is written whether it commits or not
            budget = req.max_new_tokens + 2 * self.steps_per_sync
        if not self.cache.fits(req.prompt.size, budget):
            raise ValueError(
                f"request needs {req.prompt.size + budget} cache rows > "
                f"slot capacity {self.max_len} x {self.cache.max_extents} "
                f"extent(s) = {self.cache.spannable_len}; raise "
                f"max_out_tokens/num_slots' max_len / "
                f"long_context.max_extents, or shorten the request")
        # admission sizes multi-extent chains against this reservation —
        # all rows the K-step/spec overshoot can ever write are covered, so
        # a chain never stalls on extent exhaustion mid-decode
        req.row_budget = int(budget)
        handle = SchedulerHandle(self, req)
        req.handle = handle
        self.queue.append(req)
        if self.kv_tier is not None:
            # hierarchical KV look-ahead: if the prompt's best host-tier
            # match is NVMe-spilled, start the disk read now so it overlaps
            # the request's queue wait (admission's restore joins it)
            ns = (self.adapters.namespace_of_id(adapter_id)
                  if (adapter_id is not None and self.adapters is not None) else ())
            self.kv_tier.prefetch(req.prompt, namespace=ns)
        if tel.enabled:
            tel.gauge("serving/queue_depth", len(self.queue))
        return handle

    def drain(self):
        """Run until every queued/active request finishes and the last sync
        has landed."""
        while (self.queue or self.active or self._prefill is not None
               or self._flight is not None):
            self.step()

    @property
    def in_flight(self):
        """Whether a sync was launched and has not landed: work, to whoever
        asks if this scheduler is idle."""
        return self._flight is not None

    def land_in_flight(self):
        """Land the sync in flight, if any, outside :meth:`step`: what
        everything that reads or moves a request's landed state does first
        (pause, swap, migration). Returns the tokens delivered. Pump thread
        only, like :meth:`step`."""
        if self._flight is None:
            return 0
        return self._land(self._take_flight())

    @property
    def num_slots(self):
        return self.cache.num_slots

    @property
    def steps_run(self):
        """Forwards over the whole slot block fetched so far: a sync's
        column (or block) forward and each of its substeps (a split chunk's
        one-slot forward is not among them)."""
        return self._steps

    @property
    def weights_version(self):
        """Monotonic weights generation of the slot pool: every KV row and
        trie registration is stamped with the version that computed it."""
        return self.cache.weights_version

    # ------------------------------------------------------------------ weight swap
    # The publish protocol (deepspeed_tpu/rlhf/publisher.py drives it):
    #   pause() -> flush() -> swap_weights(params) -> resume()
    # All four are host bookkeeping on the single scheduler thread — the
    # swap itself adds ZERO XLA programs (the step programs take params as
    # an argument, and the new tree has the same treedef/shapes/dtypes).
    def pause(self):
        """Stop admitting new work (queued requests stay queued; in-flight
        rows keep decoding) and land the sync in flight. Idempotent."""
        self._paused = True
        self.land_in_flight()

    def resume(self):
        """Re-open admission after a swap. Idempotent."""
        self._paused = False

    def flush(self):
        """Drive the loop until nothing is in flight (active rows and any
        mid-prefill row run to completion under the CURRENT weights). With
        admission paused this terminates even when requests are queued —
        they stay parked for the post-swap weights. A launched sync counts:
        when this returns the last one has landed."""
        while (self.active or self._prefill is not None
               or self._flight is not None):
            self.step()

    def swap_weights(self, params, version=None):
        """Install a new parameter tree as THE weights every subsequent
        dispatch reads, and invalidate all retained KV: drop every radix
        registration, reclaim every cached slot, and bump the pool's
        ``weights_version`` so a stale row can never re-register (enforced
        by the version stamps in :mod:`~deepspeed_tpu.inference.kv_cache`,
        not by convention). Requires nothing in flight — call
        :meth:`pause` + :meth:`flush` first (or use the publisher, which
        does). Returns the number of retained KV tokens invalidated.

        ``params`` must match the engine's current parameter tree in
        structure/shapes/dtypes (same model, new values) — that is what
        keeps the swap recompile-free; ``version`` is the publisher's tag
        for telemetry/bookkeeping."""
        if self.experts is not None:
            raise ValueError(
                "swap_weights under continuous_batching.expert_offload is "
                "unsupported: the expert kernels live in the paged store, "
                "not the param tree, so a tree swap would serve mixed "
                "weights — rebuild the engine to change MoE weights")
        self.land_in_flight()  # a sync of discarded rows may still be out
        if self.active or self._prefill is not None:
            raise ValueError(
                f"swap_weights with {len(self.active)} active slots"
                f"{' + an in-flight prefill' if self._prefill is not None else ''}: "
                f"pause() and flush() the scheduler first")
        invalidated = self.radix.invalidate_all() if self.radix is not None else 0
        self.cache.bump_weights_version()
        self.engine.params = params  # identity-keyed _fast_tree_cache re-keys itself
        self.published_version = version
        tel = self.telemetry
        if tel.enabled:
            tel.counter("rlhf/weight_swaps")
            tel.counter("rlhf/kv_invalidated_tokens", invalidated)
        return invalidated

    # ------------------------------------------------------------------ migration
    # Disaggregated prefill/decode (serving/replica.py drives both halves):
    # a prefill-role replica's scheduler hands a freshly-prefilled request
    # off through the fleet-shared GlobalPrefixStore — migrate_out demotes
    # the request's WHOLE KV (prompt rows + the rows its final fused sync
    # decoded) through the hierarchical tier's compiled tier_slice program,
    # and a decode replica's admit_migration restores it through
    # tier_restore into a fresh slot, where decode resumes from the exact
    # per-row state (write head, absolute step index, sampling seeds ride
    # the _Request object) — bit-identical to never having moved.
    def migrate_out(self, req, key, on_ready):
        """Release ``req`` from this scheduler with its KV parked in the
        store under ``key`` (called by the ReplicaSet's migrate hook, on
        this scheduler's pump thread, right after the final prefill sync
        delivered its tokens). The adapter page pin travels WITH the
        request — the store is fleet-shared, so the decode replica's rows
        gather the same resident pages. ``on_ready(entry_or_None)`` fires
        once the handoff entry is claimable."""
        self._refuse_state_migration()
        # the handoff moves the row's LANDED state. From the migrate hook
        # nothing is in flight (:meth:`_lands_first`); a brownout park lands
        # first itself, before it looks at the request
        self.land_in_flight()
        slot = req.slot
        kv_len = int(self.cache.lengths[slot])
        # demote FIRST, release AFTER: the compiled slice's output owns
        # fresh buffers (so the slot is reusable the moment this returns),
        # and a synchronous dispatch failure here propagates while the
        # request is STILL fully owned by this scheduler (active slot
        # intact) — the normal sick-replica shedding can fail it, instead
        # of stranding a request that is owned by nobody and parked nowhere
        with self._span("sched/tier_transfer"):
            self.kv_tier.demote_request(slot, kv_len, key, on_ready)
        if self.capacity is not None:
            # goodput: the demoted KV bytes are pure handoff traffic —
            # no request token comes out of moving them
            self.capacity.account(
                0, wasted_bytes=kv_len * self.cache.bytes_per_token())
        req.migrating = True
        del self.active[slot]
        self._release_slot(slot)  # retained cached: the prompt prefix the
        # _finish_prefill registration holds stays a donor for siblings
        self.migrations_out += 1
        req.slot = None
        if req.trace is not None and req.trace.enabled:
            # the prefill half of the handoff, stamped with THIS replica —
            # trace_summary --requests pairs it with the decode replica's
            # "migrated" instant to print the route + migration latency
            req.trace.mark("migration")
            req.trace.instant("migrate_out", replica=self.replica_idx,
                              kv_len=kv_len)
        return kv_len

    def _refuse_state_migration(self):
        if self._state_pool:
            raise ValueError("a request whose slot holds recurrent state, ring rows or rows "
                             "that layers share (layer_types) cannot migrate between "
                             "replicas yet: the handoff moves rows by length only")

    def _settle_migration(self, record, error=None, discard=True):
        """Terminal bookkeeping shared by every failed/cancelled handoff
        path: mark the request done (with ``error`` unless it was a client
        cancel), drop the parked store entry, release the adapter pin, and
        account it. One helper so the four settle sites can never drift."""
        req = record.req
        if error is not None and not req.cancelled:
            req.error = error
        req.done = True
        req.migrating = False
        if discard and record.entry is not None:
            self.kv_tier.store.discard(record.key)
        self._release_adapter(req)
        tel = self.telemetry
        if tel.enabled:
            tel.counter("serving/cancelled" if req.cancelled
                        else "serving/migrations_failed")
        if req.trace is not None:
            req.trace.instant("cancelled" if req.cancelled else "failed",
                              where="migration")
        return "settled"

    def admit_migration(self, record):
        """Admit a migrated request (runs on THIS scheduler's pump thread —
        the decode half of the handoff). Returns ``"resumed"`` when the
        request is decoding here, ``"settled"`` when it ended without a
        slot (mid-migration cancel, failed demote, stale weights version),
        or None when no slot could be acquired and it should stay
        parked. A restore raising on device settles the request as failed
        FIRST and then re-raises, so the pump's sick-replica handling
        runs without stranding a request that no scheduler owns."""
        self._refuse_state_migration()
        self.land_in_flight()
        req = record.req
        tel = self.telemetry
        if req.cancelled or record.entry is None:
            # mid-migration cancel (or a failed demote fetch): both ends'
            # slots are already free (prefill released at migrate_out;
            # decode never allocated) — just settle
            return self._settle_migration(
                record, error="migration failed: KV handoff device->host "
                              "fetch failed")
        if record.version != int(self.cache.weights_version):
            # weights swapped while the handoff was parked: the KV is stale
            # by the same structural rule that drops the prefix tier on a
            # swap — fail the request rather than decode on old-weights KV
            return self._settle_migration(
                record, error="migration failed: weights version changed "
                              "while the handoff was parked (stale KV must "
                              "not decode)")
        slot = self.cache.alloc(owner=req.rid)
        if slot is None and self.radix is not None:
            victim = self.radix.evict_lru()
            if victim is not None:
                self.cache.reclaim(victim)
                if tel.enabled:
                    tel.counter("serving/prefix_cache_evict")
                slot = self.cache.alloc(owner=req.rid)
        if slot is None:
            return None  # every slot live: stays parked, retried next pull
        try:
            with self._span("sched/tier_transfer"), self.engine.mesh:
                ok = self.kv_tier.restore_request(record.entry, slot,
                                                  record.kv_len)
            if ok and self.capacity is not None:
                # the restore half of the handoff: traffic, not tokens
                self.capacity.account(
                    0, wasted_bytes=record.kv_len * self.cache.bytes_per_token())
            if ok:
                # structural version gate lives in the pool, like
                # retain/insert
                self.cache.adopt_rows(slot, record.kv_len, record.version)
        except Exception:
            # the record is already consumed: settle the request as failed
            # and free the slot BEFORE propagating, so the pump's
            # sick-replica handling runs without leaking the slot or
            # stranding a request that no scheduler owns
            self.cache.free(slot)
            self._settle_migration(
                record, error="migration failed: KV restore raised on the "
                              "decode replica")
            raise
        if not ok:
            # claimed/dropped under us (adapter invalidation beat the pull)
            self.cache.free(slot)
            return self._settle_migration(
                record, discard=False,  # pop already consumed/killed it
                error="migration failed: handoff entry invalidated before "
                      "the decode replica could claim it")
        req.slot = slot
        req.migrating = False
        self.active[slot] = req
        self.migrations_in += 1
        if req.handle is not None:
            # result() keeps working for direct-drive callers: the handle
            # now pumps the scheduler that actually owns the request
            req.handle._sched = self
        if req.trace is not None and req.trace.enabled:
            # close the handoff as a span (parked + transfer time, started
            # at migrate_out's mark) and stamp the adopting replica
            req.trace.phase("migration", replica=self.replica_idx,
                            kv_len=record.kv_len)
            req.trace.instant("migrated", replica=self.replica_idx,
                              replica_kv_len=record.kv_len)
        return "resumed"

    def owns(self, req):
        """Does this scheduler currently hold ``req`` (queued, prefilling,
        or decoding)? A migrated-out request is owned by NO scheduler while
        its handoff is parked — the gateway's sick-replica shedding uses
        this instead of remembering placement, so a replica failing after
        it handed a request off can no longer kill that request."""
        return ((self._prefill is not None and self._prefill.req is req)
                or (req.slot is not None and self.active.get(req.slot) is req)
                or any(q is req for q in self.queue))

    # ------------------------------------------------------------------ loop
    def step(self):
        """One scheduler iteration of a pump that is one sync deep: settle
        cancellations, admit (at most one in-flight prefill), LAUNCH the next
        sync (one fused chunk+decode step while a prefill is in flight, else
        ``steps_per_sync`` decode steps) from the state after everything
        launched so far, and only then LAND the sync launched before it:
        fetch its token block and deliver it. The device runs sync N+1 the
        moment N ends, while the host delivers N's tokens and assembles N+2.
        Where the next sync's inputs need the previous one's results on the
        host (:meth:`_lands_first`) the iteration lands before it launches
        and lands what it launched before it returns, which is the order of
        a serial pump. Returns the tokens delivered.

        The iteration is the ``sched/step`` span; inside it ``sched/admit``,
        ``sched/assemble``, ``sched/dispatch``, ``sched/fetch`` and
        ``sched/deliver`` mark what the pump was doing (profiler
        annotations always; sink events while request tracing is on, where
        request phases that landed this sync flow-link to the step)."""
        tel = self.telemetry
        tracing = tel.enabled and getattr(tel, "trace_requests", False)
        self._iter_links = [] if tracing else None
        try:
            with self._span("sched/step") as span:
                delivered, kind = self._iterate()
                if kind is None:
                    span.record = False  # nothing ran: no iteration to show
                elif tracing:
                    span.attrs = {"iter": self._iter, "kind": kind,
                                  "live": len(self.active), "delivered": delivered}
                    span.flow_out = self._iter_links or None
        finally:
            self._iter_links = None
        return delivered

    def _span(self, name):
        """A block-level host span (``TelemetrySink.span``) of this pump:
        the pump's account (``HostGapTracker``, which books each name of
        ``capacity.PUMP_SPANS``) hears its boundaries; the sink records it
        while request tracing is on."""
        tel = self.telemetry
        return tel.span(name, record=getattr(tel, "trace_requests", False),
                        observer=self._gap)

    def _admit_queued(self):
        """The admission part of an iteration, under ``sched/admit``: reap
        cancellations, service long-context paging, pick from the queue,
        acquire a slot (with its trie probe) and begin the prefill. Returns
        the number of requests admitted."""
        # adapter invalidations (page evicted / adapter reloaded elsewhere
        # in the fleet) drain HERE, on the pump thread — trie surgery never
        # races a dispatch
        while self._adapter_invalidations:
            self._invalidate_adapter_uid(self._adapter_invalidations.popleft())
        self._reap_cancelled()
        if self._parked or self.cache.chain:
            # long-context paging pump: restore parked extents BEFORE
            # admission so a freed slot un-parks a live request rather than
            # admitting new work in front of it; lossy rows drop extents
            # that slid outside their attention window
            self._service_long_context()
        if self._paused:
            return 0  # swap protocol: no admission; in-flight work still advances
        while self.queue and self.queue[0].cancelled:
            self.queue.popleft().done = True
        if self._prefill is not None:
            return 0
        # FIFO, except a request whose adapter bucket is pinned SOLID (every
        # page held by live requests) must not head-of-line-block traffic
        # that needs no page — scan past such heads to the first admissible
        # request. KV-slot exhaustion still gates everyone equally: only the
        # first non-skipped candidate is tried per iteration.
        for i, req in enumerate(self.queue):
            if req.cancelled:
                continue  # reaped when it reaches the head
            if (req.adapter_id is not None and self.adapters is not None
                    and not self.adapters.acquirable(req.adapter_id)):
                continue  # its page pool is pinned solid: skip
            slot, match = self._acquire_slot(req)
            if slot is None:
                return 0
            del self.queue[i]
            self._begin_prefill(req, slot, match)
            return 1
        return 0

    def _lands_first(self):
        """Whether the next sync's inputs need the previous one's tokens or
        counts on the host, so that the pump lands before it launches: a
        drafter reads the accepted tokens, cold-expert offload replays on
        the routing counts (and backs off on an overflow), parked or chained
        rows are paged by their landed lengths, a final chunk's row may be
        handed to the migrate hook the moment its tokens are out, and a row
        flagged for cancellation
        gets the tokens already computed for it before it is reaped (as
        behind a serial pump, where a sync lands in the step that launched
        it). All of it state the scheduler can see; everything else
        launches ahead."""
        fl = self._flight
        return (self.drafter is not None or self.experts is not None
                or bool(self._parked) or bool(self.cache.chain)
                or (self.migrate_hook is not None and fl is not None and fl.final)
                or any(r.cancelled and r.inflight for r in self.active.values()))

    def _take_flight(self):
        fl, self._flight = self._flight, None
        return fl

    def _iterate(self):
        """The body of :meth:`step`. Returns (tokens delivered, the kind of
        sync that was launched or, with nothing to launch, landed: "fused",
        "spec", "decode", or None when nothing could run)."""
        tel = self.telemetry
        t0 = tel.now()
        delivered = 0
        kind = None
        if self._flight is not None:
            kind = "fused" if self._flight.chunk is not None else "decode"
            if self._lands_first():
                delivered += self._land(self._take_flight())
        with self._span("sched/admit"):
            admitted = self._admit_queued()
        if admitted and tel.enabled:
            tel.counter("serving/admitted", admitted)
        # a step method returns the _Flight it launched (which reads what it
        # needs of the sync still in ``self._flight``), the (delivered,
        # ksteps) of a path that landed its own dispatches (verify, backoff),
        # or None when no row has anything left to run
        ran = None
        if self._prefill is not None:
            kind = "fused"
            ran = self._launch_chunk()
        elif self.active:
            if self._parked and all(s in self._parked for s in self.active):
                # nothing can dispatch and nothing can ever free a row:
                # every live request waits on a restore, and restores wait
                # on a free row only a live request could release
                raise RuntimeError(
                    "long-context paging deadlock: every live request is "
                    "parked on demoted extents and no free pool row exists "
                    "to restore into — demote fewer extents or leave slot "
                    "headroom")
            if self.drafter is not None:
                ran = self._spec_decode_step()
                kind = "spec" if ran is not None else kind
            else:
                ran = self._launch_decode()
                kind = "decode" if ran is not None else kind
        prev = self._take_flight()
        if ran is not None:
            ahead = prev is not None
            self.syncs_ahead += ahead
            self.syncs_serial += not ahead
            if tel.enabled:
                tel.counter("serving/syncs_ahead" if ahead else "serving/syncs_serial")
            if isinstance(ran, _Flight):
                ran.t0 = t0
                ran.program = self._dispatched
                self._flight = ran
            else:
                delivered += ran[0]
                self._observe(ran[0], ran[1], t0)
        if prev is not None:
            delivered += self._land(prev)
        elif self._flight is not None and self._lands_first():
            delivered += self._land(self._take_flight())
        if kind is not None:
            self._iter += 1
        return delivered, kind

    def _land_block(self, fl):
        """Land a launched sync (``self._land`` of a pool without a device
        drafter): fetch its block (``sched/fetch``), deliver
        the decode rows' tokens and the chunk's (``sched/deliver``). A row
        whose request ended while the sync was in flight (an EOS or a
        cancellation the launch could not know of) computed once more for
        nothing: its tokens are dropped and counted."""
        toks_k, logits_k = self._fetch_block(fl.out, fl.collect, fl.K, fl.program)
        if fl.chunk is not None:
            preq, pos, take, final = fl.chunk
            tr = preq.trace
            if tr is not None and tr.enabled:
                fid = self._trace_link(tr)
                tr.phase("prefill_chunk", start=fl.t0,
                         flow_in=[fid] if fid else None,
                         pos=int(pos), take=int(take), final=bool(final))
        # a final chunk's row delivers behind the decode rows, in this landing
        chunk_next = fl.final and not fl.chunk[0].done
        delivered = self._deliver_block(fl.rows, toks_k, logits_k, fl.K, chunk_next)
        if fl.chunk is not None:
            delivered += self._deliver_chunk(fl, toks_k, logits_k)
        self._observe(delivered, fl.K, fl.t0)
        return delivered

    def _observe(self, delivered, ksteps, since):
        """Per-sync telemetry, at its landing. ``serving/step_ms`` is the
        wall time a sync took of the pump over its steps: from the landing
        before it (or from ``since``, its own iteration's start, where the
        pump was idle or serial) to this one."""
        tel = self.telemetry
        if not tel.enabled:
            return
        now = tel.now()
        dur_ms = (now - max(since, self._landed_ts)) * 1e3
        self._landed_ts = now
        tel.counter("serving/decode_steps", ksteps)
        tel.counter("serving/decode_tokens", delivered)
        tel.histogram("serving/step_ms", dur_ms / ksteps)
        tel.histogram("serving/tokens_per_step", delivered / ksteps)
        if self._state_pool:
            tel.gauge("serving/state_slots_live", self.cache.active_slots)
        tel.gauges([("serving/slot_occupancy", self.cache.occupancy(), None),
                    ("serving/batch_efficiency",
                     delivered / (ksteps * self.cache.num_slots), None),
                    ("serving/kv_token_utilization", self.cache.token_utilization(),
                     None),
                    ("serving/kv_bytes_live", self.cache.live_bytes(), None)])
        cap = self.capacity
        if cap is not None:
            # goodput: tokens delivered vs computed-then-discarded.
            # Speculative rejected columns fold in here (as the delta
            # of drafted - accepted this sync); MoE miss replays and
            # migration/restore traffic account at their own sites.
            rejected = ((self.spec_drafted - self.spec_accepted)
                        - self._goodput_spec_seen)
            self._goodput_spec_seen += rejected
            live_lens = [self.cache.lengths[s] for s in self.active]
            ctx = (sum(live_lens) / len(live_lens)) if live_lens else 0.0
            cap.account(delivered, wasted_tokens=max(0, rejected), ctx=ctx)

    def _trace_link(self, trace):
        """Mint a flow id binding a request phase to the sync currently in
        flight (registered on this iteration's ``sched/step`` span); None
        when tracing is off or no traced sync is active."""
        if trace is None or self._iter_links is None or not trace.enabled:
            return None
        fid = trace.link()
        self._iter_links.append(fid)
        return fid

    def _invalidate_adapter_uid(self, uid):
        """Reclaim every KV/prefix registration of adapter ``uid`` — device
        trie AND this fleet's host tier — fired via the store's listeners
        when the uid's page leaves the device or its adapter re-registers
        (the "reloaded adapter can never serve a stale page" contract)."""
        dropped = self.radix.invalidate_adapter(uid) if self.radix is not None else 0
        if self.kv_tier is not None and self.adapters is not None:
            dropped += self.kv_tier.store.drop_prefix(self.adapters.namespace(uid))
        tel = self.telemetry
        if tel.enabled and dropped:
            tel.counter("serving/adapter_kv_invalidated_tokens", dropped)

    def _release_adapter(self, req):
        """Unpin a finished/cancelled request's adapter page and account its
        per-adapter token counter (the PR 4 cardinality cap applies via the
        store's label table)."""
        if req.adapter_ref is None:
            return
        self.adapters.release(req.adapter_ref)
        req.adapter_ref = None
        tel = self.telemetry
        if tel.enabled:
            tel.counter(f"serving/adapter/{self.adapters.label(req.adapter_id)}"
                        f"/tokens", len(req.out))

    def _release_slot(self, slot):
        """Return a finished/cancelled request's slot: retained (state
        ``cached``) when the radix trie references its prefix, else freed.
        Retained lengths clamp to the trie-registered prompt prefix — the
        decode/substep rows past it (including K-step overshoot) are
        garbage for reuse, and counting them would inflate
        ``cached_tokens``/``kv_token_utilization``."""
        if self.radix is not None and self.cache.refs[slot] > 0:
            self.cache.lengths[slot] = min(int(self.cache.lengths[slot]),
                                           self.radix.registered_len(slot))
            self.cache.retain(slot)
        else:
            self.cache.free(slot)

    def _drop_parked(self, slot, req):
        """Forget a departing request's extent-paging state: the slot
        leaves the parked set and any host-parked extent entries are
        discarded (a finished/cancelled request's demoted KV dies with
        it). No-op for the single-extent common case."""
        if not self._parked and not self._ext_parked:
            return
        self._parked.discard(slot)
        for key in [k for k in self._ext_parked if k[0] == req.rid]:
            del self._ext_parked[key]
            if self.kv_tier is not None:
                self.kv_tier.store.discard((_EXT_NS, req.rid, key[1]))

    def _reap_cancelled(self):
        """Evict slots whose requests were cancelled (handle dropped). Runs
        only from step() — the single-threaded loop — so eviction never
        races an in-flight decode dispatch."""
        tel = self.telemetry
        for slot, req in list(self.active.items()):
            if req.cancelled and not req.done:
                req.done = True
                del self.active[slot]
                self._release_slot(slot)
                self._drop_parked(slot, req)
                self._release_adapter(req)
                if tel.enabled:
                    tel.counter("serving/cancelled")
                if req.trace is not None:
                    req.trace.instant("cancelled", where="decode",
                                      tokens=len(req.out))
        if self._prefill is not None and self._prefill.req.cancelled:
            req = self._prefill.req
            req.done = True
            # mid-prefill slots are never trie-registered yet -> plain free
            self._release_slot(req.slot)
            self._release_adapter(req)
            self._prefill = None
            if tel.enabled:
                tel.counter("serving/cancelled")
            if req.trace is not None:
                req.trace.instant("cancelled", where="prefill")

    # ------------------------------------------------------------------ long context
    def _ext_operands(self, rows, force=False):
        """The extent-walk operand block for ONE dispatch — ``(ext_table
        (N, E), wslot (N,), ext_base (N,), sinks (N,), wins (N,))`` over
        the FULL slot axis — or None when no live row needs it (chains and
        lossy windows absent, ``force`` off; the plain programs then run
        byte-identical to the pre-extent scheduler). ``force`` is for the
        seq-parallel program, whose signature always carries the block.

        Rows without a chain get the identity single-extent table; demoted
        extents carry -1 (the kernel clamps the DMA index and masks the
        range — only lossy rows ever dispatch with one). ``wslot`` /
        ``ext_base`` redirect each row's KV writes into its WRITE extent's
        pool row; all-zero sinks/wins are the lossless sentinel."""
        if not force and not self.cache.chain and not any(
                r.kv_window is not None for _, r in rows):
            return None
        N = self.cache.num_slots
        S = self.max_len
        E = max(1, self.cache.max_extents)
        ext = np.full((N, E), -1, np.int32)
        ext[:, 0] = np.arange(N, dtype=np.int32)
        wslot = np.arange(N, dtype=np.int32)
        base = np.zeros(N, np.int32)
        sinks = np.zeros(N, np.int32)
        wins = np.zeros(N, np.int32)
        for slot, req in rows:
            members = self.cache.extents(slot)
            for i, m in enumerate(members):
                ext[slot, i] = m
            w = min(int(self.cache.lengths[slot]) // S, len(members) - 1)
            wslot[slot] = max(int(members[w]), 0)
            base[slot] = w * S
            if req.kv_window is not None:
                sinks[slot] = req.kv_window[0]
                wins[slot] = req.kv_window[1]
        return ext, wslot, base, sinks, wins

    def demote_cold_extents(self, slot, keep_recent=1):
        """Page a live multi-extent request's COLD extents out of HBM.

        Extent 0 (the attention-sink prefix, pinned) and the write extent
        (plus ``keep_recent - 1`` extents before it) stay resident; extents
        past the write head hold nothing and are skipped. Lossless mode
        (the default — no ``kv_window`` on the request) copies each demoted
        extent to the hierarchical host tier and PARKS the row: it skips
        every dispatch until :meth:`step`'s paging pump restores all of
        them (detect-miss-and-restore), so the emitted stream stays
        bit-identical. A lossy request (``kv_window``) drops the rows
        outright — its sliding-window mask already hides every position
        they held. Returns the number of extents demoted."""
        self.land_in_flight()  # the row is paged by its landed length
        req = self.active.get(slot)
        if req is None:
            raise ValueError(f"slot {slot} is not a live decode row")
        members = self.cache.extents(slot)
        if len(members) <= 1:
            return 0
        lossy = req.kv_window is not None
        if not lossy and self.kv_tier is None:
            raise ValueError(
                "lossless extent demotion requires the hierarchical KV tier "
                "(continuous_batching.hierarchical_kv) for the host-side "
                "copy; enable it, or submit the request with kv_window for "
                "the lossy sliding-window mode")
        S = self.max_len
        tel = self.telemetry
        w = min(int(self.cache.lengths[slot]) // S, len(members) - 1)
        keep = {max(0, w - i) for i in range(max(1, int(keep_recent)))}
        demoted = 0
        for idx in range(1, len(members)):
            if idx in keep or idx > w or members[idx] < 0:
                continue
            if not lossy:
                # copy to host FIRST (the cache-level demote frees the row)
                entry = self.kv_tier.demote_extent(
                    members[idx], (_EXT_NS, req.rid, idx))
                self._ext_parked[(req.rid, idx)] = entry
            self.cache.demote_extent(slot, idx)
            demoted += 1
            self.longctx_demotes += 1
            if tel.enabled:
                tel.counter("serving/longctx_demote_tokens", S)
            if self.capacity is not None and not lossy:
                # paging traffic, not tokens: the demoted bytes buy HBM
                # headroom, never a request token
                self.capacity.account(
                    0, wasted_bytes=S * self.cache.bytes_per_token())
        if demoted and not lossy:
            self._parked.add(slot)
        return demoted

    def _service_long_context(self):
        """Host-side extent paging pump, once per scheduler iteration:

        - lossy rows (``kv_window``) auto-drop extents that have slid
          entirely outside their attention sink + recent window — the
          window mask already hides every position they hold (and the
          window's trailing edge only ever advances, so a dropped extent
          can never be needed again);
        - parked rows (lossless :meth:`demote_cold_extents`) restore every
          missing extent into free pool rows — reclaiming LRU radix
          prefixes under pressure — and rejoin the batch the moment the
          last one lands.
        """
        tel = self.telemetry
        S = self.max_len
        for slot, req in list(self.active.items()):
            if req.kv_window is None or slot not in self.cache.chain:
                continue
            sink, recent = req.kv_window
            length = int(self.cache.lengths[slot])
            members = self.cache.extents(slot)
            for idx in range(1, len(members)):
                if members[idx] < 0:
                    continue
                if idx * S >= sink and (idx + 1) * S <= length - recent:
                    self.cache.demote_extent(slot, idx)
                    self.longctx_demotes += 1
                    if tel.enabled:
                        tel.counter("serving/longctx_demote_tokens", S)
        if not self._parked:
            return
        for slot in sorted(self._parked):
            req = self.active.get(slot)
            if req is None or req.cancelled:
                continue  # _reap_cancelled owns the teardown
            restored_all = True
            for idx in self.cache.missing_extents(slot):
                row = self.cache.restore_extent(slot, idx)
                while row is None and self.radix is not None:
                    victim = self.radix.evict_lru()
                    if victim is None:
                        break
                    self.cache.reclaim(victim)
                    if tel.enabled:
                        tel.counter("serving/prefix_cache_evict")
                    row = self.cache.restore_extent(slot, idx)
                if row is None:
                    restored_all = False  # free list dry: retry next iter
                    break
                entry = self._ext_parked.pop((req.rid, idx), None)
                if entry is None or self.kv_tier is None:
                    raise RuntimeError(
                        "long-context paging invariant violated: a demoted "
                        "extent has no parked host entry to restore from")
                with self._span("sched/tier_transfer"), self.engine.mesh:
                    ok = self.kv_tier.restore_extent(entry, row)
                if not ok:
                    raise RuntimeError(
                        "long-context paging invariant violated: a parked "
                        "extent entry vanished from the host store while "
                        "its request was live")
                self.longctx_restores += 1
                if tel.enabled:
                    tel.counter("serving/longctx_restore_tokens", S)
                if self.capacity is not None:
                    self.capacity.account(
                        0, wasted_bytes=S * self.cache.bytes_per_token())
            if restored_all:
                self._parked.discard(slot)

    # ------------------------------------------------------------------ admit
    def _acquire_slot(self, req):
        """A free slot for admission plus the radix match for ``req``'s
        prompt, matched BEFORE any eviction — reclaiming a cached slot drops
        its trie registration, so matching after could lose the prompt's
        only donor. When the free list is dry, reclaims the LRU cached
        prefix slot, preferring victims other than the matched donor.
        Returns ``(slot, (matched_len, donor))``; slot is None when every
        slot serves a live request.

        Adapter requests first PIN their adapter's page resident
        (hot-loading through the store on a miss); the match then walks
        that adapter uid's own trie root. A store with every page pinned —
        or a pool with every slot live — returns slot None and the
        acquisition retries next iteration (nothing is held across the
        retry)."""
        aref = None
        if req.adapter_id is not None:
            aref = self.adapters.acquire(req.adapter_id)
            if aref is None:
                return None, (0, None)  # every page pinned: retry next iter
        akey = aref.uid if aref is not None else None
        # multi-extent request: reserve the WHOLE chain (prompt + decode
        # budget) up front, all-or-nothing — extents claimed lazily could
        # deadlock mid-decode with nothing evictable. Chains skip radix
        # reuse both ways: prefix donors are single-extent slots, and a
        # chained slot is never retained (free() tears the chain down)
        n_ext = self.cache.extents_needed(req.prompt.size + req.row_budget)
        if n_ext > 1:
            slot = self.cache.alloc_chain(n_ext, owner=req.rid)
            while slot is None and self.radix is not None:
                victim = self.radix.evict_lru()
                if victim is None:
                    break
                self.cache.reclaim(victim)
                if self.telemetry.enabled:
                    self.telemetry.counter("serving/prefix_cache_evict")
                slot = self.cache.alloc_chain(n_ext, owner=req.rid)
            if slot is None:
                if aref is not None:
                    self.adapters.release(aref)
                return None, (0, None)
            req.adapter_ref = aref
            return slot, (0, None)
        if self.radix is not None:
            # inside sched/admit: the pump's account books the probe's time
            # under its own bucket and not admission's
            with self._span("sched/trie_probe"):
                match = self.radix.match(req.prompt, adapter=akey)
        else:
            match = (0, None)
        slot = self.cache.alloc(owner=req.rid)
        if slot is None and self.radix is not None:
            victim = self.radix.evict_lru(prefer_not=match[1])
            if victim is not None:
                self.cache.reclaim(victim)
                if self.telemetry.enabled:
                    self.telemetry.counter("serving/prefix_cache_evict")
                slot = self.cache.alloc(owner=req.rid)
        if slot is None:
            if aref is not None:
                self.adapters.release(aref)
            return None, match
        req.adapter_ref = aref
        return slot, match

    def _begin_prefill(self, req, slot, match):
        """Start the chunked prefill for ``req`` on ``slot``: seed the slot
        with the longest matched prefix (``match`` from :meth:`_acquire_slot`,
        one compiled copy program) and leave the suffix to the fused chunk
        steps.

        Matches are capped at ``prompt - 1`` (the last prompt token must
        run through the model to produce the first-token logits) and
        rounded DOWN to a ``prefill_chunk`` multiple so the suffix replays
        the cold path's exact chunk boundaries — a hit is bit-identical to
        a cold prefill."""
        tel = self.telemetry
        req.slot = slot
        pos = 0
        if tel.enabled:
            # how long the request waited for the one prefill lane (its own
            # chunks' time is the rest of serving/ttft_ms)
            tel.histogram("serving/prefill_wait_ms", (tel.now() - req.submit_ts) * 1e3)
        tr = req.trace
        if tr is not None and tr.enabled:
            tr.mark("prefill")  # phase closes at _finish_prefill
            probe_t0 = tel.now()
        # multi-extent chains skip prefix reuse entirely (see _acquire_slot)
        if self.radix is not None and slot not in self.cache.chain:
            m, donor = match
            m = min(m, req.prompt.size - 1)
            m = (m // self.prefill_chunk) * self.prefill_chunk
            # the donor may have been the LRU victim reclaimed for this very
            # admission (eviction only falls back to the donor when every
            # other slot is live); its registration is gone, but the freed
            # slot became OUR slot with the prefix rows still resident —
            # src == dst makes the copy a no-op and the hit stands
            donor_ok = donor is not None and (
                donor == slot or donor in self.radix._slot_node)
            if not donor_ok:
                m = 0
            # hierarchical KV: probe the host tier and restore when it
            # beats the device match (same rounding/cap as the device hit,
            # so restored == device-hit == cold run identical chunk
            # boundaries and the decode is bit-identical across all three).
            # Adapter requests probe under their uid namespace — a base (or
            # other-adapter) host entry can never restore for them
            hm, entry = 0, None
            restored = False
            if self.kv_tier is not None:
                # host-tier probe + restore, inside sched/admit: the tracker
                # re-files their share under tier_transfer
                with self._span("sched/tier_transfer"):
                    ns = (self.adapters.namespace(req.adapter_ref.uid)
                          if req.adapter_ref is not None else ())
                    hm, entry = self.kv_tier.probe(req.prompt, namespace=ns)
                    hm = min(hm, req.prompt.size - 1)
                    hm = (hm // self.prefill_chunk) * self.prefill_chunk
                    if hm < max(self.prefill_chunk, self.kv_tier.min_restore_tokens):
                        hm, entry = 0, None
                    if entry is not None and hm > m:
                        with self.engine.mesh:
                            restored = self.kv_tier.restore(entry, slot, hm,
                                                            req.prompt.size)
            if restored:
                pos = hm
                if tel.enabled:
                    tel.counter("serving/prefix_cache_restore")
                    tel.counter("serving/prefix_cache_restore_tokens", hm)
            elif m > 0:
                if donor != slot:
                    with self.engine.mesh:
                        self.cache.pool = self._copy_fn()(
                            self.cache.pool, jnp.asarray(donor, jnp.int32),
                            jnp.asarray(slot, jnp.int32))
                pos = m
                self.radix.hits += 1
                self.radix.touch(donor)
                if tel.enabled:
                    tel.counter("serving/prefix_cache_hit")
                    tel.counter("serving/prefix_cache_hit_tokens", m)
            else:
                self.radix.misses += 1
                if tel.enabled:
                    tel.counter("serving/prefix_cache_miss")
            if tel.enabled:
                tel.gauge("serving/prefix_cache_hit_rate", self.radix.hit_rate())
                if self.kv_tier is not None:
                    tel.gauge("serving/kv_tier_hit_rate",
                              self.kv_tier.hit_rate(self.radix))
            if tr is not None and tr.enabled:
                tr.phase("prefix_probe", start=probe_t0, slot=slot,
                         cached_tokens=pos, prompt=int(req.prompt.size),
                         **({"restored": True} if restored else {}))
        self.cache.lengths[slot] = pos
        if self._state_pool:
            # the first chunk starts at position 0: the step program starts
            # this slot's state and window from zero whatever it held
            self.state_slots_reset += 1
            self.prefix_cache_state_bypass += int(self._state_bypass)
            if tel.enabled:
                tel.counter("serving/state_slots_reset")
                if self._state_bypass:
                    tel.counter("serving/prefix_cache_state_bypass")
        if req.adapter_id is not None and tel.enabled:
            tel.counter(f"serving/adapter/{self.adapters.label(req.adapter_id)}"
                        f"/requests")
        pf = _PrefillState(req, pos)
        pf.seq_parallel = bool(self._seq_chunk
                               and req.prompt.size >= self.seq_parallel_min_tokens)
        if tel.enabled:
            tel.histogram("serving/kv_extents_per_request",
                          len(self.cache.extents(slot)))
            if pf.seq_parallel:
                tel.counter("serving/seq_parallel_prefills")
        self._prefill = pf

    def _book_decode_row(self, req):
        """A prompt's final chunk was LAUNCHED: the prefill lane is free for
        the next queued prompt and the row decodes from the next launch on
        (its first id is the token this sync samples, carried on the device).
        The prompt registers in the radix trie now (live prefixes serve as
        donors too — prefill rows are never rewritten during decode): its
        rows are written by a program already queued, and whatever copies
        them is queued behind it, so a twin prompt admitted the very next
        iteration finds its donor as it would behind a serial pump."""
        self._prefill = None
        self.active[req.slot] = req
        if self.radix is not None and req.slot not in self.cache.chain:
            akey = req.adapter_ref.uid if req.adapter_ref is not None else None
            if self.kv_tier is not None:
                # a cold/device-hit prefill supersedes this scheduler's own
                # host copy of the EXACT same prompt (restore normally
                # consumes it; the corner cases — match rounded below a
                # chunk, device donor at least as long — leave it behind,
                # and registering the key on device too would break the
                # one-tier-per-key invariant)
                ns = self.adapters.namespace(akey) if akey is not None else ()
                self.kv_tier.discard_exact(req.prompt, namespace=ns)
            self.radix.insert(req.slot, req.prompt, adapter=akey)

    def _first_token(self, req, tok, last_logits):
        """The final chunk LANDED: stamp the first token's time and deliver
        token 0."""
        if req.done:  # cancelled while its final chunk was in flight
            return
        tel = self.telemetry
        req.first_token_ts = tel.now()
        if tel.enabled:
            tel.histogram("serving/ttft_ms", (req.first_token_ts - req.submit_ts) * 1e3)
            tel.gauge("serving/queue_depth", len(self.queue))
        tr = req.trace
        if tr is not None and tr.enabled:
            tr.phase("prefill", prompt=int(req.prompt.size),
                     ttft_ms=round((req.first_token_ts - req.submit_ts) * 1e3, 3))
            tr.mark("decode")  # phase closes when the request finishes
        if req.collect_logits and last_logits is not None:
            req.logits.append(last_logits)
        self._deliver(req, tok)

    def _deliver(self, req, tok):
        """Append one generated token; finish on EOS or length budget and
        evict the slot the same iteration (continuous batching's whole
        point: the freed slot admits the next queued request BEFORE the
        next decode step)."""
        if req.done:  # cancelled/settled elsewhere: never double-free the slot
            return
        req.out.append(tok)
        if ((req.eos_token_id is not None and tok == req.eos_token_id)
                or len(req.out) >= req.max_new_tokens):
            req.done = True
            if req.slot in self.active:
                del self.active[req.slot]
            self._release_slot(req.slot)
            self._drop_parked(req.slot, req)
            self._release_adapter(req)
            if self.telemetry.enabled:
                self.telemetry.counter("serving/evicted")
            tr = req.trace
            if tr is not None and tr.enabled:
                now = self.telemetry.now()
                eos = req.eos_token_id is not None and tok == req.eos_token_id
                n = len(req.out)
                ttft = ((req.first_token_ts - req.submit_ts) * 1e3
                        if req.first_token_ts is not None else 0.0)
                itl = ((now - req.first_token_ts) * 1e3 / (n - 1)
                       if req.first_token_ts is not None and n > 1 else 0.0)
                fid = self._trace_link(tr)
                tr.phase("decode", flow_in=[fid] if fid else None, tokens=n)
                tr.instant("complete", reason="stop" if eos else "length",
                           tokens=n, ttft_ms=round(ttft, 3),
                           itl_ms=round(itl, 4))
        if req.on_token is not None:
            # after the done/eviction decision so the hook sees the final
            # state; a hook exception must not wedge the shared loop (the
            # token is already delivered and the slot already settled)
            try:
                req.on_token(tok, req.done)
            except Exception:
                from ..utils.logging import logger
                logger.warning("scheduler on_token hook raised", exc_info=True)

    # ------------------------------------------------------------------ decode
    def _adapter_arg(self, rows):
        """The fused program's ``lora`` argument for this dispatch: a tuple
        over rank buckets of ``(per-row pool-slot indices (num_slots,),
        {site: (A_pool, B_pool)})`` — or None when NO live row carries an
        adapter, in which case the plain (byte-identical pre-adapter)
        program variant runs and base-only traffic pays nothing. Rows
        without an adapter index slot 0 (the reserved zero page) of every
        bucket; which rows carry which adapter is pure runtime data."""
        if self.adapters is None:
            return None
        refs = [(slot, req.adapter_ref) for slot, req in rows
                if req.adapter_ref is not None]
        if not refs:
            return None
        buckets = self.adapters.bucket_keys()
        N = self.cache.num_slots
        idx = {b: np.zeros(N, np.int32) for b in buckets}
        for slot, ref in refs:
            idx[ref.bucket][slot] = ref.slot
        pools = self.adapters.device_pools()
        return tuple((jnp.asarray(idx[b]), pools[b]) for b in buckets)

    def _live_rows(self):
        """The decode rows of the next launch: active, not parked, and with
        budget left once the tokens in flight have landed (a row that reaches
        ``max_new_tokens`` inside the sync in flight is known to end there; it
        stays in ``active`` until that sync lands and releases its slot)."""
        return [(s, r) for s, r in sorted(self.active.items())
                if s not in self._parked and len(r.out) + r.inflight < r.max_new_tokens]

    def _assemble(self, rows, width, more=None, prefill=None):
        """THE host block of one launch (:class:`_Operands`): the ``(num_slots,
        width)`` ids, the lengths, the spans and the six per-slot
        sampling-parameter rows of a compiled step program, with whether any
        row samples or collects logits. Every launch is assembled here (the
        bit-identity contract between the decode, chunk, verify and back-off
        paths rests on this assembly being one), and
        :meth:`_step_args` alone turns it into a program's arguments.

        ``rows``: the decode rows, ``(slot, request)``. Column 0 is a row's
        last token, or ``_CARRIED`` where tokens of it are in flight and the
        last is still on the device (:func:`_merge_carried`); ``more[i]``: the
        further columns of ``rows[i]`` (a drafter's proposals), which widen
        its span; ``steps`` is each row's ABSOLUTE step index, so results are
        K/fused-invariant. ``prefill``: ``(request, pos, take)`` of the prefill
        lane's row, ``take`` prompt tokens from ``pos`` (its step stays 0: the
        chunk samples token 0). Dead and cached rows keep span 0 and length 0:
        their writes are dropped, and the paged kernel's KV-block walk stays
        bounded by the longest LIVE row, not the longest retained prefix."""
        N = self.cache.num_slots
        lengths = self.cache.lengths
        ids = np.zeros((N, width), np.int32)
        lens = np.zeros(N, np.int32)
        spans = np.zeros(N, np.int32)
        seeds = np.zeros(N, np.uint32)
        steps = np.zeros(N, np.int32)
        flags = np.zeros(N, bool)
        temps = np.ones(N, np.float32)
        topks = np.zeros(N, np.int32)
        topps = np.ones(N, np.float32)
        sampling = False
        collect = False
        for i, (slot, req) in enumerate(rows):
            ids[slot, 0] = _CARRIED if req.inflight else req.out[-1]
            spans[slot] = 1
            if more is not None:
                cols = more[i]
                ids[slot, 1:1 + len(cols)] = cols
                spans[slot] += len(cols)
            lens[slot] = lengths[slot]
            seeds[slot] = req.seed
            steps[slot] = len(req.out) + req.inflight  # prefill consumed step 0
            flags[slot] = req.do_sample
            temps[slot] = req.temperature
            topks[slot] = req.top_k
            topps[slot] = req.top_p
            sampling = sampling or req.do_sample
            collect = collect or req.collect_logits
        if prefill is not None:
            req, pos, take = prefill
            ps = req.slot
            ids[ps, :take] = req.prompt[pos:pos + take]
            spans[ps] = take
            lens[ps] = lengths[ps]  # prefix copy and/or earlier chunks
            seeds[ps] = req.seed
            flags[ps] = req.do_sample
            temps[ps] = req.temperature
            topks[ps] = req.top_k
            topps[ps] = req.top_p
            sampling = sampling or req.do_sample
            collect = collect or req.collect_logits
        return _Operands(ids, lens, spans, seeds, steps, flags, temps, topks, topps,
                         sampling, collect)

    def _step_args(self, ops, eo=None, held=None, extra=(), drafts=False):
        """A step program's arguments from an assembled block, in THE canonical
        order: ``(params, pool, ids, lens, spans, seeds, steps, flags, temps,
        topks, topps)``, then the extent operands (``eo``:
        :meth:`_ext_operands`, or None), then a state pool's substep spans
        (:meth:`_substep_spans`, ``held``: the slot of a prefill row whose
        chunk is not its last), then the caller's ``extra``. The only place a
        launch's operands are put on the device, a warm-up's and a served
        one's alike, so the two cannot differ in an operand's dtype or
        sharding. ``drafts``: the device drafter's programs, whose lengths and
        steps may be carried on the device like the ids
        (``device_draft.py: _draft_inputs``) and which take no substep spans."""
        ids, lens, spans, seeds, steps, flags, temps, topks, topps = ops[:9]
        if drafts:
            ids, lens, steps = self._draft_inputs(ids, lens, steps)
            tail = ()
        else:
            ids, lens, steps = self._device_ids(ids), jnp.asarray(lens), jnp.asarray(steps)
            tail = self._substep_spans(spans, held)
        args = (self.engine.params, self.cache.pool, ids, lens, jnp.asarray(spans),
                jnp.asarray(seeds), steps, jnp.asarray(flags), jnp.asarray(temps),
                jnp.asarray(topks), jnp.asarray(topps))
        if eo is not None:
            args += tuple(jnp.asarray(x) for x in eo)
        return args + tail + tuple(extra)

    def _device_ids(self, ids):
        """The host's ids block on the device, its carried rows filled in
        from the token block of the sync in flight."""
        dev = jax.device_put(ids, self._ids_sharding)
        if self._flight is not None and (ids[:, 0] == _CARRIED).any():
            dev = self._merge(dev, self._flight.out[0])
        return dev

    def _advance(self, rows, K):
        """A sync that runs ``rows`` for ``K`` steps was launched: the host's
        view moves to the state after it. ``cache.lengths`` as a whole means
        the rows written by everything launched, not by everything landed."""
        for slot, req in rows:
            self.cache.lengths[slot] += K
            req.inflight += K

    def _fetch_block(self, out, collect, K, program=None):
        """Fetch a compiled step program's result behind the pool (which
        the launch already handed on): the (K, num_slots) token block (+
        logits when collected, the routing choice, the MoE stats).
        ``program``: the landed flight's (:meth:`_landed`); None where the
        sync was fetched by the step that launched it."""
        # the device_get waits for THIS sync. With the next one launched
        # already the device has work queued when sched/fetch closes; where
        # the pump is serial the device is idle from then until the next
        # sched/dispatch opens
        with self._span("sched/fetch"):
            toks_k, *rest = out
            self._pop_expert_stats(rest)
            # (K, N, V); MoE programs add their routing choice behind it
            logits_k = np.asarray(jax.device_get(rest[0]), np.float32) if collect else None
            self._choice = (tuple(np.asarray(x) for x in jax.device_get(rest[1:]))
                            if len(rest) == 3 else None)
            toks_k = np.asarray(jax.device_get(toks_k)).reshape(K, self.cache.num_slots)
        self._landed(program)
        self._steps += K
        return toks_k, logits_k

    def _landed(self, program=None):
        """Behind every ``sched/fetch``: where the pump's account found the
        period that just closed device-bound (``HostGapTracker.device_s``:
        the pump waited for the device, so the period IS the device's time
        for this sync), it is a sample for the capacity gauges, priced as the
        landed program (``program``: the flight's, or the last one
        dispatched where the sync landed in the step that launched it).
        Nothing is fenced for it and a host-bound period is no sample."""
        gap = self._gap
        if gap is None or gap.device_s is None:
            return
        program = program if program is not None else self._dispatched
        if program is None:
            return
        key, split, spans, lens = program
        width, ksteps = program_shape(key)
        live_ctx = lens[spans > 0] if spans.shape == lens.shape else lens
        # the extent-walk kernels DMA every extent's pool column per KV
        # block, so their KV traffic prices at max_extents x contiguous
        kv_mult = self.cache.max_extents if key[0] in ("fused_ext", "fused_seqp") else 1
        self.capacity.observe_dispatch(key, gap.device_s, live_ctx, width, ksteps,
                                       kv_mult=kv_mult, split=split)

    def _pop_expert_stats(self, rest):
        """Where a step program's MoE stats ride its result to the landing
        (stats on, no offload: nothing at launch reads the routing counts, and
        :meth:`_call_step` strips them itself under offload): fetch, record
        and strip them from ``rest``, the outputs behind the token block."""
        if self._moe_stats and self.experts is None:
            self._record_expert_stats(np.asarray(jax.device_get(rest.pop())))

    def _keep_choice(self, req, slot, width, K):
        """Keep, for a request that collects logits, the expert ids the block
        just fetched chose in its row: ``width`` columns of the first
        forward, then one column per substep."""
        if self._choice is not None and req.collect_logits:
            first, substeps = self._choice  # (L, N, C, k), (K, L, N, k)
            req.choice.append(first[:, slot, :width])
            req.choice.extend(substeps[j][:, slot][:, None] for j in range(1, K))

    def _landing_delivered(self):
        """A landing's tokens have all been through their ``on_token`` hooks:
        tell ``on_landing``, if anyone listens (the gateway hands a landing's
        tokens to its event loop at once). Called by every path that
        delivers, under ``sched/deliver`` where it has one: behind a
        landing's last token and BEFORE the pump admits, assembles and
        dispatches the next sync. Like a token hook's, its exception must
        not wedge the shared loop."""
        if self.on_landing is not None:
            try:
                self.on_landing()
            except Exception:
                from ..utils.logging import logger
                logger.warning("scheduler on_landing hook raised", exc_info=True)

    def _deliver_block(self, live, toks_k, logits_k, K, chunk_next=False):
        """Deliver a fetched K-step token block to the rows it was launched
        for. Each row's KV advanced K positions on device (the program wrote
        rows [len, len+K)); tokens past EOS/budget were computed but are
        discarded, and so is the whole row of a request that ended while the
        sync was in flight. ``chunk_next``: the landing's final chunk delivers
        behind these rows, and says so itself. Returns tokens delivered."""
        n_delivered = 0
        discarded = 0
        with self._span("sched/deliver"):
            for slot, req in live:
                req.inflight -= K
                if req.done:
                    discarded += 1
                    continue
                self._keep_choice(req, slot, 1, K)
                for k in range(K):
                    if req.done:
                        break
                    if req.collect_logits and logits_k is not None:
                        req.logits.append(logits_k[k, slot])
                    self._deliver(req, int(toks_k[k, slot]))
                    n_delivered += 1
            if not chunk_next:
                self._landing_delivered()
        self._count_discarded(discarded)
        return n_delivered

    def _count_discarded(self, rows):
        """Rows a landing dropped because their request had ended while the
        sync was in flight."""
        if rows:
            self.ahead_rows_discarded += rows
            if self.telemetry.enabled:
                self.telemetry.counter("serving/ahead_rows_discarded", rows)

    def _deliver_chunk(self, fl, toks_k, logits_k):
        """The chunk's part of a landing. A final chunk's row: token 0, then
        the K-1 tokens its substeps decoded; then, with a migrate hook and
        budget left, the handoff. Returns tokens delivered."""
        preq, _, take, final = fl.chunk
        K = fl.K
        if final:
            preq.inflight -= K
        if preq.done:  # cancelled while the chunk was in flight
            self._count_discarded(1)
            return 0
        ps = preq.slot
        self._keep_choice(preq, ps, take, K if final else 1)
        if not final:
            return 0
        collects = preq.collect_logits and logits_k is not None
        with self._span("sched/deliver"):
            self._first_token(preq, int(toks_k[0, ps]), logits_k[0, ps] if collects else None)
            delivered = 1
            for k in range(1, K):
                if preq.done:
                    break
                if collects:
                    preq.logits.append(logits_k[k, ps])
                self._deliver(preq, int(toks_k[k, ps]))
                delivered += 1
            self._landing_delivered()
        # disaggregated serving: a prefill-role replica hands the
        # freshly-prefilled request to a decode replica here — after
        # this sync's tokens streamed (they were computed anyway), with
        # budget left, via the hook the ReplicaSet installed. The hook
        # runs migrate_out; decode then resumes elsewhere from the
        # exact per-row state this sync left behind, so the stream is
        # bit-identical to staying put. Nothing was launched past this
        # sync (:meth:`_lands_first`). Multi-extent chains and lossy-window
        # rows stay put: the handoff demotes/restores one contiguous slot.
        if (not preq.done and self.migrate_hook is not None
                and ps not in self.cache.chain and preq.kv_window is None):
            self.migrate_hook(self, preq)  # True: migrated out, owned elsewhere
        return delivered

    def _dispatch(self, fn, call_args, spans, lens, chunk=None):
        """Hand ONE compiled program to the device, under ``sched/dispatch``
        (whose start closes the open host gap: the device stops being idle
        the moment the dispatch is enqueued). With the sink on, the observer
        of required work hears of it first (``required_work.py``; ``spans``,
        ``lens``: the host's copies of the spans at ``call_args[4]`` and the
        lengths at ``call_args[3]``; ``chunk``: ``(slot, final)`` of a chunk
        sync's prefill row), and what was dispatched is kept for the landing
        that will time it (:meth:`_landed`). The launch is never fenced."""
        cap = self.capacity
        if cap is not None:  # the sink is on
            key = cap.key_for(fn)
            split = self._splits_chunk(key)
            self._work.dispatched(key, split, spans, lens, chunk)
            self._dispatched = (key, split, spans, lens) if key is not None else None
        with self._span("sched/dispatch"), self.engine.mesh:
            return self._run_program(fn, call_args)

    def _run_program(self, fn, call_args):
        """Call a step program. A call that traced it (the first at these
        shapes) built it: count which K/V commit it was built with —
        ``scatter`` if any layer's span write fell back to the XLA scatter
        (``models/transformer.py: _commit_span_rows``), so a server whose
        steps relay the pool says so; and which one-token update its
        gated-delta layers and its Mamba-2 mixers took, ``xla`` if any fell
        back to the definition (``GatedDeltaNet``, ``Mamba2``), so a server
        whose states go two passes says so."""
        from ..moe.layer import traced_dispatches
        from ..ops.pallas import gdn_step, kv_commit, ssd_step
        updates = ((gdn_step, self.gdn_step_programs, "gdn_step"),
                   (ssd_step, self.ssd_step_programs, "ssd_step"))
        before, moe_before = kv_commit.traced(), traced_dispatches()
        updates_before = [module.traced() for module, _, _ in updates]
        out = fn(*call_args)
        after, moe_after = kv_commit.traced(), traced_dispatches()
        if after != before:
            path = "scatter" if after[1] > before[1] else "inplace"
            self.kv_commit_programs[path] += 1
            self.telemetry.counter(f"serving/kv_commit_{path}_programs")
        for (module, programs, name), was in zip(updates, updates_before):
            now = module.traced()
            if now != was:
                path = "xla" if now[1] > was[1] else "kernel"
                programs[path] += 1
                self.telemetry.counter(f"serving/{name}_{path}_programs")
        if moe_after != moe_before:
            _, dense, broadcast = (a > b for a, b in zip(moe_after, moe_before))
            # the counters say how a program's pairs were EVALUATED: ``dense``
            # if any layer computed every expert on every row
            evaluated = "dense" if dense or broadcast else "sparse"
            self.telemetry.counter(f"serving/moe_{evaluated}_programs")
            # ``moe_dispatch_programs`` says where its rows WENT (the serving
            # benchmark holds ``correct`` to ``dense`` 0 < ``sparse``):
            # ``dense`` if any layer broadcast them over a mesh axis or to
            # paged experts, ``sparse`` if every layer kept to the experts
            # this device holds, ``dense_held`` for those of the ``sparse``
            # ones with a layer of the dense product (benchmarks/SERVING.md)
            self.moe_dispatch_programs["dense" if broadcast else "sparse"] += 1
            self.moe_dispatch_programs["dense_held"] += dense and not broadcast
        return out

    def _substep_spans(self, spans, held=None):
        """The step program's trailing operand for a pool with state leaves:
        each row's span in the sync's SUBSTEPS, 1 for the rows that really
        decode there and 0 for slot ``held``, a prefill row whose chunk is
        not its last. Rows of K/V forgive a substep fed a garbage token (the
        next chunk overwrites what it wrote); a recurrent state does not, so
        such a row is told to stand still. () for a pool of rows only, whose
        programs take no such operand."""
        if not self._state_pool:
            return ()
        sub = np.minimum(spans, 1).astype(np.int32)
        if held is not None:
            sub[held] = 0
        return (jnp.asarray(sub), )

    def _call_step(self, fn, args, lora, spans, lens, chunk=None):
        """Dispatch ONE step program (``spans``, ``lens``: the host's copies
        of ``args[4]`` and ``args[3]``, and ``chunk``: ``(slot, final)`` of a
        chunk sync's prefill row, for :meth:`_dispatch`'s counters), owning
        the MoE serving plumbing:

        - dense models (or MoE with telemetry off and no offload): a plain
          dispatch, byte-identical to the pre-MoE scheduler;
        - MoE with stats and no offload: the program's trailing per-layer
          expert-counts output rides the result to the landing, where
          :meth:`_fetch_block` fetches, records and strips it (nothing at
          launch reads it, and fetching it here would fence the launch);
        - cold-expert offload: dispatch against a consistent residency
          snapshot, diff the routed experts against it, and on a miss
          hot-load the wanted pages and RE-DISPATCH the same program with
          the same inputs (the replay rewrites every KV row the garbage
          forward wrote — results are exact; pools are immutable arrays,
          so a sibling replica's churn can't corrupt this dispatch).

        Raises :class:`_ExpertOverflow` (carrying the donated-through pool)
        when a layer's single-step routing demand exceeds the resident
        pool — the caller backs off to a smaller step.
        """
        extra = (lora, ) if lora is not None else ()
        if self.experts is None:
            return self._dispatch(fn, args + extra, spans, lens, chunk)
        replays = 0
        # hard bound on the replay loop: each round loads at least one page
        # on this replica, so L*E rounds can only be exceeded by pathological
        # cross-replica eviction thrash — fail loudly instead of spinning
        max_replays = 2 * self.experts.num_layers * self.experts.num_experts + 8
        while True:
            emap, pools, resident = self.experts.dispatch_operands()
            out = self._dispatch(fn, args + extra + ((emap, pools), ), spans, lens, chunk)
            counts = np.asarray(jax.device_get(out[-1]))[:, :-2]
            used = counts > 0
            if not self.experts.missing(used, resident).any():
                self.experts.touch(used)
                self._record_expert_stats(np.asarray(jax.device_get(out[-1])))
                return out[:-1]
            # the donated pool moved forward; replay reads the new buffers
            args = args[:1] + (out[0], ) + args[2:]
            if not self.experts.ensure(used):
                raise _ExpertOverflow(out[0])
            replays += 1
            self.expert_replays += 1
            if self.telemetry.enabled:
                self.telemetry.counter("serving/expert_replays")
                # goodput: a miss-replay re-runs the whole step program and
                # discards the garbage forward — every column dispatched this
                # round was wasted work (the replay recomputes it)
                if self.capacity is not None and counts.size:
                    L = max(1, self.experts.num_layers)
                    topk = max(1, getattr(self.experts, "top_k", 1) or 1)
                    self.capacity.account(
                        0, wasted_tokens=float(counts.sum()) / (L * topk))
            if replays > max_replays:
                raise RuntimeError(
                    f"cold-expert replay did not converge after {replays} "
                    f"re-dispatches (cross-replica eviction thrash?); raise "
                    f"expert_offload.resident_experts")

    def _held_experts(self, num_experts):
        """The slice of the router's experts that this engine's layers hold."""
        mc = self.engine.model_config
        lo = getattr(mc, "moe_first_expert", 0)
        return slice(lo, lo + getattr(mc, "experts_held", num_experts))

    def _moe_forward_stats(self, counts):
        """In-program: ONE forward's (L, E) routed-pair counts (live rows,
        over all the router's experts), widened by two columns a step
        program can sum over its forwards: how many of the experts held
        here some live row routed to, and 1 (a layer call)."""
        here = counts[:, self._held_experts(counts.shape[1])]
        return jnp.concatenate(
            [counts, jnp.sum(here > 0, axis=1, keepdims=True, dtype=counts.dtype),
             jnp.ones((counts.shape[0], 1), counts.dtype)], axis=1)

    def _record_expert_stats(self, stats):
        """Routing telemetry from one successful dispatch's (L, E + 2)
        stats (:meth:`_moe_forward_stats`, summed over the sync's forwards):
        total token->expert assignments, the per-step load-balance gauge
        (1.0 = tokens spread evenly; 1/E = everything on one expert), and
        the row-expert pairs by where the expert lives."""
        counts, touched, calls = stats[:, :-2], stats[:, -2], stats[:, -1]
        total = int(counts.sum())
        self.expert_dispatch_tokens += total
        tel = self.telemetry
        if not tel.enabled or total == 0:
            return
        here = int(counts[:, self._held_experts(counts.shape[1])].sum())
        tel.counter("serving/moe_pairs_here", here)
        tel.counter("serving/moe_pairs_elsewhere", total - here)
        tel.counter("serving/moe_experts_touched", int(touched.sum()))
        tel.counter("serving/moe_layer_calls", int(calls.sum()))
        tel.counter("serving/expert_dispatch_tokens", total)
        mx = counts.max(axis=1)
        tot = counts.sum(axis=1)
        live = mx > 0
        if live.any():
            E = counts.shape[1]
            balance = float(np.mean(tot[live] / (E * mx[live])))
            tel.gauge("serving/expert_load_balance", balance)

    # ------------------------------------------------------------------ offload backoff
    def _decode_backoff(self, live):
        """Cold-expert pressure path: advance live rows ONE token each, in
        overflow-safe row groups through the (1-step, width-1) program —
        group demand shrinks with group size, and a single row needs at
        most ``top_k`` experts per layer, which the store validated fits.
        Excluded rows keep span 0 (no KV write, nothing delivered) and
        simply advance in a later group/sync."""
        pending = list(live)
        delivered = 0
        while pending:
            group = list(pending)
            while True:
                with self._span("sched/assemble"):
                    ops = self._assemble(group, 1)
                    lora = self._adapter_arg(group)
                    eo = self._ext_operands(group)
                    fn = self._fused_fn(ops.sampling, ops.collect, 1, 1, lora=lora is not None,
                                        ext=eo is not None)
                    args = self._step_args(ops, eo)
                try:
                    out = self._call_step(fn, args, lora, ops.spans, ops.lens)
                    break
                except _ExpertOverflow as e:
                    self.cache.pool = e.pool
                    if len(group) == 1:
                        raise RuntimeError(
                            "expert_offload: a single decode row exceeded "
                            "resident_experts — impossible when "
                            "resident_experts >= moe_top_k (validated at "
                            "build); this is a bug")
                    group = group[:(len(group) + 1) // 2]
            self.cache.pool = out[0]
            self._advance(group, 1)
            toks_k, logits_k = self._fetch_block(out[1:], ops.collect, 1)
            delivered += self._deliver_block(group, toks_k, logits_k, 1)
            done = {slot for slot, _ in group}
            pending = [(s, r) for (s, r) in pending if s not in done]
        return delivered

    def _fused_backoff(self, pf, live):
        """Cold-expert pressure during a fused chunk sync: feed the prefill
        row ALONE in shrinking chunk pieces (a piece of ``t`` prompt tokens
        demands at most ``t * top_k`` experts per layer; one token always
        fits), then advance the decode rows through the decode backoff so a
        long constrained prefill can't starve them. Chunk boundaries are
        preserved upward — pieces only subdivide the chunk the normal path
        would have fed — so the KV this path writes is byte-identical to
        the unconstrained sync's."""
        preq = pf.req
        C = self.prefill_chunk
        S = self.max_len
        ps = preq.slot
        L = preq.prompt.size
        delivered = 0
        chunk_end = min(pf.pos + C, L)
        while pf.pos < chunk_end:
            # never cross an extent boundary mid-piece: the write targets
            # one extent per forward (same rule as the normal chunk step)
            take = min(chunk_end - pf.pos, S - pf.pos % S)
            while True:
                ops = self._assemble([], C, prefill=(preq, pf.pos, take))
                lora = self._adapter_arg([(ps, preq)])
                eo = self._ext_operands([(ps, preq)])
                fn = self._fused_fn(ops.sampling, ops.collect, 1, C,
                                    lora=lora is not None, ext=eo is not None)
                try:
                    out = self._call_step(fn, self._step_args(ops, eo), lora,
                                          ops.spans, ops.lens)
                    break
                except _ExpertOverflow as e:
                    self.cache.pool = e.pool
                    if take == 1:
                        raise RuntimeError(
                            "expert_offload: a single prompt token exceeded "
                            "resident_experts — impossible when "
                            "resident_experts >= moe_top_k (validated at "
                            "build); this is a bug")
                    take = (take + 1) // 2
            self.cache.pool = out[0]
            piece = _Flight(out[1:], 1, preq.collect_logits, [],
                            (preq, pf.pos, take, pf.pos + take >= L))
            pf.pos += take
            # single-step: no substep rows past the chunk's
            self.cache.lengths[ps] = pf.pos
            if piece.final:
                preq.inflight += 1
                self._book_decode_row(preq)
            toks_k, logits_k = self._fetch_block(piece.out, piece.collect, 1)
            delivered += self._deliver_chunk(piece, toks_k, logits_k)
        if live:
            delivered += self._decode_backoff(live)
        return delivered, 1

    def warm_programs(self, ladder=True):
        """Dispatch every step-program variant the cold-expert replay and
        backoff ladder can reach — the (K, chunk) primary, its (1, chunk) /
        (K, 1) / (1, 1) fallbacks, greedy AND sampled, plus the speculative
        verify when drafting is on — against the live pool with ALL spans
        zero: no KV row is written, nothing is delivered, so the warm is
        invisible to traffic. Runs at build (before any gateway recompile
        watch arms), which is what makes residency churn recompile-free
        mid-stream. Requests overriding ``collect_logits`` per-call still
        compile their variant on first use. ``ladder=False`` leaves out the
        (1, 1) program only the backoff ladder and an extent boundary reach
        (the serving CLI's start-up warm: what plain traffic dispatches)."""
        N = self.cache.num_slots
        C = self.prefill_chunk
        K = self.steps_per_sync
        # multi-LoRA composes with offload: warm the lora program variants
        # too, with every row on the reserved all-zero slot-0 pages (the
        # backoff ladder otherwise compiles them on its first
        # adapter-bearing overflow, after the recompile watch armed)
        lora_args = (None, )
        if self.adapters is not None:
            pools = self.adapters.device_pools()
            lora_args += (tuple((jnp.asarray(np.zeros(N, np.int32)), pools[b])
                                for b in self.adapters.bucket_keys()), )

        def dispatch(fn, width, lora, eo=None):
            # no row: every span zero, through the seam that serves
            ops = self._assemble([], width)
            out = self._call_step(fn, self._step_args(ops, eo), lora, ops.spans, ops.lens)
            self.cache.pool = out[0]

        shapes = {(K, C), (1, C), (K, 1)} | ({(1, 1)} if ladder else set())
        # (steps, width, extent walk, seq-sharded) of every plain step program.
        # Seq-parallel prefill reaches the PLAIN program at the wide chunk
        # width when the seq axis has one device (same math, unsharded)
        wide = ({(K, self._seq_chunk), (1, self._seq_chunk)}
                if (self._seq_chunk and self._seq_shards == 1) else set())
        variants = [(k, w, False, False) for k, w in sorted(shapes | wide)]
        eo = None
        if (self.cache.max_extents > 1 or self.allow_lossy_kv
                or self._seq_chunk):
            # long-context variants: the extent program at every shape the
            # decode/backoff/chunk ladder reaches (plus the seq-parallel
            # chunk width), and the seq-sharded program at its one width —
            # warmed with the identity extent table and all spans zero
            eo = self._ext_operands([], force=True)
            if self._seq_chunk:
                shapes |= {(K, self._seq_chunk), (1, self._seq_chunk)}
            variants += [(k, w, True, False) for k, w in sorted(shapes)]
            if self._seq_shards > 1:
                variants += [(k, self._seq_chunk, True, True) for k in (K, 1)]
        for sampling in (False, True):
            for lora in lora_args:
                for ksteps, width, ext, seqp in variants:
                    dispatch(self._fused_fn(sampling, self.collect_logits, ksteps, width,
                                            lora=lora is not None, ext=ext, seqp=seqp),
                             width, lora, eo if ext else None)
                if self.drafter is not None:
                    dispatch(self._spec_fn(sampling, self.collect_logits,
                                           self._spec_width,
                                           lora=lora is not None),
                             self._spec_width, lora)
        if self.radix is not None:
            # the radix slot-copy program (src == dst is the identity copy,
            # safe against any pool state)
            with self.engine.mesh:
                self.cache.pool = self._copy_fn()(
                    self.cache.pool, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32))
        # the carried-token merge of a sync launched ahead, at every ids
        # width such a sync can have, over a K-step token block
        toks = jax.device_put(np.zeros((K, N), np.int32), self._ids_sharding)
        for width in sorted({1, C} | ({self._seq_chunk} if self._seq_chunk else set())):
            self._merge(self._device_ids(np.zeros((N, width), np.int32)), toks)

    def _decode_step(self):
        """Launch a pure decode sync: the fused program at chunk width 1
        (every live row span 1, no prefill row) — ONE on-device step body
        serves both paths, so fused-vs-decode results can never diverge. Dead
        and cached rows carry span 0 and length 0: their writes are dropped
        and the paged kernel's KV-block walk stays bounded by the longest LIVE
        row, not the longest retained prefix. Returns the :class:`_Flight`,
        None when every active row ends inside the sync in flight, or
        (delivered, 1) where cold-expert pressure made it back off."""
        with self._span("sched/assemble"):
            live = self._live_rows()
            if not live:
                return None
            ops = self._assemble(live, 1)
            K = self.steps_per_sync
            eo = self._ext_operands(live)
            if eo is not None and K > 1:
                # a K-step sync writes rows [len, len+K) contiguously in the
                # write extent — a row about to cross an extent boundary steps
                # through it one token at a time (the (1, 1) program is warm)
                S = self.max_len
                if any(S - int(self.cache.lengths[s]) % S < K for s, _ in live):
                    K = 1
            lora = self._adapter_arg(live)
            fn = self._fused_fn(ops.sampling, ops.collect, K, 1, lora=lora is not None,
                                ext=eo is not None)
            args = self._step_args(ops, eo)
        try:
            out = self._call_step(fn, args, lora, ops.spans, ops.lens)
        except _ExpertOverflow as e:
            # a K-step sync's routing union outgrew the expert pool: advance
            # one token per row in overflow-safe groups instead
            self.cache.pool = e.pool
            return self._decode_backoff(live), 1
        self.cache.pool = out[0]
        self._advance(live, K)
        return _Flight(out[1:], K, ops.collect, live)

    # ------------------------------------------------------------------ speculative decode
    def _spec_decode_step(self):
        """One self-speculative verify sync: the prompt-lookup drafter
        proposes up to ``spec_tokens`` continuation tokens per live row,
        and ONE fused span dispatch (:meth:`_spec_fn`) verifies every
        column — the same per-row ``q_spans`` machinery chunked prefill
        rides, with draft tokens as the extra query columns. Each column is
        sampled with the request's keys at its absolute step index; a draft
        commits only when it EQUALS the sampled token, so accepted streams
        are bit-identical to non-speculative decode and the first mismatch
        truncates (its garbage KV rows sit past the write head until later
        writes reclaim them). Rows advance by their own accepted count —
        between 1 and ``1 + spec_tokens`` tokens per dispatch. A sync where
        NO row drafts falls back to the K-step decode program, keeping its
        dispatch amortization when the drafter is dry. A drafter reads the
        accepted tokens, so this pump is serial (:meth:`_lands_first`): the
        verify lands here, and nothing is in flight when it is assembled.
        Returns (delivered, 1), or what :meth:`_decode_step` returns."""
        N, W = self.cache.num_slots, self._spec_width
        live = [(s, r) for s, r in sorted(self.active.items())
                if s not in self._parked]
        if any(s in self.cache.chain or r.kv_window is not None
               for s, r in live):
            # speculation is opportunistic: the verify program carries no
            # extent walk, and a chained/lossy row's drafts would verify
            # against truncated KV — advance exactly instead (bit-identical
            # either way; the extent mix is rare relative to decode syncs)
            return self._decode_step()
        drafts = []
        total_draft = 0
        for slot, req in live:
            # cap drafts at the remaining budget (a request one token from
            # done gains nothing from verify columns) and the slot's KV
            # headroom (the verify block writes span rows at the head)
            cap = min(W - 1, req.max_new_tokens - len(req.out) - 1,
                      self.max_len - int(self.cache.lengths[slot]) - 1)
            d = (self.drafter.draft(
                np.concatenate([req.prompt, np.asarray(req.out, np.int32)]), cap)
                if cap > 0 else np.empty(0, np.int32))
            drafts.append(d)
            total_draft += d.size
        if total_draft == 0:
            return self._decode_step()
        with self._span("sched/assemble"):
            # the drafts as each row's further columns; the verify program
            # carries no extent walk and no pool of state reaches it
            ops = self._assemble(live, W, more=drafts)
            ids, spans, collect = ops.ids, ops.spans, ops.collect
            lora = self._adapter_arg(live)
            fn = self._spec_fn(ops.sampling, collect, W, lora=lora is not None)
            args = self._step_args(ops)
        try:
            out = self._call_step(fn, args, lora, spans, ops.lens)
        except _ExpertOverflow as e:
            # speculation is opportunistic — skip it for this sync and
            # advance one exact token per row (bit-identical either way)
            self.cache.pool = e.pool
            return self._decode_backoff(live), 1
        with self._span("sched/fetch"):
            self.cache.pool, toks_k, *rest = out
            self._pop_expert_stats(rest)
            # (W, N, V)
            logits_k = np.asarray(jax.device_get(rest[0]), np.float32) if collect else None
            toks_k = np.asarray(jax.device_get(toks_k)).reshape(W, N)
        self._landed()
        self._steps += 1
        tel = self.telemetry
        delivered = 0
        accepted = 0
        for slot, req in live:
            span = int(spans[slot])
            # acceptance walk: toks_k[j] is the sampled token FOLLOWING
            # column j; column j+1's logits are valid only while the draft
            # it was conditioned on equals the sampled token
            m = 1
            while m < span and toks_k[m - 1, slot] == ids[slot, m]:
                m += 1
            self.cache.lengths[slot] += m
            row_delivered = 0
            for j in range(m):
                if req.done:
                    break
                if req.collect_logits and logits_k is not None:
                    req.logits.append(logits_k[j, slot])
                self._deliver(req, int(toks_k[j, slot]))
                row_delivered += 1
            # count only tokens that actually reached the stream: an EOS
            # accepted mid-block truncates delivery, and counting the
            # discarded tail would inflate the acceptance-rate signal the
            # k-tuning docs tell operators to watch
            delivered += row_delivered
            accepted += max(0, row_delivered - 1)
            if tel.enabled:
                tel.histogram("serving/spec_tokens_per_step", row_delivered)
        self._landing_delivered()
        self.spec_steps += 1
        self.spec_row_steps += len(live)
        self.spec_drafted += total_draft
        self.spec_accepted += accepted
        self.spec_delivered += delivered
        if tel.enabled:
            tel.counter("serving/spec_steps")
            tel.counter("serving/spec_draft_tokens", total_draft)
            tel.counter("serving/spec_accepted_tokens", accepted)
            tel.gauge("serving/spec_acceptance_rate",
                      self.spec_accepted / max(1, self.spec_drafted))
        return delivered, 1

    def mean_spec_tokens_per_step(self):
        """Mean tokens delivered per (live row, speculative sync) — > 1.0
        means speculation is netting multi-token steps."""
        return self.spec_delivered / self.spec_row_steps if self.spec_row_steps else 0.0

    # ------------------------------------------------------------------ fused chunk step
    def _fused_chunk_step(self):
        """Launch one fixed-shape fused SYNC whose ids are a ``(num_slots,
        prefill_chunk)`` block (run whole, or as its live rows only:
        :meth:`_splits_chunk`) plus the remaining ``steps_per_sync - 1``
        decode steps, all in one dispatch: live decode rows advance K tokens
        (column 0 + the substeps), the in-flight prefill row consumes up to
        a chunk of prompt tokens (and, on its final chunk, starts decoding
        in the same dispatch), dead rows carry span 0 (their KV writes are
        dropped, so retained prefix slots stay byte-stable). The prefill
        lane advances HERE, at the launch: a final chunk frees it and books
        its row as a decode row (:meth:`_book_decode_row`), so the next
        queued prompt's first chunk rides the very next sync. Returns the
        :class:`_Flight`, or (delivered, 1) where cold-expert pressure made
        it back off."""
        pf = self._prefill
        preq = pf.req
        # sequence-parallel prefill: wide chunks (the seq-parallel width),
        # sharded over the seq mesh axis when it has devices — on a 1-device
        # axis the plain program at the wide width is the same math (chunk
        # boundaries don't change per-column attention), just unsharded
        seqp = pf.seq_parallel and self._seq_shards > 1
        C = self._seq_chunk if pf.seq_parallel else self.prefill_chunk
        S = self.max_len
        L = preq.prompt.size
        # a chunk never crosses an extent boundary: each wide forward's KV
        # write lands in exactly one extent's pool row
        take = min(C, L - pf.pos, S - pf.pos % S)
        final = pf.pos + take >= L
        with self._span("sched/assemble"):
            live = self._live_rows()
            ops = self._assemble(live, C, prefill=(preq, pf.pos, take))
            ps = preq.slot
            # substeps only pay off when something real decodes in them: live
            # rows, or the prefill row itself once its final chunk lands — a
            # non-final chunk on an otherwise idle pool runs the 1-step
            # variant. Rows of the sync in flight keep a pool from being idle
            # even where every one of them ends there: the launch stays on
            # the program a busy pool runs and the landing settles who is left
            K = self.steps_per_sync if (live or final or any(
                r.inflight for r in self.active.values())) else 1
            eo = self._ext_operands(live + [(ps, preq)], force=seqp)
            if eo is not None and K > 1:
                # substep writes stay inside each row's write extent: decode
                # rows need K rows of extent headroom; a FINAL chunk's row
                # needs its chunk plus the K-1 substep rows to fit its extent
                room = [S - int(self.cache.lengths[s]) % S for s, _ in live]
                if final:
                    room.append(S - pf.pos % S - take + 1)
                if any(r < K for r in room):
                    K = 1
            lora = self._adapter_arg(live + [(ps, preq)])
            fn = self._fused_fn(ops.sampling, ops.collect, K, C, lora=lora is not None,
                                ext=eo is not None, seqp=seqp)
            args = self._step_args(ops, eo, held=None if final else ps)
        try:
            out = self._call_step(fn, args, lora, ops.spans, ops.lens, chunk=(ps, final))
        except _ExpertOverflow as e:
            # the chunk's routing demand outgrew the expert pool: feed the
            # prefill alone in shrinking pieces, then advance decode rows
            self.cache.pool = e.pool
            return self._fused_backoff(pf, live)
        self.cache.pool = out[0]
        self._advance(live, K)
        fl = _Flight(out[1:], K, ops.collect, live, (preq, pf.pos, take, final))
        pf.pos += take
        if final:
            # the chunk's rows plus K-1 substep rows: token 0's KV lands
            # when substep 1 consumes it; the newest token's KV is written
            # when the NEXT sync feeds it (same contract as the decode
            # program)
            self.cache.lengths[ps] = L + K - 1
            preq.inflight += K
            self._book_decode_row(preq)
        else:
            self.cache.lengths[ps] = pf.pos
        return fl

    # ------------------------------------------------------------------ compiled programs
    def _program(self, key, builder):
        """Compiled-program cache lookup with locked insertion: the cache
        dict may be SHARED across a replica set's schedulers (their pump
        threads race the same first-touch), and a double build would both
        waste a compile and break the replicas-add-zero-programs guard."""
        fn = self._compiled.get(key)
        if fn is None:
            with _PROGRAM_LOCK:
                fn = self._compiled.get(key)
                if fn is None:
                    fn = self._compiled[key] = builder()
        if self.capacity is not None:
            # roofline registry (telemetry/capacity.py): idempotent, so a
            # shared-cache replica registers its siblings' programs too
            self.capacity.register(key, fn)
        return fn

    def _jit_step(self, fn, aux_outs, donate):
        """jit a step program. Under tp>1 the pool output pins to the
        layout ``_init_cache`` materialized (head shard over ``tensor``)
        and host-bound outputs (tokens/logits) pin replicated — leaving
        them to propagation lets GSPMD re-layout the donated pool between
        program variants, churning reshards across the fused/spec/copy
        mix. ``aux_outs``: host-bound outputs after the pool (0 = the
        program returns the bare pool tree). At tp=1 nothing is pinned —
        the programs stay byte-identical to the unsharded scheduler's."""
        if self._pool_sharding is None:
            return jax.jit(fn, donate_argnums=donate)
        outs = (self._pool_sharding if aux_outs == 0
                else (self._pool_sharding, ) + (self._host_sharding, ) * aux_outs)
        return jax.jit(fn, donate_argnums=donate, out_shardings=outs)

    def _splits_chunk(self, key):
        """Whether the step program under ``key`` runs its first forward as
        two over the live rows (:meth:`_fused_fn`): the plain per-projection
        program (``fused``) and the fused int8 decode blocks
        (``fused_block``), on one device, at a shape where that is cheaper
        for the bytes of their weights (:func:`_split_pays`: ``fused_block``
        streams int8, whose ridge is half bf16's, so it also splits blocks
        of 240 to 480 rows such as (4, 64)). The extent and seq-parallel
        variants, adapters, a sharded pool and the verify programs
        (``spec``, ``spec_block``) keep the whole block."""
        return (isinstance(key, tuple) and key[0] in ("fused", "fused_block")
                and key[-1] != "lora" and self._shard_deg == 1
                and _split_pays(self.cache.num_slots, key[3],
                                1 if key[0] == "fused_block" else 2))

    def _fused_fn(self, sampling, collect, ksteps, chunk, lora=False,
                  ext=False, seqp=False):
        """THE step program: per-row query spans over a fixed ``(num_slots,
        chunk)`` ids block (one forward over the block, or two over its live
        rows), then the sync's remaining ``ksteps - 1`` decode
        steps in the same on-device loop — one dispatch per scheduler
        iteration, so decode keeps its K-step amortization while prefills
        chain. A pure decode sync is the same program at ``chunk == 1``
        (every live row span 1): one step body serves both paths, so
        fused-vs-decode results can never diverge. Which row is prefilling,
        its chunk fill, and every sampling parameter are runtime data —
        compiled at most (greedy/sampling) x logits-collection x two step
        counts (K, and 1 for chunks with nothing to decode) x two widths
        (chunk, 1) regardless of the prompt-length mix.

        Live rows only: where :meth:`_splits_chunk` says so (decided here,
        when the program is built, from its key, the device count, the
        shape and the bytes of the weights: ``fused`` and ``fused_block``
        alike), the first forward of a ``chunk > 1`` program is two
        (:func:`_first_forward_live_rows`): the ``(num_slots, 1)`` column,
        exactly the decode program's first forward, and the chunk's columns
        as a ``(1, chunk)`` forward over its own slot's rows of the pool,
        found from the spans at run time. Same key, same arguments, same
        outputs (the routing choice keeps its (L, N, C, k) shape; the MoE
        stats count two layer calls a layer for that phase); the substeps
        are untouched.

        Substep write positions: each row continues at its own write head
        (``lengths + max(span, 1) - 1 + k``) — decode rows one past their
        column-0 token, a FINAL chunk's row one past its chunk (so the
        fresh request starts decoding inside this very dispatch). Span-0
        (dead/cached) rows never write — the span-write path drops their
        rows in the first forward AND the substeps — so the scheduler can
        pass them length 0 and keep the paged kernel's KV-block walk
        bounded by the longest LIVE row, not the longest retained prefix.
        A prefill row whose chunk is NOT its last rides the substeps on a
        garbage token: its K/V rows are overwritten by the next chunk. Where
        slots hold recurrent state that would corrupt it, so such a pool's
        program takes the substep spans as an operand
        (:meth:`_substep_spans`) and the row stands still.

        Fused decode blocks: when the engine's structured gate passes
        (``self._fused_block``) the forward routes through
        ``CausalLMModel.fused_paged_step`` — three resident Pallas kernels
        per layer (qkv+norm+rope, paged attention, out/mlp) instead of the
        per-projection ``apply_with_cache`` path, with IDENTICAL
        write-index/q_spans threading and pool layout. The program key is
        retagged ``fused_block`` so capacity telemetry prices the fused
        kind separately; the variant count is unchanged, so the O(1)
        compiled-programs contract holds. A split chunk program calls the
        kernels at two row counts (``num_slots`` and ``chunk``: the chunk's
        forward reads and writes one slot's rows of each pool leaf, never a
        whole leaf); each kernel is jitted (``ops/pallas/decode_block.py``,
        ``decode_attention.py``, ``kv_commit.py``), so the program's layers,
        its column and its loop body share one lowering a row count.

        ``lora=True`` builds the multi-adapter variant: the program takes a
        trailing ``lora`` argument (per-bucket pool tensors + per-row slot
        indices), gathers each row's (A, B) pages ONCE, and threads them
        through every forward of the sync — first span write and all K-1
        substeps alike. The plain variant keeps its pre-adapter key and
        trace, so base-only dispatches run the byte-identical old program;
        both variants together stay O(1) in adapter count/mix/churn (which
        rows carry which adapter is runtime data, pool shapes are fixed by
        the bucket config).

        ``ext=True`` builds the multi-extent variant: the program takes the
        5-array extent operand block (:meth:`_ext_operands`) after the
        canonical step arguments and threads it into every forward — the
        paged kernels walk KV blocks across each row's extent chain, and
        writes redirect through ``wslot``/``ext_base`` into the write
        extent's pool row. Which rows chain, how many extents each holds,
        and any lossy windows are RUNTIME data: one extent program per
        (sampling, collect, chunk, ksteps) point, O(1) in the length/extent
        mix. ``seqp=True`` additionally shards the first wide forward's
        span attention over the ``seq`` mesh axis (sequence-parallel
        chunked prefill; substeps stay unsharded — their single-column
        width can't split). Both variants force the per-projection path
        (the fused decode blocks carry no extent walk)."""
        fused_block = self._fused_block and not lora and not ext and not seqp
        tag = ("fused_seqp" if seqp else "fused_ext" if ext
               else "fused_block" if fused_block else "fused")
        key = (tag, sampling, collect, chunk, ksteps) + (("lora", ) if lora else ())
        split = self._splits_chunk(key)

        def build():
            model = self.engine.module
            K = ksteps
            V = model.cfg.vocab_size
            tp = self._shard_deg
            stats = self._moe_stats
            offload = self.experts is not None
            state_pool = self._state_pool
            choice = collect and self._moe and not fused_block

            sample = _sampler(sampling)

            def fused(params, pool, ids, lengths, spans, seeds, steps, flags,
                      temps, topks, topps, *extra):
                # trailing args in fixed order: the extent operand block
                # (when the `ext`/`seqp` key flag is set), then adapter
                # operands (`lora` flag), then cold-expert operands (when
                # the scheduler carries an expert store — fixed per build)
                i = 0
                ext_ops = None
                if ext or seqp:
                    ext_ops = tuple(extra[:5])
                    i = 5
                # a pool with state leaves: the rows' substep spans
                # (_substep_spans), the last of the canonical operands
                sub_spans = None
                if state_pool:
                    sub_spans = extra[i]
                    i += 1
                lops = None
                if lora:
                    from ..adapters.batched_lora import gather_rows
                    lops = gather_rows(extra[i])
                    i += 1
                eops = extra[i] if offload else None
                C = ids.shape[1]
                N = ids.shape[0]

                def forward(pool, tok_block, pos_block, widx, sp, seq_sh=False):
                    """One in-sync forward; returns (logits, pool, counts,
                    choice), counts None when stats are off and choice None
                    unless an MoE program collects (the plain trace is
                    unchanged from the pre-MoE program)."""
                    if fused_block:
                        # 3 resident kernels per layer; stats/lora/offload
                        # are structurally absent on this path (the gate
                        # excludes MoE, and lora variants stay unfused)
                        lg, pl = model.fused_paged_step(
                            params, tok_block, pool, pos_block, widx, sp)
                        return lg, pl, None, None
                    lg, pl, *rest = model.apply_with_cache(
                        params, tok_block, pool, 0, position_ids=pos_block,
                        write_index=widx, q_spans=sp, lora_ops=lops,
                        expert_ops=eops, expert_stats=stats, expert_choice=choice,
                        ext_ops=ext_ops, seq_shard=seq_sh)
                    cnt = self._moe_forward_stats(rest.pop(0)) if stats else None
                    return lg, pl, cnt, (rest.pop(0) if choice else None)

                if split:
                    l0, pool, total_cnt, choice0 = _first_forward_live_rows(
                        forward, pool, ids, lengths, spans)
                else:
                    # only the first (wide) forward seq-shards: the substeps'
                    # single-column blocks can't split over the seq axis
                    logits, pool, total_cnt, choice0 = forward(
                        pool, ids, lengths[:, None] + jnp.arange(C)[None, :], lengths,
                        spans, seq_sh=seqp)
                    # each row's LAST live column: decode rows column 0, the
                    # prefill row its chunk fill - 1 (dead rows clamp to 0 —
                    # their token is garbage the host never reads)
                    last_col = jnp.maximum(spans - 1, 0)
                    l0 = jnp.take_along_axis(logits, last_col[:, None, None], axis=1)[:, 0]
                l0 = _replicate_logits(l0.astype(jnp.float32), tp)
                tok0 = sample(l0, seeds, steps, flags, temps, topks, topps)
                out_toks = jnp.zeros((K, N), jnp.int32).at[0].set(tok0)
                out_logits = jnp.zeros((K, N, V) if collect else (), jnp.float32)
                if collect:
                    out_logits = out_logits.at[0].set(l0)
                # an MoE program that collects also returns what every row's
                # routers chose: the whole first block, then each substep
                out_choice = (jnp.zeros((K, ) + choice0.shape[:2] + choice0.shape[3:],
                                        jnp.int32) if choice else ())

                def result(pool, out_toks, out_logits, out_choice, total_cnt):
                    return ((pool, out_toks) + ((out_logits, ) if collect else ())
                            + ((choice0, out_choice) if choice else ())
                            + ((total_cnt, ) if stats else ()))

                if K == 1:
                    return result(pool, out_toks, out_logits, out_choice, total_cnt)
                base = lengths + jnp.maximum(spans, 1) - 1  # per-row write head - 1
                # substep spans: drop dead rows' writes (and, where slots hold
                # state, hold a prefill row still until its last chunk)
                live01 = jnp.minimum(spans, 1) if sub_spans is None else sub_spans

                def body(k, carry):
                    pool, tok, out_toks, out_logits, out_choice, total_cnt = carry
                    logits, pool, cnt, ch = forward(pool, tok[:, None],
                                                    (base + k)[:, None], base + k,
                                                    live01)
                    l2 = _replicate_logits(logits[:, 0].astype(jnp.float32), tp)
                    nxt = sample(l2, seeds, steps + k, flags, temps, topks, topps)
                    out_toks = jax.lax.dynamic_update_index_in_dim(out_toks, nxt, k, 0)
                    if collect:
                        out_logits = jax.lax.dynamic_update_index_in_dim(
                            out_logits, l2, k, 0)
                    if choice:
                        out_choice = jax.lax.dynamic_update_index_in_dim(
                            out_choice, ch[:, :, 0], k, 0)
                    if stats:
                        total_cnt = total_cnt + cnt
                    return pool, nxt, out_toks, out_logits, out_choice, total_cnt

                carry = jax.lax.fori_loop(
                    1, K, body, (pool, tok0, out_toks, out_logits, out_choice,
                                 total_cnt if stats else ()))
                return result(carry[0], *carry[2:])

            return self._jit_step(fused, (1 if collect else 0) + (2 if choice else 0)
                                  + (1 if self._moe_stats else 0) + 1, (1, ))

        return self._program(key, build)

    def _spec_fn(self, sampling, collect, width, lora=False):
        """The speculative VERIFY program: one forward over a fixed
        ``(num_slots, width)`` ids block through the span machinery (row
        ``i``'s live columns = its last token + its drafts, per-row
        ``q_spans``), then EVERY column sampled with its row's keys at the
        column's absolute step index. Returns the (width, num_slots) token
        block (+ (width, num_slots, V) logits when collected); the host
        walks acceptance. Which rows draft, how many columns each carries,
        and all sampling params are runtime data — compiled at most
        (greedy/sampling) x logits-collection variants for the ONE
        configured width, so the program count stays O(1) in k and in the
        acceptance mix. Column 0's math is the decode program's math (same
        span kernel, same sampling path, same key folding), which is what
        makes accepted streams bit-identical to non-speculative decode.
        ``lora=True`` is the multi-adapter variant (same contract as
        :meth:`_fused_fn`): drafts verify through each row's gathered
        adapter pages, so speculative acceptance stays bit-identical to
        that adapter's non-speculative stream. When the fused decode-block
        gate passes, verification routes through ``fused_paged_step``
        (key retagged ``spec_block``) — drafts verify through the SAME
        fused kernels that decode, keeping acceptance bit-identical to
        fused non-speculative decode."""
        fused_block = self._fused_block and not lora
        key = ("spec_block" if fused_block else "spec",
               sampling, collect, width) + (("lora", ) if lora else ())

        def build():
            model = self.engine.module
            tp = self._shard_deg
            stats = self._moe_stats
            offload = self.experts is not None

            sample = _sampler(sampling)

            def spec(params, pool, ids, lengths, spans, seeds, steps, flags,
                     temps, topks, topps, *extra):
                i = 0
                lops = None
                if lora:
                    from ..adapters.batched_lora import gather_rows
                    lops = gather_rows(extra[i])
                    i += 1
                eops = extra[i] if offload else None
                C = ids.shape[1]
                pos = lengths[:, None] + jnp.arange(C)[None, :]
                if fused_block:
                    logits, pool = model.fused_paged_step(
                        params, ids, pool, pos, lengths, spans)
                elif stats:
                    logits, pool, cnt = model.apply_with_cache(
                        params, ids, pool, 0, position_ids=pos,
                        write_index=lengths, q_spans=spans, lora_ops=lops,
                        expert_ops=eops, expert_stats=True)
                    cnt = self._moe_forward_stats(cnt)
                else:
                    logits, pool = model.apply_with_cache(
                        params, ids, pool, 0, position_ids=pos,
                        write_index=lengths, q_spans=spans, lora_ops=lops)
                l = _replicate_logits(logits.astype(jnp.float32), tp)  # (N, C, V)
                toks = jnp.stack([sample(l[:, j], seeds, steps + j, flags,
                                         temps, topks, topps) for j in range(C)])
                out = (pool, toks) + ((l.swapaxes(0, 1), ) if collect else ())
                return out + ((cnt, ) if stats else ())

            return self._jit_step(spec, (1 if collect else 0)
                                  + (1 if self._moe_stats else 0) + 1, (1, ))

        return self._program(key, build)

    def _copy_fn(self):
        """The ONE slot-to-slot cache copy program (radix prefix hit): src and
        dst are runtime scalars, so every donor/recipient pair shares it."""
        return self._program("copy", lambda: self._jit_step(
            lambda pool, src, dst: copy_slot(pool, src, dst), 0, (0, )))

    # ------------------------------------------------------------------ introspection
    def compiled_program_count(self):
        """Number of distinct XLA programs this scheduler has built — the
        compile-count regression guard reads this (and the jax.monitoring
        compile events agree)."""
        return len(self._compiled)
