"""Per-scheduler KV tier client: demotion and restoration between the
device slot pool and the fleet-global host prefix store.

One :class:`KVTier` hangs off each
:class:`~deepspeed_tpu.inference.scheduler.DecodeScheduler` whose config
enables the hierarchical KV tier. It owns the device↔host transfer
mechanics and rides the shared streaming layer
(:class:`~deepspeed_tpu.memory.streams.LayerStreamExecutor`):

- **demote** (radix eviction → host): ONE compiled slot-slice program copies
  the victim slot's rows out of the pool (fixed shape — the full slot; the
  prefix slice happens host-side so the program count stays O(1) in prefix
  length), then the device→host fetch + store registration runs through the
  executor's BOUNDED async fetch window, so admissions that evict don't
  stall on the copy-out — backpressure only past ``fetch_window`` in-flight
  demotes.
- **restore** (host store → a fresh slot, ahead of chunked prefill): the
  entry's rows land in persistent full-slot staging buffers (rows past the
  prefix are stale staging garbage — masked exactly like a device donor's
  rows past the matched prefix), ride ONE fenced ``device_put`` through the
  executor's put path, and ONE compiled slot-write program
  (:func:`~deepspeed_tpu.inference.kv_cache.slot_update`) installs them at
  the admitted slot. The restored rows are the bit-identical bytes the
  demote fetched, so restored == device-hit == cold decode (the suffix
  chunk-prefills on the same chunk boundaries either way).

All dtype tiers ride through generically — the pool's flat leaf list is
sliced/padded on the row axis (``ndim - 2``), which holds for plain bf16/
fp32 pools and the int8 pool (K/V leaves, split or packed, and per-token-row
scales) alike.

Compiled-program budget: exactly two programs (``tier_slice``,
``tier_restore``), warmed on the first demote/restore; every cycle after
warmup adds ZERO XLA programs (guarded by
``tests/unit/memory/test_kv_tier.py``).
"""

import numpy as np

import jax

from .streams import LayerStreamExecutor


class KVTier:
    """Demote/restore client binding one scheduler to a shared
    :class:`~deepspeed_tpu.memory.prefix_store.GlobalPrefixStore`.

    ``min_restore_tokens``: the restore-vs-recompute threshold — a host
    match shorter than this (after chunk rounding) chunk-prefills cold
    instead of paying the host→device copy (restores shorter than one
    ``prefill_chunk`` are structurally impossible: the match rounds down to
    chunk multiples)."""

    def __init__(self, scheduler, store, min_restore_tokens=0, fetch_window=2):
        self.sched = scheduler
        self.kv = scheduler.cache
        self.store = store
        self.min_restore_tokens = max(0, int(min_restore_tokens))
        # depth 0: restore puts are point-of-use FENCED (the persistent
        # staging buffers may be rewritten by the next restore the moment
        # take() returns); the async half of the tier is the demote fetch
        # window below
        self.executor = LayerStreamExecutor(self._dispatch_restore, None,
                                            prefetch_depth=0,
                                            fetch_window=fetch_window)
        self._stage = None      # persistent full-slot host staging leaves
        self._pending = None    # (leaves, treedef) staged for the in-flight put
        self.demotes = 0
        self.restores = 0
        self.restored_tokens = 0

    # ------------------------------------------------------------------ demote
    def demote(self, slot, tokens, namespace=()):
        """Copy ``slot``'s registered prefix KV out of the pool and register
        it in the store (called by ``RadixPrefixCache.evict_lru`` BEFORE the
        registration is removed). The slice program dispatches synchronously
        — its output owns fresh buffers, so later pool donations can't
        corrupt it — and the device→host fetch + store put ride the bounded
        async fetch window.

        ``namespace``: key prefix scoping the entry (multi-LoRA serving
        passes the adapter uid's negative-sentinel namespace from
        ``PagedAdapterStore.namespace``) — sentinels can never equal a real
        token, so adapter-scoped and base entries share one store but can
        never cross-match; the entry's host ROWS cover ``tokens`` only."""
        m = len(tokens)
        if m < max(self.sched.prefill_chunk, self.min_restore_tokens):
            # below the restore threshold it could never be restored (the
            # match rounds to chunk multiples and honors min_restore_tokens)
            # — demoting it would only waste host RAM
            return
        version = int(self.kv.weights_version)
        with self.sched.engine.mesh:
            dev = self._slice_fn()(self.kv.pool, np.int32(slot))
        flat = jax.tree_util.tree_leaves(dev)
        key = tuple(int(t) for t in namespace) + tuple(int(t) for t in tokens)
        ex = self.executor

        def fetch():
            with ex.timed_fetch():
                host = [np.asarray(jax.device_get(leaf)) for leaf in flat]
            rows = [np.ascontiguousarray(x[(Ellipsis, slice(0, m), slice(None))])
                    for x in host]
            self.store.put(key, rows, version, origin=id(self))
            self.demotes += 1
            tel = self.sched.telemetry
            if tel.enabled:
                tel.counter("serving/prefix_cache_demote")
        ex.submit_fetch(fetch)

    # ------------------------------------------------------------------ probe
    def probe(self, tokens, drain=True, namespace=()):
        """Longest host-tier prefix of ``tokens`` under the scheduler's
        weights version (scoped to ``namespace`` — the adapter axis):
        ``(matched_len, entry)`` or ``(0, None)``; ``matched_len`` counts
        TOKENS (the namespace sentinels are excluded, and a match that dies
        inside the namespace is a miss). With ``drain``, a MISS joins
        in-flight demotes and re-probes — a prefix demoted moments ago must
        be probe-visible — but a hit skips the join, so admissions don't
        stall on unrelated copy-outs (the bounded-async demote window's
        whole point). Submit-time look-ahead passes drain=False —
        advisory only."""
        ns = tuple(int(t) for t in namespace)
        key = ns + tuple(int(t) for t in tokens)
        m, entry = self.store.probe(key, self.kv.weights_version)
        if drain and entry is None and self.executor._fetches:
            self.executor.drain_fetches()
            m, entry = self.store.probe(key, self.kv.weights_version)
        if entry is None or m <= len(ns):
            return 0, None
        return m - len(ns), entry

    def prefetch(self, tokens, namespace=()):
        """Submit-time look-ahead: when the prompt's best host match is
        NVMe-spilled, start its disk read now so it overlaps the request's
        queue wait (the restore joins it)."""
        m, entry = self.probe(tokens, drain=False, namespace=namespace)
        if entry is not None and entry.spill_path is not None:
            self.store.prefetch(entry)
        return m, entry

    # ------------------------------------------------------------------ restore
    def restore(self, entry, slot, matched, prompt_len):
        """Install ``entry``'s rows at ``slot`` (rows ``[0, matched)``;
        ``matched`` is already chunk-rounded by the scheduler). The entry is
        CONSUMED (one-tier-per-key move) unless it is strictly longer than
        the restoring prompt — then its cached tail outlives this partial
        restore (a 64-token turn must not destroy the 512-token
        conversation prefix it branched from), and its key can never
        collide with the prompt's own device re-registration. Returns
        False when a concurrent restore claimed the entry first (the caller
        falls back to cold prefill)."""
        leaves = self.store.pop(
            entry, consume=self._token_len(entry) <= int(prompt_len))
        if leaves is None:
            return False
        self._install(leaves, slot, matched)
        self.restores += 1
        self.restored_tokens += int(matched)
        return True

    def _install(self, leaves, slot, rows):
        """Stage ``leaves``' first ``rows`` rows and write them into
        ``slot`` (ONE fenced put + the ONE compiled ``tier_restore``
        program) — the mechanics shared by prefix restore and the
        whole-request migration handoff. Pure transfer: no counters."""
        pool_leaves, treedef = jax.tree_util.tree_flatten(self.kv.pool)
        if self._stage is None:
            # zeros, not empty: rows past the restored prefix are masked on
            # device exactly like a donor's garbage rows, but they must be
            # FINITE bit patterns (uninitialized bf16 bytes can be NaN)
            self._stage = [np.zeros(s.shape[:s.ndim - 4] + (1,) + s.shape[s.ndim - 3:],
                                    np.dtype(s.dtype)) for s in pool_leaves]
        # rows written by a pool of another geometry (split K and V leaves
        # against this pool's packed ones, another head count or dtype) are
        # refused, never reinterpreted: everything but the row axis matches
        off_rows = lambda x: (x.shape[:x.ndim - 2], x.shape[x.ndim - 1:], np.dtype(x.dtype))
        if (len(leaves) != len(self._stage)
                or any(off_rows(src) != off_rows(buf) for buf, src in zip(self._stage, leaves))):
            raise ValueError(
                f"KV rows of {len(leaves)} leaves {[tuple(x.shape) for x in leaves[:2]]}... do "
                f"not have this pool's geometry ({len(self._stage)} leaves "
                f"{[tuple(b.shape) for b in self._stage[:2]]}...): written by a pool of "
                f"another layout")
        for buf, src in zip(self._stage, leaves):
            n = min(rows, src.shape[src.ndim - 2])
            buf[(Ellipsis, slice(0, n), slice(None))] = \
                src[(Ellipsis, slice(0, n), slice(None))]
        self._pending = (self._stage, treedef)
        dev = self.executor.take("restore")  # depth 0: fenced point-of-use put
        self._pending = None
        self.kv.pool = self._restore_fn()(self.kv.pool, dev, np.int32(slot))

    # ------------------------------------------------------------------ migration
    # Disaggregated prefill/decode (serving/replica.py): the prefill->decode
    # handoff rides the SAME two compiled programs and the same store as the
    # prefix tier, at whole-request granularity — the entry's rows cover the
    # request's full KV (prompt + the tokens its final fused sync decoded),
    # its key is a synthetic negative-sentinel tuple (adapter namespace
    # first, so adapter invalidation reclaims parked handoffs too), and it
    # is pinned host-resident until the decode side claims it.
    def demote_request(self, slot, rows, key, on_ready):
        """Copy ``slot``'s first ``rows`` KV rows out of the pool and park
        them in the store under ``key`` for a decode replica to claim. The
        slice program dispatches synchronously (its output owns fresh
        buffers — the slot can be released/reused immediately); the
        device->host fetch + store put ride the bounded async window, and
        ``on_ready(entry_or_None)`` fires from the transfer thread once the
        entry is probe-visible (None: the fetch failed — the caller fails
        the request instead of parking it forever)."""
        version = int(self.kv.weights_version)
        with self.sched.engine.mesh:
            dev = self._slice_fn()(self.kv.pool, np.int32(slot))
        flat = jax.tree_util.tree_leaves(dev)
        ex = self.executor

        def fetch():
            try:
                with ex.timed_fetch():
                    host = [np.asarray(jax.device_get(leaf)) for leaf in flat]
                rows_h = [np.ascontiguousarray(
                    x[(Ellipsis, slice(0, rows), slice(None))]) for x in host]
                entry = self.store.put(key, rows_h, version, origin=id(self),
                                       pinned=True, length=rows)
            except Exception:  # noqa: BLE001 — surface as a failed handoff
                # on_ready(None) already fails THIS request; re-raising
                # would poison the shared fetch window and resurface at an
                # unrelated drain point (sicking a healthy admission path
                # for an error that was already handled)
                from ..utils.logging import logger
                logger.warning("KV handoff demote fetch failed", exc_info=True)
                on_ready(None)
                return
            on_ready(entry)
        ex.submit_fetch(fetch)

    def restore_request(self, entry, slot, rows):
        """Install a migrated request's ``entry`` at ``slot`` (rows
        ``[0, rows)``) and consume it — the decode half of the handoff.
        Returns False when the entry was already claimed/dropped (adapter
        invalidation or a weight swap beat the restore; the caller fails
        the request rather than decoding on vanished KV)."""
        leaves = self.store.pop(entry, consume=True)
        if leaves is None:
            return False
        self._install(leaves, slot, rows)
        return True

    # ------------------------------------------------------------------ extent paging
    # Long-context cold-range demotion (``DecodeScheduler.demote_cold_extents``):
    # a live multi-extent request pages whole EXTENTS — pool rows, not
    # prefixes — to the host store mid-decode and restores them on the
    # detect-miss path. Rides the SAME two compiled programs and the same
    # pinned-entry protocol as the migration handoff, but synchronous both
    # ways: the scheduler parks the row until every extent is resident
    # again, so there is no async window worth hiding the copy in.
    def demote_extent(self, pool_slot, key):
        """Copy pool row ``pool_slot``'s full extent to the store under the
        scheduler's synthetic ``key`` (a negative-sentinel tuple no prompt
        or adapter namespace can collide with) and return the PINNED entry
        — the scheduler holds it for the restore; probes can never find
        it."""
        version = int(self.kv.weights_version)
        with self.sched.engine.mesh:
            dev = self._slice_fn()(self.kv.pool, np.int32(pool_slot))
        host = [np.asarray(jax.device_get(leaf))
                for leaf in jax.tree_util.tree_leaves(dev)]
        self.demotes += 1
        return self.store.put(key, host, version, origin=id(self),
                              pinned=True, length=self.kv.max_len)

    def restore_extent(self, entry, pool_slot):
        """Install a demoted extent's rows back at ``pool_slot`` and consume
        the entry. False when the entry vanished — structurally impossible
        while the owning request is live (weight swaps require an empty
        pool), so the scheduler treats False as an invariant failure."""
        leaves = self.store.pop(entry, consume=True)
        if leaves is None:
            return False
        self._install(leaves, pool_slot, self.kv.max_len)
        self.restores += 1
        return True

    def warmup(self):
        """Compile ``tier_slice``/``tier_restore`` ahead of the first real
        demote/restore by round-tripping slot 0's rows onto themselves (a
        byte-identical self-copy — safe even mid-decode). Disaggregated
        fleets call this at build so the first migration adds ZERO XLA
        programs and never trips the gateway's post-warmup recompile
        watch."""
        with self.sched.engine.mesh:
            dev = self._slice_fn()(self.kv.pool, np.int32(0))
        host = [np.asarray(jax.device_get(leaf))
                for leaf in jax.tree_util.tree_leaves(dev)]
        with self.sched.engine.mesh:
            self._install(host, 0, self.kv.max_len)

    @staticmethod
    def _token_len(entry):
        """Entry length in TOKENS: namespace sentinels (negative ints — the
        adapter axis) never count against the restoring prompt."""
        ns = 0
        while ns < len(entry.key) and entry.key[ns] < 0:
            ns += 1
        return entry.length - ns

    def _dispatch_restore(self, name):
        leaves, treedef = self._pending
        return jax.device_put(jax.tree_util.tree_unflatten(treedef, leaves))

    # ------------------------------------------------------------------ programs
    def _slice_fn(self):
        """ONE compiled slot→(B=1)-tree copy-out program (src slot is a
        runtime scalar; the pool is NOT donated — the scheduler keeps it)."""
        from ..inference.kv_cache import slot_slice
        return self.sched._program(
            "tier_slice",
            lambda: self.sched._jit_step(lambda pool, s: slot_slice(pool, s), 0, ()))

    def _restore_fn(self):
        """ONE compiled (B=1)-tree→slot write program (dst slot runtime;
        pool donated — the write replaces it in place)."""
        from ..inference.kv_cache import slot_update
        return self.sched._program(
            "tier_restore",
            lambda: self.sched._jit_step(
                lambda pool, tree, s: slot_update(pool, s, tree), 0, (0, )))

    def discard_exact(self, tokens, namespace=()):
        """Drop this scheduler's own host entry for an exact key about to be
        device-registered (a cold or device-hit prefill superseded it) —
        restore normally consumes the entry, but a match that rounded below
        a chunk or a device donor at least as long leaves it behind, and
        holding both copies would break the one-tier-per-key invariant."""
        self.executor.drain_fetches()
        self.store.discard(tuple(int(t) for t in namespace)
                           + tuple(int(t) for t in tokens), origin=id(self))

    # ------------------------------------------------------------------ invariants
    def invalidate(self):
        """Weight-swap path (called through
        ``RadixPrefixCache.invalidate_all`` before the pool version bumps):
        join in-flight demotes, then drop every store entry of the outgoing
        version. Returns prefix tokens dropped from the host tier."""
        self.executor.drain_fetches()
        self.executor.invalidate()
        return self.store.drop_version(self.kv.weights_version)

    def check_invariants(self, radix):
        """Tier half of ``RadixPrefixCache.check_invariants``: no prefix may
        be simultaneously device-registered in ``radix`` and host-demoted BY
        THIS SCHEDULER under the same key (cross-replica duplication is
        legal — another replica may hold its own device copy)."""
        self.executor.drain_fetches()
        for slot in radix.registered_slots():
            tokens = radix.registered_tokens(slot)
            ns = radix.adapter_ns(radix.registered_adapter(slot))
            key = tuple(int(t) for t in ns) + tuple(int(t) for t in tokens)
            if self.store.contains_exact(key, origin=id(self)):
                raise AssertionError(
                    f"prefix of slot {slot} is device-registered AND host-"
                    f"demoted by the same scheduler (key length {len(tokens)})")

    def hit_rate(self, radix):
        """Combined tier hit rate: (device hits + host restores) over all
        admissions that probed (the ``serving/kv_tier_hit_rate`` gauge)."""
        total = radix.hits + radix.misses + self.restores
        return (radix.hits + self.restores) / total if total else 0.0

    def stats(self):
        return {"demotes": self.demotes, "restores": self.restores,
                "restored_tokens": self.restored_tokens,
                "store": self.store.stats()}
