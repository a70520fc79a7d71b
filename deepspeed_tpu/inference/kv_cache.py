"""Slot-based paged KV cache for continuous-batching decode, plus the radix
prefix cache that reuses it across requests.

The Orca/vLLM lesson translated to XLA: instead of allocating a fresh
(B, S) cache per request shape (the static-batch engine path), serving keeps
ONE fixed-shape pool of ``num_slots`` cache slots,

    stacked layers:  (L, num_slots, kv_heads, max_len, head_dim) x2
    unrolled layers: per-layer tuples of (num_slots, kv_heads, max_len, head_dim)

plus a host-side row of per-slot positions. A request is admitted by
claiming a free slot, prefilling its prompt KV into rows ``[0, len)`` of
that slot, and then riding the shared one-token decode program; on finish
the slot returns to the free list and the next queued request overwrites it.
Because the pool shape never changes, XLA sees exactly one decode program
regardless of which requests are live — admission and eviction are pure
host-side bookkeeping plus a per-row write index.

"Paged" here is slot/block-granular rather than vLLM's 16-token pages: the
unit of allocation is a slot, but *attention work and DMA* scale with live
tokens, not pool capacity — the paged Pallas kernel
(``ops/pallas/decode_attention.paged_decode_attention``) walks KV blocks
only up to the longest live row, and per-slot ends mask the tail. Pages of
``page_size`` tokens are the accounting unit the occupancy gauges report.

Cross-request KV reuse (SGLang RadixAttention translated to the slot pool):
a finished request's slot is RETAINED instead of scrubbed — its prompt
prefix stays registered in a token trie (:class:`RadixPrefixCache`) and the
slot moves to the ``cached`` state. Admission walks the trie, copies the
longest matched prefix's KV rows from the donor slot into the new slot
(:func:`copy_slot` — one compiled program for any src/dst pair), and only
prefills the suffix. Cached slots are reclaimed LRU-first when the free
list runs dry. Reference counts (`refs`) track trie registrations per slot;
a slot is only reclaimable once the trie drops its last reference.

Weights versioning (RLHF hybrid engine, ``deepspeed_tpu/rlhf/``): KV rows
are only valid against the weights that computed them, so every slot is
stamped with the pool's ``weights_version`` at :meth:`SlotKVCache.alloc`
and every trie registration carries it too. A weight publication bumps the
version (``DecodeScheduler.swap_weights``), after which retaining a
stale-version slot or matching a stale registration is a hard error —
cross-version KV reuse is impossible STRUCTURALLY, not by convention.

Host-side state lives here; the compiled prefill/decode programs that read
and write the pool live in :mod:`deepspeed_tpu.inference.scheduler`.
"""

import numpy as np

import jax

# The kinds of leaf (``cache_spec``) that hold one entry a position and so grow
# with a slot's length: rows (positions at ``ndim - 2``) and columns (positions
# last). A ring's rows are positions mod its length and a state has none:
# per-slot bytes, both. Nothing here slices by position: a slot is sliced,
# updated and copied whole, by its slot axis.
PER_POSITION_KINDS = ("rows", "columns")


class SlotKVCache:
    """Fixed pool of KV cache slots + free-list allocation with three slot
    states:

    - ``free``   — no meaningful contents; on the free list.
    - ``active`` — owned by a live request (prefilling or decoding).
    - ``cached`` — released by its request but holding a retained prefix the
      radix cache still references (``refs[slot] > 0``); not allocatable
      until :meth:`reclaim` (radix eviction) returns it to the free list.
    - ``extent`` — a secondary row of a long-context extent chain
      (:meth:`alloc_chain`): its KV belongs to the chain's primary slot,
      which alone carries the request's logical length and owner.

    ``pool`` is the device-side cache tree (``model.init_cache(num_slots,
    max_len)``); it is REPLACED by the scheduler after every compiled step
    (functional update with donation, so the buffers alias in place).

    ``kinds``: the model's declaration of what each leaf holds
    (``model.cache_kinds()``, a tree of ``pool``'s structure): ``"rows"``
    (a row axis at ``ndim - 2``, one row a position) or ``"columns"`` (the
    position axis last, one column a position: a latent layer's leaf, which
    grows with a slot's length as rows do) or ``"state"`` (per
    slot, no row axis: a linear-attention or Mamba layer's recurrent state
    and convolution window) or ``"ring"`` (a windowed layer's K and V: a row
    axis at ``ndim - 2`` of a fixed number of rows whatever ``max_len`` is,
    position ``p`` in row ``p mod R``; per-slot bytes, as a state's). A
    layer may hold nothing (None in ``kinds`` and in ``pool``: no leaf).
    None: every leaf holds rows. A leaf's shape does not say which it is;
    the declaration does.
    """

    def __init__(self, pool, num_slots, max_len, page_size=256, max_extents=1, kinds=None):
        self.pool = pool
        self.leaf_kinds = (None if kinds is None else tuple(jax.tree_util.tree_leaves(kinds)))
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        # long-context extent chains: one request may span up to
        # ``max_extents`` pool slots; ``lengths[primary]`` then counts the
        # request's LOGICAL tokens (up to chain_len * max_len) while the
        # extra slots sit in the ``extent`` state, invisible to alloc/radix
        self.max_extents = int(max_extents)
        self.chain = {}  # primary slot -> [primary, ext1, ...]; -1 = demoted
        self.lengths = np.zeros(self.num_slots, np.int32)  # live tokens per slot
        self.state = ["free"] * self.num_slots
        self.refs = np.zeros(self.num_slots, np.int32)  # trie references
        self._free = list(range(self.num_slots - 1, -1, -1))  # pop() -> slot 0 first
        self._owner = [None] * self.num_slots  # request id per slot (debugging)
        self.total_allocs = 0
        self.total_frees = 0
        # weights versioning: rows are only meaningful against the weights
        # that computed them; slots are stamped at alloc and a bump
        # (weight publication) makes every pre-bump row untrustworthy
        self.weights_version = 0
        self.slot_version = np.zeros(self.num_slots, np.int64)

    # ------------------------------------------------------------------ alloc
    def alloc(self, owner=None):
        """Claim a free slot (lowest index first) or return None when no
        slot is on the free list (cached slots need a :meth:`reclaim`
        first). The slot's length row resets to 0; stale cache contents need
        no scrub — the prefill overwrites ``[0, len)`` and per-slot ends
        mask everything past the write head."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.lengths[slot] = 0
        self.state[slot] = "active"
        self._owner[slot] = owner
        self.slot_version[slot] = self.weights_version
        self.total_allocs += 1
        return slot

    def alloc_chain(self, n_ext, owner=None):
        """Claim ``n_ext`` pool slots as ONE logical extent chain for a
        long-context request: the first (primary) slot carries the request's
        bookkeeping — logical ``lengths`` row, owner, state ``active`` —
        and every extra slot enters the ``extent`` state, off the free list
        and invisible to radix reuse. Logical token position ``p`` lives in
        extent ``p // max_len`` at offset ``p % max_len``; the scheduler's
        per-request extent table hands the chain to the extent-walking
        Pallas kernels. Returns the primary slot, or None when the request
        exceeds ``max_extents`` or fewer than ``n_ext`` slots are free
        (all-or-nothing: a partial chain is never claimed)."""
        n_ext = int(n_ext)
        if n_ext <= 1:
            return self.alloc(owner)
        if n_ext > self.max_extents or len(self._free) < n_ext:
            return None
        primary = self.alloc(owner)
        members = [primary]
        for _ in range(n_ext - 1):
            s = self._free.pop()
            self.lengths[s] = 0
            self.state[s] = "extent"
            self._owner[s] = owner
            self.slot_version[s] = self.weights_version
            members.append(s)
        self.chain[primary] = members
        return primary

    def extents(self, slot):
        """Pool rows backing ``slot``'s logical KV, extent order (entry i
        holds logical tokens ``[i*max_len, (i+1)*max_len)``); -1 marks a
        host-demoted extent. Single-extent slots are their own chain."""
        return self.chain.get(slot, [slot])

    def extent_capacity(self, slot):
        """Logical token capacity of ``slot``'s chain (demoted extents
        still count — their logical range exists, just not on-device)."""
        return len(self.extents(slot)) * self.max_len

    def missing_extents(self, slot):
        """Indices of host-demoted extents in ``slot``'s chain — non-empty
        means the request cannot decode (losslessly) until
        :meth:`restore_extent` brings every index back."""
        return [i for i, s in enumerate(self.extents(slot)) if s < 0]

    def demote_extent(self, primary, idx):
        """Release the pool row behind chain extent ``idx`` of ``primary``
        (cold-range demotion: the KV bytes have been handed to the host
        tier, or — lossy sliding-window mode — masked out forever). The
        row returns to the free list for other admissions and the chain
        marks the extent -1. Extent 0 is pinned: it anchors the request's
        bookkeeping row AND holds the attention-sink tokens (StreamingLLM),
        so only ``idx >= 1`` demotes. Returns the freed pool row."""
        members = self.chain.get(primary)
        if members is None:
            raise ValueError(f"demote_extent on slot {primary} with no extent chain")
        if not 1 <= int(idx) < len(members):
            raise ValueError(f"extent index {idx} outside chain of {len(members)} "
                             f"(extent 0 is pinned)")
        s = members[int(idx)]
        if s < 0:
            raise ValueError(f"extent {idx} of slot {primary} already demoted")
        self.state[s] = "free"
        self._owner[s] = None
        self._free.append(s)
        members[int(idx)] = -1
        return s

    def restore_extent(self, primary, idx):
        """Re-claim a pool row for a demoted extent (detect-miss-and-restore
        paging: the scheduler noticed the next decode step needs the range
        and is about to land the host copy back). Returns the new pool row,
        or None when the free list is dry — the request stays PARKED and
        the scheduler retries after the next free."""
        members = self.chain.get(primary)
        if members is None:
            raise ValueError(f"restore_extent on slot {primary} with no extent chain")
        if not 1 <= int(idx) < len(members):
            raise ValueError(f"extent index {idx} outside chain of {len(members)}")
        if members[int(idx)] >= 0:
            raise ValueError(f"extent {idx} of slot {primary} is not demoted")
        if not self._free:
            return None
        s = self._free.pop()
        self.lengths[s] = 0
        self.state[s] = "extent"
        self._owner[s] = self._owner[primary]
        self.slot_version[s] = self.weights_version
        members[int(idx)] = s
        return s

    def free(self, slot):
        """Return an active ``slot`` to the pool (eviction at
        token-iteration granularity: the scheduler calls this the moment a
        sequence finishes, mid-decode-loop). Frees the slot's whole extent
        chain — demoted (-1) entries hold no pool row and are skipped."""
        if self.state[slot] != "active":
            raise ValueError(f"double free of slot {slot} (state {self.state[slot]})")
        members = self.chain.pop(slot, None)
        if members is not None:
            for s in members[1:]:
                if s < 0:
                    continue
                if self.state[s] != "extent":
                    raise ValueError(f"chain member {s} of slot {slot} in state "
                                     f"{self.state[s]} (extent bookkeeping drift)")
                self.lengths[s] = 0
                self.state[s] = "free"
                self._owner[s] = None
                self._free.append(s)
        self.lengths[slot] = 0
        self.state[slot] = "free"
        self._owner[slot] = None
        self._free.append(slot)
        self.total_frees += 1

    def retain(self, slot):
        """Release an active slot WITHOUT scrubbing: its prefix KV stays
        resident for radix reuse (state ``cached``). Counts as a free for
        the alloc/free ledger — the request released it — but the slot
        stays off the free list until :meth:`reclaim`."""
        if self.state[slot] != "active":
            raise ValueError(f"retain of non-active slot {slot} (state {self.state[slot]})")
        if slot in self.chain:
            raise ValueError(
                f"retain of multi-extent slot {slot}: spanned prefixes don't "
                f"register for radix reuse (free the chain instead)")
        if self.refs[slot] <= 0:
            raise ValueError(f"retain of slot {slot} with no trie reference")
        if self.slot_version[slot] != self.weights_version:
            raise ValueError(
                f"retain of slot {slot} stamped weights_version "
                f"{int(self.slot_version[slot])} under pool version "
                f"{self.weights_version}: KV computed under stale weights must "
                f"never be retained for reuse (swap_weights invalidates first)")
        self.state[slot] = "cached"
        self._owner[slot] = None
        self.total_frees += 1

    def reclaim(self, slot):
        """Cached -> free: the radix cache evicted the slot's last
        reference; its rows are garbage from here on."""
        if self.state[slot] != "cached":
            raise ValueError(f"reclaim of non-cached slot {slot} (state {self.state[slot]})")
        if self.refs[slot] != 0:
            raise ValueError(f"reclaim of slot {slot} still holding {self.refs[slot]} refs")
        self.lengths[slot] = 0
        self.state[slot] = "free"
        self._free.append(slot)

    def fits(self, prompt_len, max_new_tokens):
        """Would a request of this shape ever fit — spanning up to
        ``max_extents`` chained slots when one extent isn't enough?"""
        return prompt_len + max_new_tokens <= self.spannable_len

    @property
    def spannable_len(self):
        """Maximum logical tokens one request can hold across its longest
        permitted extent chain."""
        return self.max_len * self.max_extents

    def extents_needed(self, total_tokens):
        """Chain length a request of ``total_tokens`` logical tokens needs
        (ceil over the per-extent capacity; at least 1)."""
        return max(1, -(-int(total_tokens) // self.max_len))

    def adopt_rows(self, slot, length, version):
        """Account ``length`` externally-computed KV rows landing on an
        ACTIVE ``slot`` (the disaggregated prefill→decode handoff: a decode
        replica installs rows another replica's prefill wrote). The rows'
        ``version`` must match this pool's current weights version — the
        same structural rule that makes cross-version reuse impossible on
        the retain/insert paths applies to migration."""
        if self.state[slot] != "active":
            raise ValueError(f"adopt_rows on non-active slot {slot} "
                             f"(state {self.state[slot]})")
        if int(version) != self.weights_version:
            raise ValueError(
                f"adopt_rows of KV stamped weights_version {int(version)} onto "
                f"a pool at version {self.weights_version}: a migrated request "
                f"whose weights were swapped mid-handoff must fail, not decode "
                f"on stale rows")
        cap = self.extent_capacity(slot)
        if not 0 <= int(length) <= cap:
            raise ValueError(f"adopt_rows length {length} outside [0, {cap}]")
        self.lengths[slot] = int(length)
        self.slot_version[slot] = self.weights_version

    def bump_weights_version(self):
        """New weights published: every row computed so far is stale. The
        caller (``DecodeScheduler.swap_weights``) must have already emptied
        the active/cached states — a bump with retained rows would leave
        registrations whose version can never match again, which
        :meth:`check_invariants` treats as corruption."""
        for i, s in enumerate(self.state):
            if s != "free":
                raise ValueError(
                    f"bump_weights_version with slot {i} still {self.state[i]}: "
                    f"drain live requests and invalidate retained prefixes first")
        self.weights_version += 1
        return self.weights_version

    # ------------------------------------------------------------------ stats
    @property
    def active_slots(self):
        """Slots owned by LIVE requests (cached prefix slots don't count —
        they hold no in-flight sequence)."""
        return sum(1 for s in self.state if s == "active")

    @property
    def cached_slots(self):
        return sum(1 for s in self.state if s == "cached")

    @property
    def extent_slots(self):
        """Pool rows serving as secondary extents of long-context chains."""
        return sum(1 for s in self.state if s == "extent")

    @property
    def free_slots(self):
        return len(self._free)

    def occupancy(self):
        """Fraction of slots holding live sequences."""
        return self.active_slots / self.num_slots

    def _tokens(self, state):
        return int(sum(int(self.lengths[i]) for i in range(self.num_slots)
                       if self.state[i] == state))

    def live_tokens(self):
        """Total KV rows backing ACTIVE slots."""
        return self._tokens("active")

    def cached_tokens(self):
        """Total KV rows retained in cached prefix slots."""
        return self._tokens("cached")

    def _pages(self, state):
        p = self.page_size
        return int(sum((int(self.lengths[i]) + p - 1) // p
                       for i in range(self.num_slots) if self.state[i] == state))

    def live_pages(self):
        """Allocated pages (``page_size``-token blocks) backing active rows —
        the unit the paged decode kernel walks."""
        return self._pages("active")

    def cached_pages(self):
        """Pages backing retained (shared-prefix) rows."""
        return self._pages("cached")

    def token_utilization(self):
        """(live + retained) tokens / pool capacity: how much of the
        fixed-shape pool is doing useful work — decoding or standing by as a
        reusable prefix (the static-batch path's equivalent is live/(B*S)
        and decays with padding)."""
        return ((self.live_tokens() + self.cached_tokens())
                / float(self.num_slots * self.max_len))

    def max_live_len(self):
        return int(self.lengths.max()) if self.num_slots else 0

    def bytes_per_token(self):
        """HBM bytes backing ONE cache row (all layers, K+V, and — on the
        int8 tier — the per-token scale leaves): every leaf of rows or of
        columns keeps its slot axis and its position axis, wherever the
        latter is, so per-position bytes fall out of leaf sizes
        generically for the plain and quantized layouts, split, packed (the
        packed leaf holds the split pair's bytes) or latent (a position a
        column: the same bytes). State and ring leaves
        (:meth:`state_bytes_per_slot`, :meth:`window_bytes_per_slot`) do not
        count: they do not grow with a slot's length. 0 when the
        pool is host-bookkeeping-only (tests)."""
        denom = self.num_slots * self.max_len
        return int(sum((leaf.size // denom) * leaf.dtype.itemsize
                       for kind in PER_POSITION_KINDS for leaf in self._leaves(kind)))

    def _leaves(self, kind):
        """The pool's leaves the model declared as ``kind``."""
        if self.pool is None:
            return []
        leaves = jax.tree_util.tree_leaves(self.pool)
        if self.leaf_kinds is None:
            return leaves if kind == "rows" else []
        return [leaf for leaf, k in zip(leaves, self.leaf_kinds) if k == kind]

    def state_bytes_per_slot(self):
        """HBM bytes of per-slot STATE one slot holds (all layers' recurrent
        state and convolution window): what a slot costs whatever its
        length. 0 for a pool of rows only."""
        return int(sum((leaf.size // self.num_slots) * leaf.dtype.itemsize
                       for leaf in self._leaves("state")))

    def window_bytes_per_slot(self):
        """HBM bytes of RING rows one slot holds (all windowed layers' K and
        V): what a slot costs whatever its length, as a state does. 0 for a
        pool without windowed layers."""
        return int(sum((leaf.size // self.num_slots) * leaf.dtype.itemsize
                       for leaf in self._leaves("ring")))

    def capacity_bytes(self):
        """Total HBM held by the fixed-shape pool: rows, rings and state."""
        return (self.bytes_per_token() * self.max_len + self.state_bytes_per_slot()
                + self.window_bytes_per_slot()) * self.num_slots

    def live_bytes(self):
        """Bytes backing live + retained rows (the working set; the rest of
        ``capacity_bytes`` is preallocated headroom)."""
        return (self.live_tokens() + self.cached_tokens()) * self.bytes_per_token()

    def check_invariants(self):
        """Every slot is in exactly one state; the free list matches the
        state row; refs only on active/cached slots. Raises on drift (the
        eviction-storm tests call this after every operation)."""
        if sorted(self._free) != sorted(i for i, s in enumerate(self.state)
                                        if s == "free"):
            raise AssertionError(f"free list {sorted(self._free)} != free states")
        if len(set(self._free)) != len(self._free):
            raise AssertionError("duplicate slots on the free list")
        for i, s in enumerate(self.state):
            if s == "free" and (self.lengths[i] != 0 or self.refs[i] != 0):
                raise AssertionError(f"free slot {i} holds rows/refs")
            if s == "cached" and self.refs[i] <= 0:
                raise AssertionError(f"cached slot {i} holds no reference")
            if s == "cached" and self.slot_version[i] != self.weights_version:
                raise AssertionError(
                    f"cached slot {i} carries weights_version "
                    f"{int(self.slot_version[i])} != pool version "
                    f"{self.weights_version} (stale-weights KV retained)")
            if self.refs[i] < 0:
                raise AssertionError(f"negative refcount on slot {i}")
        chained = [s for m in self.chain.values() for s in m[1:] if s >= 0]
        if len(set(chained)) != len(chained):
            raise AssertionError("pool row appears in two extent chains")
        for primary, members in self.chain.items():
            if len(members) < 2 or len(members) > self.max_extents:
                raise AssertionError(f"chain of slot {primary} has bad length "
                                     f"{len(members)} (max_extents {self.max_extents})")
            if members[0] != primary:
                raise AssertionError(f"chain of slot {primary} doesn't lead with it")
            if self.state[primary] != "active":
                raise AssertionError(f"chain primary {primary} is "
                                     f"{self.state[primary]}, not active")
            if self.lengths[primary] > len(members) * self.max_len:
                raise AssertionError(f"slot {primary} logical length "
                                     f"{int(self.lengths[primary])} exceeds its "
                                     f"chain capacity")
            for s in members[1:]:
                if s < 0:
                    continue  # demoted: range lives on the host tier
                if self.state[s] != "extent":
                    raise AssertionError(f"chain member {s} of slot {primary} is "
                                         f"{self.state[s]}, not extent")
                if self.lengths[s] != 0 or self.refs[s] != 0:
                    raise AssertionError(f"extent row {s} holds its own "
                                         f"lengths/refs (belong to the primary)")
        for i, s in enumerate(self.state):
            if s == "extent" and i not in set(chained):
                raise AssertionError(f"extent-state row {i} belongs to no chain")
        if (self.active_slots + self.cached_slots + self.free_slots
                + self.extent_slots != self.num_slots):
            raise AssertionError("slot states don't partition the pool")


def slot_slice(pool, slot):
    """Pure function: one slot's cache as a (B=1)-batch cache tree, for the
    single-request prefill program. Works on both layouts — stacked leaves
    are (L, N, kv, S, lanes) (slot axis 1), per-layer leaves (N, kv, S,
    lanes) (slot axis 0) — and on every geometry of ``init_cache`` (split
    K and V leaves, the packed K/V leaf, the position-last latent leaf, the
    int8 tier's scale leaf, a linear-attention layer's state and window):
    the slot axis is at ``ndim - 4`` in all of them."""
    return jax.tree_util.tree_map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=c.ndim - 4), pool)


def slot_update(pool, slot, slot_cache):
    """Pure function: write a (B=1) slot cache back into the pool at
    ``slot`` (inverse of :func:`slot_slice`)."""
    return jax.tree_util.tree_map(
        lambda p, c: jax.lax.dynamic_update_slice_in_dim(p, c.astype(p.dtype), slot,
                                                         axis=p.ndim - 4),
        pool, slot_cache)


def copy_slot(pool, src, dst):
    """Pure function: duplicate slot ``src``'s cache rows into slot ``dst``
    (radix prefix hit: the donor's retained prefix seeds the new request's
    slot, so only the suffix needs prefilling). Copies the FULL slot — rows
    past the matched prefix are garbage either way (per-slot ends mask
    them until later writes land) and a full copy keeps this ONE compiled
    program for every (src, dst, match-length) combination."""
    return slot_update(pool, dst, slot_slice(pool, src))


class _RadixNode:
    __slots__ = ("edge", "children", "slots", "parent")

    def __init__(self, edge=(), parent=None):
        self.edge = edge        # token tuple on the edge INTO this node
        self.children = {}      # first token of child edge -> child node
        self.slots = set()      # slots whose retained prefix ends here
        self.parent = parent


class RadixPrefixCache:
    """Token trie (path-compressed radix tree) over retained prompt
    prefixes, SGLang-RadixAttention-style, mapped onto the slot pool:

    - :meth:`insert` registers a slot's full prompt once its prefill
      completes (live AND finished slots serve as donors — prefill rows are
      never rewritten during decode, so a mid-decode donor is stable).
    - :meth:`match` walks the longest shared prefix of a new prompt and
      returns ``(matched_len, donor_slot)``; the scheduler copies the
      donor's rows and chunk-prefills only the suffix.
    - :meth:`evict_lru` drops the least-recently-used CACHED slot's
      registration (active slots are pinned by their request) so the
      scheduler can :meth:`SlotKVCache.reclaim` it for admission.

    Each registration holds one reference in ``kv.refs``; eviction releases
    it. ``hits``/``misses``/``evictions`` feed the
    ``serving/prefix_cache_*`` telemetry.
    """

    def __init__(self, kv):
        self.kv = kv
        self.root = _RadixNode()
        # adapter axis (multi-tenant LoRA serving, deepspeed_tpu/adapters/):
        # every registration lives under its ADAPTER's root — base traffic
        # under `self.root` (key None), each adapter uid under its own —
        # so a prefix prefilled under adapter A is STRUCTURALLY unmatchable
        # for adapter B (or for base): match() only walks the requesting
        # adapter's subtree. There is no cross-adapter "wrong hit" to guard
        # against by convention; the trees are disjoint.
        self._roots = {None: self.root}   # adapter key (uid) -> root node
        self._slot_node = {}   # slot -> registration node
        self._slot_adapter = {}  # slot -> adapter key at registration
        self._slot_len = {}    # slot -> retained prefix length
        self._slot_version = {}  # slot -> weights_version at registration
        self._lru = {}         # slot -> last-use tick (monotonic)
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0  # whole-trie drops (weight swaps)
        self.adapter_invalidations = 0  # per-adapter drops (reload/evict)
        # hierarchical KV tier (deepspeed_tpu/memory/kv_tier.KVTier): when
        # attached, evicted registrations DEMOTE their prefix KV to the
        # fleet-global host store instead of being destroyed, and
        # invalidate_all drops the host tier too
        self.tier = None
        # adapter key -> host-store key namespace (set by the scheduler
        # when a PagedAdapterStore is attached); () keeps base prefixes on
        # their pre-adapter keys
        self.adapter_ns = lambda adapter: ()

    # ------------------------------------------------------------------ core
    def _touch(self, slot):
        self._tick += 1
        self._lru[slot] = self._tick

    @staticmethod
    def _common(edge, tokens, depth):
        n = min(len(edge), len(tokens) - depth)
        m = 0
        while m < n and edge[m] == tokens[depth + m]:
            m += 1
        return m

    def insert(self, slot, tokens, adapter=None):
        """Register ``slot`` as holding KV for the full ``tokens`` prefix
        under ``adapter``'s root (None = base). One registration per slot
        (re-registering raises: a slot must be evicted/freed before it can
        carry a different prefix). The registration is tagged with the
        pool's current ``weights_version`` — registering rows stamped under
        older weights raises, so a stale prefix can never ENTER the trie,
        let alone be served from it."""
        if slot in self._slot_node:
            raise ValueError(f"slot {slot} already registered in the prefix trie")
        if self.kv.slot_version[slot] != self.kv.weights_version:
            raise ValueError(
                f"slot {slot} holds KV stamped weights_version "
                f"{int(self.kv.slot_version[slot])} but the pool is at "
                f"{self.kv.weights_version}: stale-weights rows cannot register "
                f"as reusable prefixes")
        tokens = tuple(int(t) for t in tokens)
        root = self._roots.get(adapter)
        if root is None:
            root = self._roots[adapter] = _RadixNode()
        node, depth = root, 0
        while depth < len(tokens):
            child = node.children.get(tokens[depth])
            if child is None:
                new = _RadixNode(edge=tokens[depth:], parent=node)
                node.children[tokens[depth]] = new
                node, depth = new, len(tokens)
                break
            m = self._common(child.edge, tokens, depth)
            if m < len(child.edge):
                # split the edge at the divergence/exhaustion point
                mid = _RadixNode(edge=child.edge[:m], parent=node)
                node.children[tokens[depth]] = mid
                child.edge = child.edge[m:]
                child.parent = mid
                mid.children[child.edge[0]] = child
                node, depth = mid, depth + m
            else:
                node, depth = child, depth + m
        node.slots.add(slot)
        self._slot_node[slot] = node
        self._slot_adapter[slot] = adapter
        self._slot_len[slot] = len(tokens)
        self._slot_version[slot] = self.kv.weights_version
        self.kv.refs[slot] += 1
        self._touch(slot)

    def match(self, tokens, adapter=None):
        """Longest prefix of ``tokens`` registered under ``adapter``'s
        root: returns ``(matched_len, donor_slot)`` or ``(0, None)``. Any
        slot in the deepest matched node's subtree shares at least
        ``matched_len`` tokens with the prompt (most recently used wins).
        Registrations under OTHER adapters (or base) are invisible — the
        per-adapter roots make cross-adapter KV reuse structurally
        impossible, not merely checked."""
        root = self._roots.get(adapter)
        if root is None:
            return 0, None
        tokens = tuple(int(t) for t in tokens)
        node, depth = root, 0
        while depth < len(tokens):
            child = node.children.get(tokens[depth])
            if child is None:
                break
            m = self._common(child.edge, tokens, depth)
            depth += m
            node = child
            if m < len(child.edge):
                break  # partial edge: child's subtree still shares `depth`
        if depth == 0:
            return 0, None
        donor = self._best_slot(node)
        if donor is None:  # pruning keeps subtrees non-empty; belt&braces
            return 0, None
        return min(depth, self._slot_len[donor]), donor

    def _best_slot(self, node):
        """Most-recently-used slot registered in ``node``'s subtree whose
        registration matches the pool's current weights version (stale
        registrations only exist transiently between a version bump and
        :meth:`invalidate_all`; skipping them here is the belt to that
        braces)."""
        best, best_tick = None, -1
        stack = [node]
        while stack:
            n = stack.pop()
            for s in n.slots:
                if (self._slot_version.get(s) != self.kv.weights_version
                        or self.kv.slot_version[s] != self.kv.weights_version):
                    continue
                if self._lru.get(s, 0) > best_tick:
                    best, best_tick = s, self._lru.get(s, 0)
            stack.extend(n.children.values())
        return best

    def touch(self, slot):
        """LRU bump on a prefix hit."""
        if slot in self._slot_node:
            self._touch(slot)

    def remove(self, slot):
        """Drop ``slot``'s registration (and its trie reference), pruning
        now-empty branches up to its adapter's root (an emptied adapter
        root leaves the root table too — base keeps its permanent root)."""
        node = self._slot_node.pop(slot, None)
        if node is None:
            return False
        adapter = self._slot_adapter.pop(slot, None)
        root = self._roots.get(adapter, self.root)
        node.slots.discard(slot)
        del self._slot_len[slot]
        self._slot_version.pop(slot, None)
        self._lru.pop(slot, None)
        self.kv.refs[slot] -= 1
        # prune childless, slotless nodes up the path
        while node is not root and not node.slots and not node.children:
            parent = node.parent
            del parent.children[node.edge[0]]
            node = parent
        if adapter is not None and not root.slots and not root.children:
            self._roots.pop(adapter, None)
        return True

    def evict_lru(self, prefer_not=None):
        """Evict the least-recently-used CACHED registration and return its
        slot (caller reclaims it), or None when nothing is evictable
        (every registered slot still serves a live request).

        ``prefer_not``: a slot to spare when any other candidate exists —
        the scheduler passes the incoming prompt's matched donor so an
        eviction-for-admission doesn't destroy the very prefix it is about
        to copy (the donor falls only when it is the sole cached slot, in
        which case it becomes the admitted slot and its rows survive)."""
        candidates = [s for s in self._slot_node
                      if self.kv.state[s] == "cached"]
        if not candidates:
            return None
        spared = [s for s in candidates if s != prefer_not]
        victim = min(spared or candidates, key=lambda s: self._lru.get(s, 0))
        if self.tier is not None and len(self._slot_node[victim].slots) == 1:
            # hierarchical KV: the registration dies but its prefix rows
            # demote to the host tier BEFORE removal (the tier needs the
            # registered token key, reconstructed from the trie path).
            # Only the LAST device copy demotes: a sibling registration at
            # the same node holds the identical key (same prompt admitted
            # twice), so the bytes survive on device — demoting one copy
            # would put the key in BOTH tiers and break one-tier-per-key.
            # Adapter registrations demote under their uid NAMESPACE, so a
            # host restore can only ever serve the same (adapter, version)
            self.tier.demote(victim, self.registered_tokens(victim),
                             namespace=self.adapter_ns(self._slot_adapter.get(victim)))
        self.remove(victim)
        self.evictions += 1
        return victim

    def registered_tokens(self, slot):
        """The full token sequence ``slot`` registered (reconstructed from
        the trie path — edges concatenated root→registration node), or ()
        when unregistered. The demotion path keys host-tier entries on
        this, so the trie doubles as the token storage."""
        node = self._slot_node.get(slot)
        if node is None:
            return ()
        edges = []
        while node.parent is not None:  # every root (base or adapter) has parent None
            edges.append(node.edge)
            node = node.parent
        out = tuple(t for edge in reversed(edges) for t in edge)
        assert len(out) == self._slot_len[slot], (slot, len(out))
        return out

    def registered_adapter(self, slot):
        """Adapter key ``slot`` registered under (None = base / unregistered)."""
        return self._slot_adapter.get(slot)

    def invalidate_adapter(self, adapter):
        """Drop every registration under ``adapter``'s root and reclaim its
        cached slots — fired when the adapter's device page is evicted or a
        reload bumps its version (``PagedAdapterStore`` listeners): KV
        registered against a page that left the device (or changed bytes)
        must never seed a new request. LIVE slots lose their registration
        but keep decoding — their request pinned the old page, which stays
        resident until release; with no trie reference left the slot frees
        (instead of retaining) when it ends. Returns tokens dropped."""
        root = self._roots.get(adapter)
        if root is None:
            return 0
        dropped = 0
        for slot in [s for s, a in self._slot_adapter.items() if a == adapter]:
            dropped += int(self._slot_len.get(slot, 0))
            self.remove(slot)
            if self.kv.state[slot] == "cached" and self.kv.refs[slot] == 0:
                self.kv.reclaim(slot)
        self.adapter_invalidations += 1
        return dropped

    def registered_len(self, slot):
        """Token length of ``slot``'s registered prefix (0 if unregistered)
        — the rows still useful for reuse once the slot's request ends."""
        return self._slot_len.get(slot, 0)

    def invalidate_all(self):
        """Drop EVERY registration and reclaim every cached slot — the
        weight-swap path (``DecodeScheduler.swap_weights``): KV computed
        under the outgoing weights must never be served against the new
        ones. Registrations pinned by LIVE slots raise (the scheduler
        flushes in-flight work first). Returns the number of retained KV
        tokens invalidated (the ``rlhf/kv_invalidated_tokens`` telemetry)."""
        live = [s for s in self._slot_node if self.kv.state[s] == "active"]
        if live:
            raise ValueError(f"invalidate_all with live registered slots {live}: "
                             f"flush in-flight requests before swapping weights")
        dropped_tokens = 0
        for slot in list(self._slot_node):
            dropped_tokens += int(self.kv.lengths[slot])
            self.remove(slot)
            if self.kv.state[slot] == "cached":
                self.kv.reclaim(slot)
        if self.tier is not None:
            # the host tier holds KV computed under the SAME outgoing
            # weights — serving it post-swap is the stale-KV RLHF failure
            # mode, so the swap drops it with the device registrations
            dropped_tokens += self.tier.invalidate()
        self.invalidations += 1
        return dropped_tokens

    def check_invariants(self):
        """Pool invariants (:meth:`SlotKVCache.check_invariants`) plus the
        tiered-registration contract when a hierarchical KV tier is
        attached: a prefix must never be simultaneously device-registered
        here AND host-demoted by this same scheduler under one key (the
        demote/restore protocol moves a prefix between tiers, never copies
        it within one scheduler's view)."""
        self.kv.check_invariants()
        for slot in self._slot_node:
            if slot not in self._slot_len or slot not in self._slot_version:
                raise AssertionError(f"slot {slot} registration missing metadata")
            if slot not in self._slot_adapter:
                raise AssertionError(f"slot {slot} registration missing its "
                                     f"adapter key")
            adapter = self._slot_adapter[slot]
            if adapter is not None and adapter not in self._roots:
                raise AssertionError(f"slot {slot} registered under adapter "
                                     f"{adapter!r} whose root is gone")
            # the adapter axis is structural: the registration node must sit
            # in ITS adapter's tree (walk to the root and compare)
            node = self._slot_node[slot]
            while node.parent is not None:
                node = node.parent
            if node is not self._roots.get(adapter, self.root):
                raise AssertionError(f"slot {slot} registration reachable from "
                                     f"the wrong adapter root (cross-adapter "
                                     f"trie corruption)")
        if set(self._slot_adapter) != set(self._slot_node):
            raise AssertionError("adapter-key table out of sync with registrations")
        if self.tier is not None:
            self.tier.check_invariants(self)

    # ------------------------------------------------------------------ stats
    def hit_rate(self):
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def registered_slots(self):
        return sorted(self._slot_node)
