"""Paged KV cache: a global page pool + free list + per-slot page tables.

The paper's streaming taxonomy applied to KV memory management:

  * **Pages as Independent transfer tasks (§4.1)** — the cache of one
    request is no longer one contiguous ``max_seq`` region but a set of
    fixed-size pages drawn from a global pool.  Pages of different requests
    are mutually Independent: they can be allocated, scattered (prefill),
    written (decode), gathered (evict) and reclaimed in any order, so long
    and short requests share HBM instead of each reserving the worst case.
  * **The page table as the RAW handoff (§4.2)** — decode step t+1 reads
    exactly the pages that step t (and the prefill stream before it) wrote;
    the per-slot page table is the True-dependence carrier between those
    tasks, playing the role the chunked-prefill KV cache plays between
    prefill chunks.
  * **Prefix pages as the SYNC transfer (§4.1)** — data shared by *every*
    task that must be staged once before streaming begins is the paper's
    SYNC type; the serving analog is a common prompt prefix (a shared
    system prompt).  ``PrefixRegistry`` maps a page-aligned prefix token
    hash to its physical blocks, so N requests with the same prefix map the
    same pages into their tables at refcount+1 instead of prefilling and
    storing N copies: the SYNC data is staged once, and only the uncovered
    tail streams.  Blocks free on refcount-zero; a write to a shared block
    forks it first (copy-on-write), so a writer's divergence is invisible
    to the other sharers.
  * **Block size as the task-granularity knob** — ML-guided tuning of
    streamed codes (Zhang et al.) finds task/block granularity dominant;
    ``rmetric``'s R gate + ``optimal_streams`` size it here too (see
    ``serving.plan_decode_policy``).

Layout: each attention unit position owns a K and V pool of shape
``(r, num_blocks, block_size, n_kv_heads, head_dim)`` (``r`` = scan repeats,
i.e. the layers axis); a single page table ``(max_batch, max_pages)`` is
shared by every layer.  **Block 0 is the trash page**: free slots' page
tables point at it, so the batched decode step's padding rows scatter their
garbage K/V there and can never corrupt a live request's pages.

``BlockAllocator`` is the pure host-side refcounted free-list
(property-tested: no double allocation, no free while referenced, full
reclaim); ``PagedKVCache`` owns the device pools and the jitted page
scatter/gather/copy used by admission, evict/readmit and COW forks.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import pathlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.budget import tick_path, transfer_budget
from repro.kernels import quant
from repro.models import transformer as T
from repro.models.transformer import ModelConfig

#: On-disk prefix-store schema; bump when the npz layout changes.  A
#: mismatched file is ignored wholesale (cold start), never misread.
PREFIX_STORE_SCHEMA = 1

TRASH_PAGE = 0  # physical block 0: sink for padding writes, never allocated

# Per-shape jitted scatter/gather/load helpers are cached by page count; an
# unbounded dict would grow one compile per distinct prefix/evict size over a
# long-lived server, so the caches are small LRUs instead.
_JIT_CACHE_CAP = 16


def _lru_jit(cache: "collections.OrderedDict", key, make, *,
             cap: int = _JIT_CACHE_CAP):
    """Fetch-or-build a jitted helper in a small LRU compile cache."""
    fn = cache.get(key)
    if fn is None:
        fn = make()
        cache[key] = fn
        if len(cache) > cap:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fn


class PoolInvariantError(AssertionError):
    """A pool invariant does not hold; ``rule`` is the analyzer rule ID
    (POOL001 refcount conservation, POOL002 aliasing/table-ownership,
    POOL003 free-list corruption, POOL005 quant scale layout).  Raised by
    ``check_invariants`` and by the ``REPRO_SANITIZE=1`` runtime sanitizer
    (``analysis.poolcheck``) — one predicate set for both."""

    def __init__(self, rule: str, msg: str):
        super().__init__(f"{rule}: {msg}")
        self.rule = rule


class BlockAllocator:
    """Refcounted free-list allocator over physical blocks 1..num_blocks-1.

    All-or-nothing ``alloc``: either the full request is satisfied or no
    block moves, so callers never have to roll back partial grants.  Blocks
    come out of ``alloc`` at refcount 1; sharers take extra references with
    ``incref`` and every ``free`` drops one reference — the block returns to
    the free list only at refcount zero.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (block 0 is the trash page), got "
                f"{num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed (still cache-warm) pages go first.
        self._free = list(range(num_blocks - 1, 0, -1))
        self._ref: dict[int, int] = {}  # block -> live reference count

    @property
    def capacity(self) -> int:
        """Usable pages (excludes the trash page)."""
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Physical blocks held (shared blocks count once)."""
        return len(self._ref)

    @property
    def shared_count(self) -> int:
        """Physical blocks referenced by more than one holder."""
        return sum(1 for r in self._ref.values() if r > 1)

    @property
    def total_refs(self) -> int:
        """Logical references; ``total_refs - used_count`` copies avoided."""
        return sum(self._ref.values())

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` pages from the free list, or None if they don't fit."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, pages: list[int]) -> None:
        """Add one reference per page (sharing an allocated block)."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"cannot share unallocated page {p}")
        for p in pages:
            self._ref[p] += 1

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; a block is reclaimed only when its
        last reference goes (freeing a non-allocated page is a bug)."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"double free / foreign page {p}")
        for p in pages:
            if self._ref[p] == 1:
                del self._ref[p]
                self._free.append(p)
            else:
                self._ref[p] -= 1

    def check_invariants(self, holders=None, registry_use=None) -> None:
        """Raise :class:`PoolInvariantError` unless the allocator is sound.

        Structural checks (always): the free list holds each page once,
        never the trash page, never a live-referenced page, and together
        with the live refs accounts for every usable page (POOL003); every
        live refcount is >= 1 (POOL001).

        Conservation (when ``holders`` is given): ``holders`` is the
        per-slot owned-page lists and ``registry_use`` the prefix
        registry's distinct retained blocks (one retention ref each); each
        page's refcount must equal its occurrences across holders plus its
        registry retention — refcount sum == mapped pages + registry refs.
        """
        free = self._free
        if len(set(free)) != len(free):
            raise PoolInvariantError(
                "POOL003", f"duplicate pages on the free list: {free}")
        bad = [p for p in free if not 1 <= p < self.num_blocks]
        if bad:
            raise PoolInvariantError(
                "POOL003", f"out-of-range/trash pages on the free list: "
                f"{bad}")
        overlap = set(free) & self._ref.keys()
        if overlap:
            raise PoolInvariantError(
                "POOL003", f"pages both free and referenced: "
                f"{sorted(overlap)}")
        if TRASH_PAGE in self._ref:
            raise PoolInvariantError(
                "POOL003", "the trash page is refcounted (it is never "
                "allocated)")
        if len(free) + len(self._ref) != self.capacity:
            raise PoolInvariantError(
                "POOL003", f"{self.capacity - len(free) - len(self._ref)} "
                "pages leaked (neither free nor referenced)")
        low = {p: r for p, r in self._ref.items() if r < 1}
        if low:
            raise PoolInvariantError(
                "POOL001", f"non-positive refcounts: {low}")
        if holders is None:
            return
        expect = collections.Counter(p for h in holders for p in h)
        if registry_use is not None:
            expect.update(dict.fromkeys(registry_use, 1))
        for p in self._ref.keys() | expect.keys():
            if self._ref.get(p, 0) != expect.get(p, 0):
                raise PoolInvariantError(
                    "POOL001", f"page {p}: refcount {self._ref.get(p, 0)} "
                    f"!= {expect.get(p, 0)} holders (slot mappings + "
                    "registry retention)")
        if self.total_refs != sum(expect.values()):
            raise PoolInvariantError(
                "POOL001", f"refcount sum {self.total_refs} != "
                f"{sum(expect.values())} mapped pages + registry refs")


class PrefixRegistry:
    """Host-side LRU map: page-aligned prompt-prefix tokens -> block list.

    The lookup key is a digest of the raw token bytes (the stored bytes are
    compared on hit, so a digest collision can never alias two prefixes).
    Entries of one prompt nest (lengths 1..n pages share blocks), so the
    registry tracks per-block usage across entries and holds exactly **one**
    allocator reference per distinct block: ``put``/``pop_lru``/``clear``
    return the blocks whose registry-wide usage crossed zero, for the caller
    to ``incref``/``free`` — this keeps ``total_refs`` an honest count of
    copies avoided.
    """

    def __init__(self, max_entries: int = 1024):
        self.max_entries = max_entries
        # digest -> (token bytes, blocks)
        self._entries: collections.OrderedDict[
            bytes, tuple[bytes, list[int]]] = collections.OrderedDict()
        self._block_use: collections.Counter = collections.Counter()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks_held(self) -> int:
        """Distinct blocks the registry holds a retention reference on."""
        return len(self._block_use)

    def entries(self) -> list[tuple[bytes, list[int]]]:
        """(token bytes, blocks) per entry, LRU-oldest first — the
        persistence view (``PagedKVCache.save_prefixes``)."""
        return [(tb, list(blocks)) for tb, blocks in self._entries.values()]

    @staticmethod
    def _digest(token_bytes: bytes) -> bytes:
        return hashlib.sha1(token_bytes).digest()

    def _retain(self, blocks: list[int]) -> list[int]:
        """Track entry blocks; returns those newly referenced (0 -> 1)."""
        fresh = [b for b in blocks if self._block_use[b] == 0]
        self._block_use.update(blocks)
        return fresh

    def _release(self, blocks: list[int]) -> list[int]:
        """Untrack entry blocks; returns those no longer referenced."""
        gone = []
        for b in blocks:
            self._block_use[b] -= 1
            if self._block_use[b] == 0:
                del self._block_use[b]
                gone.append(b)
        return gone

    def get(
        self, tokens: np.ndarray, *, count: bool = True
    ) -> list[int] | None:
        """Exact-length probe.  ``count=False`` leaves the hit/miss counters
        alone: a longest-match descent (``PagedKVCache.lookup_prefix``)
        probes many lengths for *one* logical lookup and records the single
        outcome itself via ``record_lookup`` — counting every failed probe
        as a miss would drown the hit rate in descent noise."""
        tb = np.ascontiguousarray(tokens).tobytes()
        d = self._digest(tb)
        entry = self._entries.get(d)
        if entry is None or entry[0] != tb:
            if count:
                self.misses += 1
            return None
        self._entries.move_to_end(d)
        if count:
            self.hits += 1
        return list(entry[1])

    def record_lookup(self, hit: bool) -> None:
        """Count one logical (admission-level) lookup outcome."""
        if hit:
            self.hits += 1
        else:
            self.misses += 1

    def put(
        self, tokens: np.ndarray, blocks: list[int]
    ) -> tuple[list[int], list[int]]:
        """Insert.  Returns (blocks to incref, blocks to free) — the
        registry-wide reference transitions this insert caused (including
        any LRU overflow / digest-collision drops)."""
        tb = np.ascontiguousarray(tokens).tobytes()
        d = self._digest(tb)
        released: list[int] = []
        if d in self._entries:
            if self._entries[d][0] == tb:
                self._entries.move_to_end(d)
                return [], []
            released += self._release(self._entries.pop(d)[1])  # collision
        self._entries[d] = (tb, list(blocks))
        retained = self._retain(blocks)
        while len(self._entries) > self.max_entries:
            released += self._release(self._entries.popitem(last=False)[1][1])
        return retained, released

    def pop_lru(self) -> list[int] | None:
        """Drop the least-recently-used entry; returns the blocks it was
        the last entry to reference (None if the registry is empty)."""
        if not self._entries:
            return None
        return self._release(self._entries.popitem(last=False)[1][1])

    def clear(self) -> list[int]:
        """Drop everything; returns all registry-referenced blocks."""
        out = list(self._block_use)
        self._entries.clear()
        self._block_use.clear()
        return out

    def drop_stranded(
        self, align_tokens: int, *, itemsize: int = 4
    ) -> list[int]:
        """Drop entries whose token length is not a multiple of
        ``align_tokens`` — stranded when the prefill chunk changes (e.g.
        autotune): the chunk-grid-aligned lookup can never probe their
        lengths again, so they'd only pin pages until pool pressure
        reclaimed them.  Returns the blocks no surviving entry references
        (for the caller to free)."""
        if align_tokens < 1:
            raise ValueError(
                f"align_tokens must be >= 1, got {align_tokens}")
        stranded = [d for d, (tb, _) in self._entries.items()
                    if (len(tb) // itemsize) % align_tokens]
        released: list[int] = []
        for d in stranded:
            released += self._release(self._entries.pop(d)[1])
        return released


def _config_digest(cfg: Any) -> str:
    """Stable hash over every ModelConfig field (dtypes by canonical name).

    The prefix store's staleness key: saved page contents are only valid
    for the exact model geometry/dtype they were computed under.  (Same
    recipe as ``tuning.db._config_digest``; duplicated because the runtime
    never imports the tuner.)
    """

    def norm(v):
        if isinstance(v, (list, tuple)):
            return [norm(x) for x in v]
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            return {k: norm(x)
                    for k, x in sorted(dataclasses.asdict(v).items())}
        try:
            return np.dtype(v).name
        except TypeError:
            return v

    fields = {f.name: norm(getattr(cfg, f.name))
              for f in dataclasses.fields(cfg)}
    blob = json.dumps(fields, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()


class StateStore:
    """Host-side LRU map: chunk-aligned prompt prefix -> recurrent-state
    snapshot (the ``MambaServable`` analog of the prefix registry).

    Attention prefixes share *pages* — position-granular KV rows that any
    aligned proper prefix of them can reuse.  A recurrent SSM compresses
    the whole prefix into O(1) state, so the only shareable artifact is a
    *snapshot* of that state at a known token boundary: an admission whose
    prompt extends a stored prefix restores the snapshot and streams only
    the uncovered tail (prefix sharing "degrades to snapshot reuse at
    aligned boundaries").  Snapshots are host copies — device pools never
    hold them — and boundaries are restricted to multiples of the prefill
    chunk so a resumed prefill dispatches the exact chunk tasks a full
    prefill would (bitwise token parity, same argument as the page path).
    """

    def __init__(self, max_entries: int = 32):
        if max_entries < 1:
            raise ValueError(
                f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        # digest -> (token bytes, n_tokens, host state pytree)
        self._entries: collections.OrderedDict[
            bytes, tuple[bytes, int, Any]] = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def put(self, tokens: np.ndarray, snapshot: Any) -> None:
        """Store a host snapshot for ``tokens`` (LRU-bounded; an existing
        entry for the same tokens is refreshed in place)."""
        tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        tb = tokens.tobytes()
        d = hashlib.sha1(tb).digest()
        self._entries[d] = (tb, int(tokens.size), snapshot)
        self._entries.move_to_end(d)
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def lookup(
        self, tokens: np.ndarray, *, align_tokens: int,
    ) -> tuple[int, Any]:
        """Longest stored chunk-aligned *proper* prefix of ``tokens``.

        Returns (n_tokens, snapshot); (0, None) on miss.  Stored bytes are
        compared on hit, so a digest collision can never alias prefixes.
        The whole descent counts as one logical lookup.
        """
        if align_tokens < 1:
            raise ValueError(
                f"align_tokens must be >= 1, got {align_tokens}")
        tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        top = ((tokens.size - 1) // align_tokens) * align_tokens
        for n in range(top, 0, -align_tokens):
            tb = tokens[:n].tobytes()
            entry = self._entries.get(hashlib.sha1(tb).digest())
            if entry is not None and entry[0] == tb:
                self._entries.move_to_end(hashlib.sha1(tb).digest())
                self.hits += 1
                return entry[1], entry[2]
        self.misses += 1
        return 0, None

    def clear(self) -> None:
        self._entries.clear()


@dataclasses.dataclass(frozen=True)
class PoolStats:
    """Point-in-time pool accounting (bench / autoscaling signal)."""

    capacity: int  # usable pages
    in_use: int
    peak_in_use: int
    page_bytes: int  # bytes of one page across all layers (K+V)
    active_slots: int
    shared_pages: int = 0  # physical pages referenced by >1 holder
    total_refs: int = 0  # logical references (slot mappings + registry)
    registry_pages: int = 0  # pages the prefix registry retains

    @property
    def utilization(self) -> float:
        return self.in_use / self.capacity if self.capacity else 0.0

    @property
    def bytes_in_use(self) -> int:
        return self.in_use * self.page_bytes

    @property
    def bytes_saved(self) -> int:
        """HBM that slot mappings beyond the first copy would have
        duplicated without sharing.  The registry's own retention reference
        is excluded — retaining a prefix for *future* sharers saves nothing
        by itself."""
        extra = self.total_refs - self.in_use - self.registry_pages
        return max(0, extra) * self.page_bytes


class PagedKVCache:
    """Device page pools + per-slot page tables for the batched engine.

    The pools pytree mirrors ``T.init_cache``'s structure (so it threads
    through ``forward_hidden``'s scan unchanged), but attention K/V leaves
    are page pools shared by all slots; per-slot state (mamba SSM/conv) stays
    slot-indexed and is scattered/gathered alongside the pages.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        max_batch: int,
        max_seq: int,
        block_size: int,
        num_blocks: int | None = None,
        jit_cache_cap: int | None = None,
        kv_dtype: str = "fp32",
    ):
        if max_seq % block_size != 0:
            raise ValueError(
                f"max_seq {max_seq} must be a multiple of block_size "
                f"{block_size}")
        if jit_cache_cap is not None and jit_cache_cap < 1:
            raise ValueError(
                f"jit_cache_cap must be >= 1, got {jit_cache_cap}")
        self.kv_dtype = quant.validate_kv_dtype(kv_dtype)
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.block_size = block_size
        self.max_pages = max_seq // block_size
        if num_blocks is None:
            # Parity budget with the contiguous cache: every slot can still
            # grow to max_seq simultaneously (+ the trash page).  Smaller
            # pools oversubscribe HBM and rely on backpressure/preemption.
            num_blocks = max_batch * self.max_pages + 1
        self.num_blocks = num_blocks
        self.allocator = BlockAllocator(num_blocks)
        self.registry = PrefixRegistry()
        self.pools = T.init_paged_cache(
            cfg, max_batch, num_blocks, block_size, kv_dtype=kv_dtype)
        #: Registry that counts the runs of programs returning the pools
        #: (see :meth:`adopt`); the engine installs its own.
        self.metrics: Any = None
        # Host-side table; pushed to device per decode tick (tiny int32s).
        self.page_table = np.full(
            (max_batch, self.max_pages), TRASH_PAGE, np.int32)
        self._owned: list[list[int]] = [[] for _ in range(max_batch)]
        self.peak_pages_in_use = 0
        self.cow_forks = 0
        self.last_lookup_probed = False  # did the newest lookup_prefix
        # descend at all? (the engine's per-admission counter keys off it)
        # Per-n_pages compile-cache bound; a tuned plan sizes it to the
        # distinct admission/evict page counts its geometry actually sees.
        self._jit_cap = jit_cache_cap if jit_cache_cap else _JIT_CACHE_CAP
        self._scatter_jit: collections.OrderedDict = collections.OrderedDict()
        self._gather_jit: collections.OrderedDict = collections.OrderedDict()
        self._load_jit: collections.OrderedDict = collections.OrderedDict()
        self._copy_jit: Any = None
        # Opt-in runtime sanitizer: re-check the full invariant set after
        # every mutating method (analysis.poolcheck shares the predicates
        # with the static audit).  Counted so tests can assert it ran.
        self.sanitize_checks = 0
        if os.environ.get("REPRO_SANITIZE"):
            from repro.analysis.poolcheck import attach_sanitizer
            attach_sanitizer(self)

    # -- accounting ------------------------------------------------------------

    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` cache rows."""
        return -(-length // self.block_size)

    @property
    def free_pages(self) -> int:
        return self.allocator.free_count

    @property
    def pages_in_use(self) -> int:
        return self.allocator.used_count

    @property
    def page_bytes(self) -> int:
        """HBM bytes of one page across all layers (K + V, plus the
        per-page scale rows when the pool is quantized)."""
        total = 0
        for c in self.pools["blocks"].values():
            for key in ("k", "v", "k_scale", "v_scale"):
                if key in c:
                    leaf = c[key]
                    total += leaf.size * leaf.dtype.itemsize // self.num_blocks
        return total

    @property
    def slot_state_bytes(self) -> int:
        """HBM bytes of one slot's per-slot state across all layers (SSM
        and conv rows, cross-attention K/V): what travels with a slot's
        pages on evict, readmit and the admission scatter."""
        total = 0
        for c in self.pools["blocks"].values():
            for key, leaf in c.items():
                if key not in T.PAGED_LEAVES:
                    total += leaf.size * leaf.dtype.itemsize // self.max_batch
        return total

    def stats(self, *, active_slots: int = 0) -> PoolStats:
        return PoolStats(
            capacity=self.allocator.capacity, in_use=self.pages_in_use,
            peak_in_use=self.peak_pages_in_use, page_bytes=self.page_bytes,
            active_slots=active_slots,
            shared_pages=self.allocator.shared_count,
            total_refs=self.allocator.total_refs,
            registry_pages=self.registry.blocks_held)

    def slot_pages(self, slot: int) -> list[int]:
        return list(self._owned[slot])

    # -- allocation ------------------------------------------------------------

    def _alloc_blocks(self, n: int) -> list[int] | None:
        """Free-list alloc with prefix reclaim: on shortfall, LRU-drop
        registry entries (their blocks free once no slot shares them) until
        the request fits or the registry is empty."""
        pages = self.allocator.alloc(n)
        while pages is None:
            dropped = self.registry.pop_lru()
            if dropped is None:
                return None
            self.allocator.free(dropped)
            pages = self.allocator.alloc(n)
        return pages

    def alloc(self, slot: int, length: int) -> bool:
        """Grow ``slot``'s page table to cover ``length`` rows (lazy: only
        the missing tail pages are taken).  All-or-nothing; False = the free
        list can't satisfy it (caller applies backpressure or preempts)."""
        need = self.pages_for(length) - len(self._owned[slot])
        if need <= 0:
            return True
        pages = self._alloc_blocks(need)
        if pages is None:
            return False
        start = len(self._owned[slot])
        self._owned[slot].extend(pages)
        self.page_table[slot, start: start + len(pages)] = pages
        self.peak_pages_in_use = max(
            self.peak_pages_in_use, self.pages_in_use)
        return True

    def map_shared(self, slot: int, blocks: list[int]) -> None:
        """Map already-resident prefix blocks into the front of ``slot``'s
        page table at refcount+1 — the SYNC prefix staged once, not copied.
        The slot must be empty (sharing happens at admission)."""
        assert not self._owned[slot], (slot, self._owned[slot])
        self.allocator.incref(blocks)
        self._owned[slot] = list(blocks)
        self.page_table[slot, : len(blocks)] = blocks

    def shield(self, slot: int) -> None:
        """Point ``slot``'s table row at trash while keeping ownership.

        An admission in progress is still a *padding row* of the interleaved
        batched decode ticks; padding rows scatter garbage K/V through the
        page table, which must land in the trash block — not in the slot's
        pages (fatal for a mapped shared prefix, whose corruption every
        sharer would read).  ``publish`` re-exposes the pages on activation.
        """
        self.page_table[slot, :] = TRASH_PAGE

    def publish(self, slot: int) -> None:
        """Re-expose ``slot``'s owned pages in the page table (after the
        admission scatter, before the slot goes active)."""
        pages = self._owned[slot]
        self.page_table[slot, :] = TRASH_PAGE
        self.page_table[slot, : len(pages)] = pages

    @tick_path(allowed_fetches=0)
    def ensure_write(self, slot: int, pos: int) -> bool:
        """Make position ``pos`` writable for ``slot`` (the lazy page fault
        as ``cur`` advances).  If the target page is shared, fork it first
        (copy-on-write): the write lands in a private copy, so the other
        sharers never observe this slot's divergence."""
        if not self.alloc(slot, pos + 1):
            return False
        idx = pos // self.block_size
        blk = self._owned[slot][idx]
        if self.allocator.refcount(blk) == 1:
            return True
        fresh = self._alloc_blocks(1)
        if fresh is None:
            return False  # caller preempts; the shared mapping stays valid
        self._copy_block(blk, fresh[0])
        self.allocator.free([blk])  # drop this slot's reference only
        self._owned[slot][idx] = fresh[0]
        self.page_table[slot, idx] = fresh[0]
        self.cow_forks += 1
        self.peak_pages_in_use = max(
            self.peak_pages_in_use, self.pages_in_use)
        return True

    @tick_path(allowed_fetches=0)
    def truncate(self, slot: int, length: int) -> None:
        """Shrink ``slot``'s page table to cover exactly ``length`` rows —
        the speculative-decode rollback: pages allocated for draft positions
        beyond the accepted prefix go back to the free list.  The engine
        only ever truncates pages it faulted in this tick (``ensure_write``
        forks shared targets before writing), so the dropped tail is
        exclusively owned — freeing it reaches refcount zero immediately
        and never disturbs shared/COW prefix pages."""
        keep = self.pages_for(length)
        tail = self._owned[slot][keep:]
        if not tail:
            return
        assert all(self.allocator.refcount(p) == 1 for p in tail), (
            "rollback would drop a shared page", slot, tail)
        self.allocator.free(tail)
        del self._owned[slot][keep:]
        self.page_table[slot, keep:] = TRASH_PAGE

    def release(self, slot: int) -> None:
        """Drop ``slot``'s page references and point its table at trash;
        blocks still shared (other slots / the prefix registry) stay."""
        if self._owned[slot]:
            self.allocator.free(self._owned[slot])
            self._owned[slot] = []
        self.page_table[slot, :] = TRASH_PAGE

    def adopt(self, pools: Any) -> None:
        """Take ``pools``, what a program that was handed ``self.pools``
        returned, and count that run: ``serving.pool_donated_runs`` when
        the program consumed the old buffers (donated, updated in place),
        ``serving.pool_copied_runs`` when they are still alive beside the
        new ones (a fresh pool was made). Reads nothing from the device."""
        donated = jax.tree.leaves(self.pools)[0].is_deleted()
        self.pools = pools
        if self.metrics is not None:
            self.metrics.inc("serving.pool_donated_runs" if donated
                             else "serving.pool_copied_runs")

    @tick_path(allowed_fetches=0)
    def device_page_table(self) -> jax.Array:
        return jnp.asarray(self.page_table)

    def check_invariants(self) -> None:
        """Raise :class:`PoolInvariantError` unless the whole pool is sound:
        allocator conservation against the slots' owned pages + registry
        retentions (POOL001/POOL003 via ``BlockAllocator.check_invariants``),
        page-table rows consistent with ownership and free of cross-slot
        aliasing, trash never mapped as real data (POOL002), and quant
        scale leaves traveling with their pages (POOL005)."""
        use = self.registry._block_use
        self.allocator.check_invariants(self._owned, use)
        for slot, owned in enumerate(self._owned):
            if TRASH_PAGE in owned:
                raise PoolInvariantError(
                    "POOL002", f"slot {slot} owns the trash page (trash "
                    "writes would be read back as data)")
            row = self.page_table[slot]
            n = len(owned)
            tail = row[n:]
            if tail.size and not (tail == TRASH_PAGE).all():
                raise PoolInvariantError(
                    "POOL002", f"slot {slot} table maps pages beyond its "
                    f"{n} owned ({row.tolist()})")
            head = row[:n]
            # A row entry is either this slot's page at that index or
            # trash (shielded during admission) — anything else aliases
            # another slot's data through this table.
            bad = [i for i in range(n)
                   if head[i] != TRASH_PAGE and head[i] != owned[i]]
            if bad:
                raise PoolInvariantError(
                    "POOL002", f"slot {slot} table rows {bad} alias pages "
                    f"it does not own there (table {head.tolist()}, owned "
                    f"{owned})")
        for b in use:
            if b == TRASH_PAGE:
                raise PoolInvariantError(
                    "POOL002", "the prefix registry retains the trash page")
            if self.allocator.refcount(b) < 1:
                raise PoolInvariantError(
                    "POOL001", f"registry retains unallocated page {b}")
        if quant.is_quantized(self.kv_dtype):
            st = quant.storage_dtype(self.kv_dtype)
            for name, c in self.pools["blocks"].items():
                for key in ("k", "v"):
                    leaf = c.get(key)
                    if leaf is None or leaf.ndim < 2 \
                            or leaf.shape[1] != self.num_blocks:
                        continue  # per-slot state, not a page pool
                    if leaf.dtype != st:
                        raise PoolInvariantError(
                            "POOL005", f"{name}.{key}: pool dtype "
                            f"{leaf.dtype} != declared storage {st}")
                    skey = f"{key}_scale"
                    sc = c.get(skey)
                    if sc is None:
                        raise PoolInvariantError(
                            "POOL005", f"{name}.{key}: quantized pool leaf "
                            "has no scale leaf (scales must travel with "
                            "their page)")
                    if sc.dtype != jnp.float32 \
                            or sc.shape[:2] != leaf.shape[:2]:
                        raise PoolInvariantError(
                            "POOL005", f"{name}.{skey}: scale layout "
                            f"{sc.shape}/{sc.dtype} does not ride the "
                            f"page axis of {leaf.shape} as f32")
        self.sanitize_checks += 1

    # -- prefix registry (the SYNC transfer, staged once) ----------------------

    def lookup_prefix(
        self, tokens: np.ndarray, *, min_pages: int = 1,
        align_tokens: int = 1, count: bool = True,
    ) -> tuple[int, list[int]]:
        """Longest registered page-aligned *proper* prefix of ``tokens``.

        ``align_tokens`` restricts matches to multiples of the caller's
        prefill chunk so the uncovered tail re-runs the exact chunk grid a
        full prefill would (token parity is bitwise, not approximate).
        Returns (n_pages, blocks); (0, []) on miss.

        The whole descent is *one* logical lookup: at most one hit or miss
        lands on ``registry.hits``/``misses`` per call (failed probes on
        the way down are not misses — de-noised counters).  ``count=False``
        records nothing: the admission *gate* re-evaluates the same queued
        request every scheduling quantum under backpressure, so the engine
        counts one outcome per admission (in ``_admit``), not per poll.
        Either way ``last_lookup_probed`` reports whether this call probed
        at all (a prompt too short for an aligned proper prefix has no
        outcome worth counting later).
        """
        tokens = np.asarray(tokens, np.int32)
        bs = self.block_size
        max_pages = (len(tokens) - 1) // bs  # proper: >= 1 tail token
        probed = False
        hit: tuple[int, list[int]] | None = None
        for n in range(max_pages, max(1, min_pages) - 1, -1):
            if align_tokens > 1 and (n * bs) % align_tokens:
                continue
            probed = True
            blocks = self.registry.get(tokens[: n * bs], count=False)
            if blocks is not None:
                hit = (n, blocks)
                break
        self.last_lookup_probed = probed
        if count and probed:
            self.registry.record_lookup(hit is not None)
        return hit if hit is not None else (0, [])

    def register_prefix(
        self, tokens: np.ndarray, slot: int, *, min_pages: int = 1,
        align_tokens: int = 1,
    ) -> None:
        """Publish the page-aligned prefixes of ``slot``'s prompt so later
        admissions can map its blocks.  ``align_tokens`` should mirror the
        lookup's chunk alignment: entries at lengths the lookup never
        probes would only burn registry slots and digest work.  Each entry
        holds one registry-wide reference per distinct block; whole-page
        prompt rows are never rewritten by this slot's decode (writes start
        at ``len(tokens)``), so registered pages stay immutable until COW
        or reclaim."""
        tokens = np.asarray(tokens, np.int32)
        bs = self.block_size
        owned = self._owned[slot]
        for n in range(max(1, min_pages), len(tokens) // bs + 1):
            if align_tokens > 1 and (n * bs) % align_tokens:
                continue
            retained, released = self.registry.put(
                tokens[: n * bs], owned[:n])
            if retained:
                self.allocator.incref(retained)
            if released:
                self.allocator.free(released)

    def reclaim_for(self, n: int) -> bool:
        """Drop LRU prefix entries until at least ``n`` pages are free.

        False = the registry ran dry first: the pool is genuinely full of
        slot-referenced pages and the caller must backpressure or preempt.
        (Entries whose blocks are still shared by active slots free nothing
        when dropped; the loop keeps going past them.)
        """
        while self.allocator.free_count < n:
            dropped = self.registry.pop_lru()
            if dropped is None:
                return False
            self.allocator.free(dropped)
        return True

    def clear_prefixes(self) -> None:
        """Drop every registry entry (frees blocks no slot still shares)."""
        self.allocator.free(self.registry.clear())

    def clear_stranded_prefixes(self, align_tokens: int) -> int:
        """Drop registry entries stranded by a prefill-chunk change: the
        chunk-grid-aligned lookup only probes multiples of the chunk, so an
        entry registered under the old grid whose length doesn't land on
        the new one can never match again — without this it lingers,
        pinning pages, until pool pressure reclaims it.  Returns how many
        entries were dropped."""
        before = len(self.registry)
        self.allocator.free(self.registry.drop_stranded(align_tokens))
        return before - len(self.registry)

    # -- prefix persistence (registry survives engine rebuilds) ----------------

    def save_prefixes(self, path: str | os.PathLike) -> int:
        """Serialize the prefix registry — token keys, block lists, and the
        referenced page contents — to ``path`` (npz, atomic replace).

        Stored next to the tuning db so a later engine serving the same
        model warm-starts sharing instead of re-prefilling every common
        prefix.  Returns entries written; 0 writes nothing and leaves any
        existing file untouched (an empty registry is not worth a file).
        """
        entries = self.registry.entries()
        if not entries:
            return 0
        distinct: list[int] = []
        seen: set[int] = set()
        for _, blocks in entries:
            for b in blocks:
                if b not in seen:
                    seen.add(b)
                    distinct.append(b)
        arrays: dict[str, np.ndarray] = {}
        idx = np.asarray(distinct, np.int64)
        for name, c in self.pools["blocks"].items():
            for key in ("k", "v", "k_scale", "v_scale"):
                if key in c:
                    arrays[f"pool.{name}.{key}"] = np.asarray(c[key][:, idx])
        for i, (tb, blocks) in enumerate(entries):
            arrays[f"entry{i}.tokens"] = np.frombuffer(tb, np.int32)
            arrays[f"entry{i}.blocks"] = np.asarray(blocks, np.int64)
        meta = {
            "schema": PREFIX_STORE_SCHEMA,
            "model": _config_digest(self.cfg),
            "block_size": self.block_size,
            # Pool dtype is a staleness key: page bytes written at fp32 are
            # not loadable codes for an int8 pool (and vice versa), so a
            # mismatched store must be rejected, never reinterpreted.
            "kv_dtype": self.kv_dtype,
            "blocks": distinct,
            "n_entries": len(entries),
        }
        arrays["meta"] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), np.uint8)
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
        return len(entries)

    def load_prefixes(self, path: str | os.PathLike) -> int:
        """Restore a saved prefix registry into this pool.

        Stale or unreadable stores are skipped wholesale (returns 0): the
        meta block pins the store schema, the model-config digest, and the
        block size, and every page array's shape is checked against the
        live pool before any block is allocated.  Saved block ids are
        remapped onto freshly allocated blocks; each restored block carries
        exactly one allocator reference — the registry's retention ref —
        so reclaim and COW behave as if the prefixes had been registered
        by a slot that since retired.  Returns entries restored.
        """
        path = pathlib.Path(path)
        if not path.exists():
            return 0
        try:
            data = np.load(path, allow_pickle=False)
        except (OSError, ValueError):
            return 0
        try:
            if "meta" not in data:
                return 0
            meta = json.loads(bytes(data["meta"].tobytes()))
            if (meta.get("schema") != PREFIX_STORE_SCHEMA
                    or meta.get("model") != _config_digest(self.cfg)
                    or meta.get("block_size") != self.block_size
                    or meta.get("kv_dtype", "fp32") != self.kv_dtype):
                return 0
            old_ids = [int(b) for b in meta.get("blocks", [])]
            old_set = set(old_ids)
            n = len(old_ids)
            if n == 0 or len(old_set) != n:
                return 0
            pages: dict[tuple[str, str], np.ndarray] = {}
            for name, c in self.pools["blocks"].items():
                for key in ("k", "v", "k_scale", "v_scale"):
                    if key not in c:
                        continue
                    akey = f"pool.{name}.{key}"
                    if akey not in data:
                        return 0
                    arr = data[akey]
                    leaf = c[key]
                    want = (leaf.shape[0], n) + tuple(leaf.shape[2:])
                    if tuple(arr.shape) != want:
                        return 0
                    pages[(name, key)] = arr
            raw_entries: list[tuple[np.ndarray, list[int]]] = []
            for i in range(int(meta.get("n_entries", 0))):
                toks = np.asarray(data[f"entry{i}.tokens"], np.int32)
                blocks = [int(b) for b in data[f"entry{i}.blocks"]]
                if not blocks or any(b not in old_set for b in blocks):
                    return 0
                raw_entries.append((toks, blocks))
        except (KeyError, ValueError):
            return 0
        finally:
            data.close()
        if not raw_entries:
            return 0
        new = self.allocator.alloc(n)
        if new is None:  # pool too small for the store: cold start
            return 0
        mapping = dict(zip(old_ids, new))
        nidx = np.asarray(new, np.int64)
        for (name, key), arr in pages.items():
            leaf = self.pools["blocks"][name][key]
            self.pools["blocks"][name][key] = leaf.at[:, nidx].set(
                jnp.asarray(arr, leaf.dtype))
        new_set = set(new)
        released_ext: list[int] = []
        for toks, blocks in raw_entries:  # oldest first: LRU order survives
            _, released = self.registry.put(
                toks, [mapping[b] for b in blocks])
            # alloc's refcount *is* the retention ref — no incref here;
            # blocks outside this restore batch settle in the final sweep.
            released_ext += [b for b in released if b not in new_set]
        use = self.registry._block_use
        self.allocator.free([b for b in released_ext if b not in use])
        self.allocator.free([b for b in new if b not in use])
        return len(raw_entries)

    # -- page scatter / gather / copy (admission, evict, readmit, COW) ---------

    @transfer_budget(d2h_arrays=0, d2h_outputs=())
    def _make_scatter(self, n_pages: int):
        bs = self.block_size
        kv_dtype = self.kv_dtype

        def fn(pools, src, pages, slot, row0):
            out = {"blocks": {}}
            for name, c in pools["blocks"].items():
                sc = src["blocks"][name]
                oc = {}
                for key, leaf in c.items():
                    if key in ("k_scale", "v_scale"):
                        continue  # written alongside their data leaf below
                    if key in ("k", "v"):
                        rows = jax.lax.dynamic_slice_in_dim(
                            sc[key][:, 0], row0, n_pages * bs, axis=1)
                        r = rows.shape[0]
                        rows = rows.reshape(r, n_pages, bs, *rows.shape[2:])
                        skey = f"{key}_scale"
                        if skey in c:
                            # Quantization fused into the page scatter: the
                            # pool never holds full-precision rows.
                            scales = quant.scales_of(rows, kv_dtype)
                            codes = quant.quantize(rows, scales, kv_dtype)
                            oc[key] = leaf.at[:, pages].set(codes)
                            oc[skey] = c[skey].at[:, pages].set(scales)
                        else:
                            oc[key] = leaf.at[:, pages].set(
                                rows.astype(leaf.dtype))
                    else:  # per-slot state (mamba ssm/conv)
                        oc[key] = jax.lax.dynamic_update_slice_in_dim(
                            leaf, sc[key].astype(leaf.dtype), slot, axis=1)
                out["blocks"][name] = oc
            return out

        return jax.jit(fn, donate_argnums=0)  # the pools, written in place

    @transfer_budget(d2h_arrays=0, d2h_outputs=())
    def _make_gather(self, n_pages: int):
        bs = self.block_size

        cdt = self.cfg.compute_dtype

        def fn(pools, pages, slot):
            out = {"blocks": {}}
            for name, c in pools["blocks"].items():
                oc = {}
                for key, leaf in c.items():
                    if key in ("k_scale", "v_scale"):
                        continue  # folded into the dequantized k/v rows
                    if key in ("k", "v"):
                        g = leaf[:, pages]  # (r, n, bs, hkv, hd)
                        skey = f"{key}_scale"
                        if skey in c:
                            g = quant.dequantize(
                                g, c[skey][:, pages]).astype(cdt)
                        r = g.shape[0]
                        oc[key] = g.reshape(
                            r, n_pages * bs, *g.shape[3:])[:, None]
                    else:
                        oc[key] = jax.lax.dynamic_slice_in_dim(
                            leaf, slot, 1, axis=1)
                out["blocks"][name] = oc
            return out

        return jax.jit(fn)

    @transfer_budget(d2h_arrays=0, d2h_outputs=())
    def _make_load(self, n_pages: int):
        bs = self.block_size

        def fn(pools, caches, pages):
            out = {"blocks": {}}
            for name, dst in caches["blocks"].items():
                c = pools["blocks"].get(name, {})
                oc = {}
                for key, leaf in dst.items():
                    if key in ("k", "v") and key in c:
                        g = c[key][:, pages]  # (r, n, bs, hkv, hd)
                        skey = f"{key}_scale"
                        if skey in c:
                            g = quant.dequantize(g, c[skey][:, pages])
                        r = g.shape[0]
                        rows = g.reshape(r, n_pages * bs, *g.shape[3:])[:, None]
                        oc[key] = jax.lax.dynamic_update_slice(
                            leaf, rows.astype(leaf.dtype), (0,) * leaf.ndim)
                    else:
                        oc[key] = leaf
                out["blocks"][name] = oc
            return out

        return jax.jit(fn, donate_argnums=1)  # the b=1 cache it fills

    def scatter(
        self, slot: int, caches: Any, length: int, *, start_page: int = 0
    ) -> None:
        """Write a b=1 contiguous cache's rows ``[start_page * block_size,
        length)`` into ``slot``'s pages (admission after chunked prefill, or
        readmit).  The slot must already own ``pages_for(length)`` pages;
        the target pages must be exclusively owned (shared prefix pages are
        mapped, never scattered over)."""
        n_total = self.pages_for(length)
        n = n_total - start_page
        assert n > 0 and len(self._owned[slot]) >= n_total, (
            slot, length, start_page, self._owned[slot])
        target = self._owned[slot][start_page:n_total]
        assert all(self.allocator.refcount(p) == 1 for p in target), (
            "scatter into a shared page would corrupt its sharers", target)
        fn = _lru_jit(self._scatter_jit, n, lambda: self._make_scatter(n),
                      cap=self._jit_cap)
        self.adopt(fn(
            self.pools, caches, jnp.asarray(target, jnp.int32),
            jnp.int32(slot), jnp.int32(start_page * self.block_size)))

    def gather(self, slot: int, length: int) -> Any:
        """Pull ``slot``'s first ``length`` rows out of the pool as a b=1
        contiguous cache of ``pages_for(length) * block_size`` rows (evict:
        page contents travel with the request)."""
        n = self.pages_for(length)
        assert len(self._owned[slot]) >= n, (slot, length, self._owned[slot])
        fn = _lru_jit(self._gather_jit, n, lambda: self._make_gather(n),
                      cap=self._jit_cap)
        pages = jnp.asarray(self._owned[slot][:n], jnp.int32)
        return fn(self.pools, pages, jnp.int32(slot))

    def load_prefix(self, caches: Any, blocks: list[int]) -> Any:
        """Copy ``blocks``' pool rows into the front of a b=1 contiguous
        cache (the prefill context for the uncovered tail of a shared-prefix
        admission).  Returns the updated cache pytree; ``caches`` is
        donated to it."""
        n = len(blocks)
        assert n > 0
        fn = _lru_jit(self._load_jit, n, lambda: self._make_load(n),
                      cap=self._jit_cap)
        return fn(self.pools, caches, jnp.asarray(blocks, jnp.int32))

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side page copy (the COW fork body)."""
        if self._copy_jit is None:
            def fn(pools, s, d):
                out = {"blocks": {}}
                for name, c in pools["blocks"].items():
                    oc = {}
                    for key, leaf in c.items():
                        if key in ("k", "v", "k_scale", "v_scale"):
                            # the COW fork moves the scale with the page
                            oc[key] = leaf.at[:, d].set(leaf[:, s])
                        else:
                            oc[key] = leaf
                    out["blocks"][name] = oc
                return out

            self._copy_jit = jax.jit(fn, donate_argnums=0)
        self.adopt(self._copy_jit(self.pools, jnp.int32(src),
                                  jnp.int32(dst)))
