"""KV-cache pages — first-class transferable objects with an explicit
RDMA-style lifecycle.

A serving session's KV-cache is not a blob to serialize: it is a set of
**pages** (one per layer cache array) that a prefill tier *exports*,
*describes* over the control plane, and a decode tier *imports* — the
payload itself moving as registered memory (the in-process/ICI fabric,
or a shm ring slot), never through the serialized message path.  This
module is the export registry: the sender-side bookkeeping that makes a
page a capability with a bounded lifetime instead of a leaked alias.

Lifecycle (mirrors ``transport/shm_ring``'s slot discipline):

    export    the page's device array is posted on the ICI fabric
              (``InProcessFabric.post`` — the "memory registration")
              and pinned in a FIXED page table under a fresh
              generation; the table is bounded, so a leak is visible
              as exhaustion, not as silent growth
    describe  ``(page_id, generation, nbytes)`` — 12 bytes on the wire
              per page; the generation makes every descriptor
              single-lifetime (a recycled page id cannot resolve an
              old descriptor)
    import    one-shot: resolves the descriptor through the registry
              and CONSUMES the fabric entry (``InProcessFabric.take``),
              so a second import of the same descriptor — or an import
              after the exporter released — fails LOUDLY with
              :class:`KvPageError` (surfaced as ERESPONSE by the
              handoff service, never "success with an empty cache")
    release   generation-checked: releasing a page twice, or with a
              stale generation, raises instead of freeing the table
              slot's NEXT tenant

Pages are tagged with an **owner** key at export (the client
connection whose session they belong to): a dying socket sweeps its
pages (``on_socket_closed``, wired into ``Socket.release`` next to the
shm sweep), and the drain plane waits for every outstanding exported
page to settle before the process exits (``drain_settle``, bounded by
the drain grace like the shm ring's).

Since the paged-KV round this module is the **allocator**, not just the
courier.  Three more planes live here:

- :class:`PageAllocator` — host-side bookkeeping for the continuous
  batcher's device page pool (block-paged attention,
  ``models/transformer_lm.make_paged_batch_decode``: the step writes
  its row into the slot's current page and attends over the slot's
  LIVE pages where they lie, ``ops/paged_attention``, never over a
  gathered ``max_seq`` copy of the block table): a fixed pool of
  fixed-size token pages, REFCOUNTED so the prefix cache can alias a
  session's immutable full pages, generation-checked so a stale alias
  fails loudly instead of reading the slot's next tenant;
- :class:`PrefixCache` — a radix tree over page-granular token-chunk
  fingerprints: a re-sent system prompt / chat history hits, ALIASES
  the shared pages (refcount up, zero bytes moved — the round-18
  import-is-an-alias discipline applied inside one pool) and skips
  prefill for the covered prefix;
- :class:`HostPagePool` — the LRU eviction tier: a cold session's
  private pages spill to a pinned host-RAM pool under the shm ring's
  slot discipline (fixed slots, one memcpy per page, generation-checked
  handles, loud double-free) and re-import on resume.  Mid-spill pages
  are an in-flight gauge the drain plane counts (``drain_settle``): at
  grace expiry the pool is marked aborted and its owner closes the
  parked sessions under the named ``kv_spill_drain_aborted`` reason.
"""

from __future__ import annotations

import hashlib
import struct
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ..butil.flags import define_flag, get_flag
from ..butil.logging_util import LOG

define_flag("kv_pages", 256,
            "size of the KV page export table (exported-but-unsettled "
            "pages; bounded so leaks surface as exhaustion)",
            validator=lambda v: isinstance(v, int) and 0 < v <= 65535)

# ---------------------------------------------------------------------------
# Closed reason/event enums (no "unknown" bucket — tools/check/enums.py
# requires a test pin per member, the same discipline as transport.py's
# KV_FALLBACK_REASONS).
# ---------------------------------------------------------------------------

# stream close reasons the ALLOCATOR can emit: every session the paged
# batcher refuses or abandons closes under exactly one of these
KV_EVICT_REASONS = (
    "kv_pool_exhausted",       # no device pages free for a new session
    "kv_host_tier_full",       # spill refused: the host tier is full too
    "kv_spill_drain_aborted",  # drain grace expired on a mid-evict spill
)

# prefix-cache outcome events (counters, closed set)
PREFIX_CACHE_EVENTS = (
    "prefix_hit",              # every full page of the context aliased
    "prefix_partial_hit",      # a proper prefix aliased, remainder
    #                            caught up by teacher-forced steps
    "prefix_miss",             # nothing aliased: full bucketed prefill
    "prefix_insert",           # a new prefix entered the radix tree
    "prefix_evict",            # an LRU entry released its page refs
)

_evict_lock = threading.Lock()
_evicts: Dict[str, int] = {r: 0 for r in KV_EVICT_REASONS}
_prefix_events: Dict[str, int] = {e: 0 for e in PREFIX_CACHE_EVENTS}


def count_evict(reason: str) -> None:
    assert reason in _evicts, f"unnamed kv evict reason {reason!r}"
    with _evict_lock:
        _evicts[reason] += 1
    try:
        from .. import fleet
        fleet.record_event("fleet_kv_evict", reason)
    except Exception:
        pass


def count_prefix(event: str) -> None:
    assert event in _prefix_events, f"unnamed prefix event {event!r}"
    with _evict_lock:
        _prefix_events[event] += 1


def kv_evict_counters() -> Dict[str, int]:
    with _evict_lock:
        return dict(_evicts)


def prefix_event_counters() -> Dict[str, int]:
    with _evict_lock:
        return dict(_prefix_events)

_DESC_FMT = "<IIQ"          # page_id, generation, nbytes
DESC_BYTES = struct.calcsize(_DESC_FMT)


class KvPageError(Exception):
    """A KV page descriptor this process cannot honor — stale
    generation, double import, double free, or an unknown page.  A
    protocol violation, not a fallback shape: the handoff service
    answers ERESPONSE (the import side must fail loudly, never hand
    the decoder an empty cache)."""


class KvPageHandle:
    """Sender-side lease of one exported page (settle exactly once)."""

    __slots__ = ("page_id", "gen", "nbytes")

    def __init__(self, page_id: int, gen: int, nbytes: int):
        self.page_id = page_id
        self.gen = gen
        self.nbytes = nbytes

    def describe(self) -> bytes:
        return struct.pack(_DESC_FMT, self.page_id, self.gen,
                           self.nbytes)


def decode_desc(data: bytes) -> Tuple[int, int, int]:
    if len(data) != DESC_BYTES:
        raise KvPageError(f"malformed kv page descriptor "
                          f"({len(data)} bytes)")
    return struct.unpack(_DESC_FMT, data)


class _Rec:
    __slots__ = ("desc_id", "nbytes", "owner", "imported")

    def __init__(self, desc_id: int, nbytes: int, owner: Any):
        self.desc_id = desc_id
        self.nbytes = nbytes
        self.owner = owner
        self.imported = False


class KvPageStore:
    """The process's page export table (fixed size, generation-checked
    — the shm ring's slot model applied to device arrays)."""

    def __init__(self, npages: int):
        self.npages = int(npages)
        self._lock = threading.Lock()
        self._recs: List[Optional[_Rec]] = [None] * self.npages
        self._gen = [0] * self.npages
        self._free = list(range(self.npages))
        self.exported = 0            # lifetime counters (stats)
        self.imported = 0
        self.swept = 0

    # -- export ------------------------------------------------------------

    def export_array(self, array: Any, nbytes: int,
                     owner: Any = None) -> Optional[KvPageHandle]:
        """Register one page (a live device array) for transfer.  The
        array is posted on the in-process fabric — kept alive and
        addressable until imported, released, or swept.  Returns None
        when the table is full (the caller falls back under a NAMED
        reason — exhaustion is backpressure, not an error)."""
        from ..ici.fabric import in_process_fabric
        with self._lock:
            if not self._free:
                return None
            page_id = self._free.pop()
            self._gen[page_id] += 1
            gen = self._gen[page_id]
        desc_id = in_process_fabric().post(array, nbytes)
        with self._lock:
            self._recs[page_id] = _Rec(desc_id, nbytes, owner)
            self.exported += 1
        return KvPageHandle(page_id, gen, nbytes)

    # -- import (one-shot, loud) -------------------------------------------

    def import_page(self, page_id: int, gen: int, nbytes: int) -> Any:
        """Resolve a descriptor into its array, CONSUMING the fabric
        entry: the importer owns the array from here on.  Stale
        generation, unknown page, size mismatch, or a second import all
        raise :class:`KvPageError` — the loud-failure contract."""
        from ..ici.fabric import in_process_fabric
        with self._lock:
            rec = self._recs[page_id] \
                if 0 <= page_id < self.npages else None
            if rec is None or self._gen[page_id] != gen:
                raise KvPageError(
                    f"stale kv page import (page {page_id} gen {gen})")
            if rec.imported:
                raise KvPageError(
                    f"kv page {page_id} already imported")
            if rec.nbytes != nbytes:
                raise KvPageError(
                    f"kv page {page_id} size mismatch "
                    f"({nbytes} != {rec.nbytes})")
            desc_id = rec.desc_id
            rec.imported = True
        arr = in_process_fabric().take(desc_id)
        if arr is None:
            # released/swept between the rec check and the take — the
            # registry says live but the registration is gone: loud
            raise KvPageError(
                f"kv page {page_id} no longer registered")
        with self._lock:
            self.imported += 1
        return arr

    # -- release (generation-checked, loud on misuse) ----------------------

    def release(self, page_id: int, gen: int) -> None:
        """Settle one exported page (the sender's end-of-handoff).
        Double-free and stale-generation frees raise — a silent no-op
        here would free the table slot's NEXT tenant one day."""
        from ..ici.fabric import in_process_fabric
        with self._lock:
            rec = self._recs[page_id] \
                if 0 <= page_id < self.npages else None
            if rec is None or self._gen[page_id] != gen:
                raise KvPageError(
                    f"double/stale kv page free (page {page_id} "
                    f"gen {gen})")
            self._recs[page_id] = None
            self._free.append(page_id)
            desc_id, imported = rec.desc_id, rec.imported
        if not imported:
            # never imported: drop the fabric registration ourselves
            in_process_fabric().release(desc_id)

    def settle_handles(self, handles) -> None:
        """Release a handoff's whole page set (each exactly once)."""
        for h in handles:
            self.release(h.page_id, h.gen)

    # -- sweeps / drain ----------------------------------------------------

    def release_owner(self, owner: Any) -> int:
        """Reclaim every page tagged with ``owner`` (its connection
        died before the handoff settled).  Soft by design — the sweep
        races legitimate settles and must not throw at either."""
        from ..ici.fabric import in_process_fabric
        stale = []
        with self._lock:
            for page_id, rec in enumerate(self._recs):
                if rec is not None and rec.owner == owner:
                    self._recs[page_id] = None
                    self._free.append(page_id)
                    if not rec.imported:
                        stale.append(rec.desc_id)
                    self.swept += 1
        for desc_id in stale:
            in_process_fabric().release(desc_id)
        return len(stale)

    def outstanding(self) -> int:
        with self._lock:
            return self.npages - len(self._free)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"pages": self.npages,
                    "outstanding": self.npages - len(self._free),
                    "exported": self.exported,
                    "imported": self.imported,
                    "swept": self.swept}


# ---------------------------------------------------------------------------
# Process-wide registry (mirrors shm_ring's process_tx_ring shape)
# ---------------------------------------------------------------------------

_reg_lock = threading.Lock()
_store: Optional[KvPageStore] = None


def process_kv_store() -> KvPageStore:
    global _store
    with _reg_lock:
        if _store is None:
            _store = KvPageStore(int(get_flag("kv_pages")))
        return _store


def on_socket_closed(owner: Any) -> None:
    """Sweep pages exported for a dead connection (its handoff will
    never settle) — wired into ``Socket.release`` next to the shm
    sweep, so it runs on the owning loop and must stay non-blocking."""
    with _reg_lock:
        store = _store
    if store is not None:
        n = store.release_owner(owner)
        if n:
            LOG.info("kv page sweep: %d page(s) of dead owner %r", n,
                     owner)


def outstanding_pages() -> int:
    """Exported-but-unsettled pages — the drain plane's gauge (0 when
    the kv plane never engaged)."""
    with _reg_lock:
        store = _store
    return store.outstanding() if store is not None else 0


def drain_settle(deadline_mono_s: float) -> int:
    """Operability plane: wait — bounded by the drain-grace deadline —
    for every outstanding exported page to settle (handoff responses
    release them; dead-conn sweeps run from socket close) AND for every
    host-tier spill in flight to land or abort.  At deadline expiry any
    pool still mid-spill is marked aborted so its owner force-closes
    the parked sessions under the named ``kv_spill_drain_aborted``
    reason — a page mid-evict at drain time settles or closes loudly,
    it never leaks.  Returns pages + spills still outstanding at the
    deadline (0 = fully settled)."""
    import time as _time
    ev = threading.Event()
    while True:
        n = outstanding_pages() + host_inflight_spills()
        if n == 0:
            return 0
        if _time.monotonic() >= deadline_mono_s:
            for pool in list(_host_pools):
                if pool.inflight():
                    pool.drain_abort("kv_spill_drain_aborted")
            return n
        ev.wait(0.005)     # timed: the drain path stays deadline-bound


def _reset_for_tests() -> None:
    global _store
    with _reg_lock:
        _store = None
    with _evict_lock:
        for k in _evicts:
            _evicts[k] = 0
        for k in _prefix_events:
            _prefix_events[k] = 0


# ===========================================================================
# The allocator planes (paged-KV round).  Everything below is HOST-side
# bookkeeping: the device page pool itself lives in the batcher's cache
# pytree (``models/transformer_lm.empty_paged_cache``); these classes
# decide which rows of it a session may touch.
# ===========================================================================


class PageAllocator:
    """Refcounted free-list over the device page pool's row blocks.

    Page 0 is RESERVED as the garbage page: unallocated block-table
    entries and inactive-slot writes land there, and the attention mask
    never admits it — so the allocator only ever hands out pages
    ``1..num_pages-1``.

    Refcounts exist for the prefix cache: a session's immutable full
    pages are aliased (``ref``) by the radix tree and by later sessions
    that hit it; the page returns to the free list only when the LAST
    holder releases.  Each return bumps the page's generation, so a
    stale alias (a bug, by construction) fails loudly on the next
    generation check instead of reading the row's next tenant.
    """

    def __init__(self, num_pages: int, page_tokens: int,
                 page_bytes: int = 0):
        if num_pages < 2:
            raise ValueError("PageAllocator needs >= 2 pages "
                             "(page 0 is the reserved garbage page)")
        self.num_pages = int(num_pages)
        self.page_tokens = int(page_tokens)
        self.page_bytes = int(page_bytes)   # device bytes per page (stats)
        self._lock = threading.Lock()
        self._ref = [0] * self.num_pages
        self._gen = [0] * self.num_pages
        # LIFO free list, page 0 never enters it
        self._free = list(range(self.num_pages - 1, 0, -1))
        self.peak_in_use = 0
        self.alloc_failures = 0

    # -- allocation --------------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` fresh pages (refcount 1 each).  Returns None when
        the pool cannot cover the request — exhaustion is backpressure
        with a NAMED close reason (``kv_pool_exhausted``), never a
        partial grant."""
        with self._lock:
            if n > len(self._free):
                self.alloc_failures += 1
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
            self._note_peak_locked()
            return pages

    def ref(self, page_id: int) -> None:
        """Alias a live page (prefix-cache hit / radix insert).  Only a
        page somebody already holds can be aliased — ref'ing a free
        page would resurrect a row the pool may re-grant."""
        with self._lock:
            if not (0 < page_id < self.num_pages) \
                    or self._ref[page_id] <= 0:
                raise KvPageError(
                    f"alias of dead kv device page {page_id}")
            self._ref[page_id] += 1

    def release(self, page_id: int) -> None:
        """Drop one hold.  The page rejoins the free list (generation
        bumped) when the last holder releases.  Double-release raises —
        a silent no-op would free an aliased page under a live
        session."""
        with self._lock:
            if not (0 < page_id < self.num_pages) \
                    or self._ref[page_id] <= 0:
                raise KvPageError(
                    f"double/stale kv device page free (page "
                    f"{page_id})")
            self._ref[page_id] -= 1
            if self._ref[page_id] == 0:
                self._gen[page_id] += 1
                self._free.append(page_id)

    def release_all(self, pages) -> None:
        for p in pages:
            self.release(p)

    # -- generation / stats ------------------------------------------------

    def gen_of(self, page_id: int) -> int:
        with self._lock:
            return self._gen[page_id]

    def refcount(self, page_id: int) -> int:
        with self._lock:
            return self._ref[page_id]

    def in_use(self) -> int:
        with self._lock:
            return self.num_pages - 1 - len(self._free)

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def _note_peak_locked(self) -> None:
        used = self.num_pages - 1 - len(self._free)
        if used > self.peak_in_use:
            self.peak_in_use = used

    def stats(self) -> Dict[str, int]:
        with self._lock:
            used = self.num_pages - 1 - len(self._free)
            return {"pages": self.num_pages,
                    "page_tokens": self.page_tokens,
                    "in_use": used,
                    "free": len(self._free),
                    "peak_in_use": self.peak_in_use,
                    "alloc_failures": self.alloc_failures,
                    "bytes_in_use": used * self.page_bytes}


class WindowTable:
    """The block table of the SECOND page class: the pages of layers
    that attend a window of the context (``LMConfig.windows``).

    A global layer's page lives as long as its session; a window
    layer's only until every position in it lies behind the window.
    So this class has an allocator of its own (over pools of its own,
    with a garbage page 0 of their own) and a table that MOVES:
    :meth:`cover` is told the positions a slot's next program reaches
    (``first..last``), gives back the pages wholly behind ``first``
    (their entries read 0 again) and takes fresh ones up to ``last``'s.
    A slot therefore holds at most ``window // page + 2`` pages while it
    decodes (the window, the page its head lies in, the page being
    written), and one span more while its prompt is filled.

    Giving a page back while a program that reads it is still queued
    is safe for the reason a finished session's pages are: the device
    runs programs in order, and whoever gets the page next writes it in
    a later one.  Each page's generation is kept beside its id, so a
    test can show that no entry outlives its page."""

    def __init__(self, alloc: PageAllocator, slots: int, pps: int):
        import numpy as np      # not at import: the transport plane's
        #                         importers of this module stay cheap
        self.alloc = alloc
        self.page = alloc.page_tokens
        self.bt = np.zeros((slots, pps), np.int32)
        self.gen = np.zeros((slots, pps), np.int64)
        # entries lo[s] <= i < hi[s] of row s are live
        self.lo = [0] * slots
        self.hi = [0] * slots
        self.released = 0

    def held(self, slot: int) -> int:
        return self.hi[slot] - self.lo[slot]

    def cover(self, slot: int, first: int, last: int) -> bool:
        """Make row ``slot`` hold exactly the pages of positions
        ``first..last`` and what it already holds between them.  False
        (and nothing taken) where the pool cannot cover it."""
        lo, hi = first // self.page, last // self.page + 1
        row, held_lo, held_hi = self.bt[slot], self.lo[slot], self.hi[slot]
        for i in range(held_lo, min(lo, held_hi)):   # wholly behind first
            self.alloc.release(int(row[i]))
            row[i] = 0
            self.released += 1
        held_lo = max(held_lo, min(lo, held_hi))
        if held_hi <= held_lo:               # nothing held: start at lo
            held_lo = held_hi = lo
        ok = True
        if hi > held_hi:
            pages = self.alloc.alloc(hi - held_hi)
            ok = pages is not None
            if ok:
                row[held_hi:hi] = pages
                self.gen[slot, held_hi:hi] = [self.alloc.gen_of(p)
                                              for p in pages]
                held_hi = hi
        self.lo[slot], self.hi[slot] = held_lo, held_hi
        return ok

    def release_slot(self, slot: int) -> None:
        """The slot's session is gone: every page back (not counted as
        ``released``: that counts what the window let go of)."""
        row = self.bt[slot]
        for i in range(self.lo[slot], self.hi[slot]):
            self.alloc.release(int(row[i]))
        row[:] = 0
        self.lo[slot] = self.hi[slot] = 0

    def check(self, slot: int, first: int, last: int) -> None:
        """Raise unless every page of positions ``first..last`` is held
        by this row at the generation it was taken at."""
        for i in range(first // self.page, last // self.page + 1):
            p = int(self.bt[slot, i])
            if not (self.lo[slot] <= i < self.hi[slot]) or p <= 0 \
                    or self.alloc.refcount(p) <= 0 \
                    or self.alloc.gen_of(p) != self.gen[slot, i]:
                raise KvPageError(
                    f"window page {p} (slot {slot}, entry {i}) read "
                    "after release")


class _PrefixNode:
    __slots__ = ("digest", "page", "gen", "children", "parent", "tick")

    def __init__(self, digest: bytes, page: int, gen: int,
                 parent: Optional["_PrefixNode"], tick: int):
        self.digest = digest
        self.page = page
        self.gen = gen
        self.children: Dict[bytes, "_PrefixNode"] = {}
        self.parent = parent
        self.tick = tick


class PrefixCache:
    """Radix tree over page-granular token-chunk fingerprints.

    Granularity is FULL pages only: a page is cached only once the
    session that wrote it can never write it again (its context's full
    pages — decode writes land at positions >= ctx_len), so aliasing
    needs no copy-on-write and a hit moves ZERO bytes.  The partial
    tail of a context is never shared; a hit's remainder is caught up
    with teacher-forced decode steps, which keeps token identity with
    the uncached path exact by construction.

    Each node fingerprints one page-sized token chunk (chained blake2b,
    so a digest commits to the whole prefix, not just its own chunk),
    holds ONE page id plus the allocator's generation snapshot, and
    takes its own refcount on the page — a cached page cannot return to
    the free list, which is what makes the generation check an
    invariant assertion rather than a race guard.  Eviction is
    leaf-first LRU (a parent is never younger than a live child), so
    the tree stays a valid prefix set under any budget.

    ``state_layers``: the model also carries per-sequence recurrent
    state, which a page of keys does not restore.  Every lookup then
    DECLINES (no pages, nothing covered, ``declined_state`` counted)
    and nothing is ever inserted: such a model never gets a keys-only
    hit.  (Sharing would need state snapshots at page boundaries.)
    """

    def __init__(self, alloc: PageAllocator,
                 budget_pages: Optional[int] = None,
                 state_layers: bool = False):
        self._state_layers = bool(state_layers)
        self.declined_state = 0
        self._alloc = alloc
        self._page = alloc.page_tokens
        self._budget = budget_pages
        self._lock = threading.Lock()
        self._root: Dict[bytes, _PrefixNode] = {}
        self._nodes = 0
        self._tick = 0
        self.hits = 0
        self.partial_hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0

    # -- fingerprints ------------------------------------------------------

    def _digests(self, tokens) -> List[bytes]:
        """Chained per-page digests of the FULL pages of ``tokens``."""
        n_full = len(tokens) // self._page
        out: List[bytes] = []
        prev = b""
        for i in range(n_full):
            chunk = tokens[i * self._page:(i + 1) * self._page]
            payload = struct.pack(f"<{self._page}q",
                                  *(int(t) for t in chunk))
            prev = hashlib.blake2b(prev + payload,
                                   digest_size=16).digest()
            out.append(prev)
        return out

    # -- lookup (takes refs) -----------------------------------------------

    def lookup(self, ctx_tokens) -> Tuple[List[int], int]:
        """Longest cached prefix of ``ctx_tokens``.  Returns
        ``(pages, covered_tokens)`` with one reference TAKEN per page —
        the caller owns those holds and must release them with the rest
        of the session's block table.  Counts exactly one of
        prefix_hit / prefix_partial_hit / prefix_miss (or, for a model
        with state layers, ``declined_state`` alone)."""
        if self._state_layers:
            self.declined_state += 1
            return [], 0
        digs = self._digests(ctx_tokens)
        with self._lock:
            self._tick += 1
            matched: List[_PrefixNode] = []
            level = self._root
            for d in digs:
                node = level.get(d)
                if node is None:
                    break
                if self._alloc.gen_of(node.page) != node.gen:
                    # the cache holds a ref, so the generation CANNOT
                    # have moved — this is a double-release elsewhere
                    raise KvPageError(
                        f"prefix cache generation skew on page "
                        f"{node.page}")
                node.tick = self._tick
                matched.append(node)
                level = node.children
            pages = [n.page for n in matched]
            for p in pages:
                self._alloc.ref(p)
        if digs and len(matched) == len(digs):
            self.hits += 1
            count_prefix("prefix_hit")
        elif matched:
            self.partial_hits += 1
            count_prefix("prefix_partial_hit")
        else:
            self.misses += 1
            count_prefix("prefix_miss")
        return pages, len(pages) * self._page

    # -- insert (after an uncached admit's prefill) ------------------------

    def insert(self, ctx_tokens, page_ids) -> int:
        """Cache the full pages of a freshly prefilled context.
        ``page_ids[i]`` must hold chunk ``i``'s KV rows.  Takes one
        cache-owned ref per NEW node; returns how many were new."""
        if self._state_layers:
            return 0
        digs = self._digests(ctx_tokens)
        new = 0
        with self._lock:
            self._tick += 1
            level = self._root
            parent: Optional[_PrefixNode] = None
            for i, d in enumerate(digs):
                node = level.get(d)
                if node is None:
                    page = page_ids[i]
                    self._alloc.ref(page)
                    node = _PrefixNode(d, page,
                                       self._alloc.gen_of(page),
                                       parent, self._tick)
                    level[d] = node
                    self._nodes += 1
                    new += 1
                node.tick = self._tick
                parent = node
                level = node.children
        if new:
            self.inserts += new
            count_prefix("prefix_insert")
            self.evict_to_budget()
        return new

    # -- eviction (leaf-first LRU) -----------------------------------------

    def _leaves_locked(self) -> List[_PrefixNode]:
        leaves: List[_PrefixNode] = []
        stack = list(self._root.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                leaves.append(n)
        return leaves

    def evict_lru(self) -> bool:
        """Drop the least-recently-touched LEAF (parents are never
        younger than a live child, so the tree stays a prefix set)."""
        with self._lock:
            leaves = self._leaves_locked()
            if not leaves:
                return False
            victim = min(leaves, key=lambda n: n.tick)
            siblings = victim.parent.children if victim.parent \
                else self._root
            del siblings[victim.digest]
            self._nodes -= 1
            page = victim.page
        self._alloc.release(page)
        self.evictions += 1
        count_prefix("prefix_evict")
        return True

    def evict_to_budget(self) -> int:
        if self._budget is None:
            return 0
        n = 0
        while self.held_pages() > self._budget and self.evict_lru():
            n += 1
        return n

    def evict_all(self) -> int:
        n = 0
        while self.evict_lru():
            n += 1
        return n

    def held_pages(self) -> int:
        with self._lock:
            return self._nodes

    def stats(self) -> Dict[str, int]:
        return {"nodes": self.held_pages(),
                "hits": self.hits,
                "partial_hits": self.partial_hits,
                "misses": self.misses,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "declined_state": self.declined_state}


# ---------------------------------------------------------------------------
# Host tier — pinned host-RAM pool the cold sessions spill into
# ---------------------------------------------------------------------------

# every live HostPagePool, so the drain plane can count in-flight
# spills without the pools' owners registering anything
_host_pools: "weakref.WeakSet" = weakref.WeakSet()


class HostHandle:
    """One staged page in the host tier (slot + generation + size —
    the shm ring's descriptor shape, host-RAM flavored)."""

    __slots__ = ("slot", "gen", "nbytes")

    def __init__(self, slot: int, gen: int, nbytes: int):
        self.slot = slot
        self.gen = gen
        self.nbytes = nbytes


class HostPagePool:
    """Fixed-slot pinned host-RAM pool for evicted KV pages.

    The shm ring's slot discipline applied to the eviction tier: a
    fixed preallocated buffer (no growth, exhaustion is a NAMED close
    reason), one memcpy per staged page (audited under the
    ``spill_host`` stage), generation-checked handles, and loud
    double-free.  ``begin_spill``/``end_spill`` bracket a whole
    session's spill so the drain plane can count evictions in flight;
    ``drain_abort`` marks the pool dead at drain-grace expiry and
    refuses new spills from then on.
    """

    def __init__(self, slots: int, slot_bytes: int):
        import numpy as np
        self._np = np
        self.slots = int(slots)
        self.slot_bytes = int(slot_bytes)
        self._buf = np.zeros((self.slots, self.slot_bytes),
                             dtype=np.uint8)
        self._lock = threading.Lock()
        self._free = list(range(self.slots))
        self._gen = [0] * self.slots
        self._live = [False] * self.slots
        self._inflight = 0
        self._abort_reason: Optional[str] = None
        self.staged = 0
        self.fetched = 0
        self.peak_slots_used = 0
        _host_pools.add(self)

    # -- spill bracketing (the drain gauge) --------------------------------

    def begin_spill(self) -> bool:
        """Open one spill bracket; False once the pool is aborted (the
        caller must close the session under the abort reason)."""
        with self._lock:
            if self._abort_reason is not None:
                return False
            self._inflight += 1
            return True

    def end_spill(self) -> None:
        with self._lock:
            self._inflight -= 1
            assert self._inflight >= 0, "unbalanced kv spill bracket"

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def drain_abort(self, reason: str) -> None:
        assert reason in KV_EVICT_REASONS, reason
        with self._lock:
            self._abort_reason = reason

    def abort_reason(self) -> Optional[str]:
        with self._lock:
            return self._abort_reason

    # -- stage / fetch / free ----------------------------------------------

    def stage(self, src) -> Optional[HostHandle]:
        """Land one page's bytes in a slot — the tier's ONE memcpy per
        page.  ``src`` is a host uint8 view (<= slot_bytes).  None when
        the tier is full (the caller closes under
        ``kv_host_tier_full``)."""
        nb = src.nbytes
        if nb > self.slot_bytes:
            raise KvPageError(
                f"kv spill page of {nb} bytes exceeds host slot "
                f"({self.slot_bytes})")
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._gen[slot] += 1
            gen = self._gen[slot]
            self._live[slot] = True
            used = self.slots - len(self._free)
            if used > self.peak_slots_used:
                self.peak_slots_used = used
        self._np.copyto(self._buf[slot, :nb],
                        src.reshape(-1).view(self._np.uint8))
        from ..butil import copy_audit
        if copy_audit.enabled and nb >= copy_audit.AUDIT_FLOOR:
            copy_audit.record("spill_host", nb)
        with self._lock:
            self.staged += 1
        return HostHandle(slot, gen, nb)

    def fetch(self, h: HostHandle):
        """Read a staged page back (generation-checked view — the
        caller devices-put it and then frees the slot)."""
        with self._lock:
            if not (0 <= h.slot < self.slots) \
                    or not self._live[h.slot] \
                    or self._gen[h.slot] != h.gen:
                raise KvPageError(
                    f"stale kv host fetch (slot {h.slot} gen {h.gen})")
            self.fetched += 1
        return self._buf[h.slot, :h.nbytes]

    def free(self, h: HostHandle) -> None:
        with self._lock:
            if not (0 <= h.slot < self.slots) \
                    or not self._live[h.slot] \
                    or self._gen[h.slot] != h.gen:
                raise KvPageError(
                    f"double/stale kv host free (slot {h.slot} gen "
                    f"{h.gen})")
            self._live[h.slot] = False
            self._free.append(h.slot)

    def slots_free(self) -> int:
        with self._lock:
            return len(self._free)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"slots": self.slots,
                    "slot_bytes": self.slot_bytes,
                    "free": len(self._free),
                    "inflight": self._inflight,
                    "staged": self.staged,
                    "fetched": self.fetched,
                    "peak_slots_used": self.peak_slots_used}


def host_inflight_spills() -> int:
    """Host-tier spills currently in flight across every live pool —
    the drain plane's second gauge (0 when no host tier exists)."""
    return sum(pool.inflight() for pool in list(_host_pools))
