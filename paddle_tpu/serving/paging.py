"""Host-side page bookkeeping for the paged KV pool.

The device state is a shared per-layer pool [num_pages, page_size, H, D]
plus a per-slot page table [S, max_pages] (see nlp/generation.py's paged
DecodeCache). This module owns the HOST half: which pages are free
and which belong to which request.

Page 0 is reserved as the TRASH page: it is never handed out, free
slots' page-table rows point every entry at it, and the device scatter
redirects out-of-window writes into it — so membership changes never
reshape or retrace the compiled programs.

Pages are REFERENCE COUNTED so the prefix cache (serving/prefix.py) can
share one physical page between any number of requests plus the radix
tree. Every page is in exactly one of three states:

- FREE      — on the free list, allocatable;
- USED      — refcount >= 1: held by running request(s) and/or
              protected mid-operation (COW source during the copy);
- CACHED    — refcount == 0 but still resident: the page belongs to the
              prefix cache's radix tree and nobody references it right
              now. Cached pages are NOT allocatable; the cache evicts
              (frees) them under page pressure.

A fourth, SWAPPED, state tracks the HOST-RAM tier (graceful overload
degradation): `swap_out(pages)` declares that a page's KV content has
been copied to host memory — the device page returns to the free list
(that is the point: preempting a resident frees HBM) and the pool
counts the outstanding host-resident logical page until either
`swapped_restored` (the content was swapped back into freshly
allocated device pages) or `drop_swapped` (the preempted request died
before resuming and its host copy was discarded). The actual host
bytes live in a `HostPagePool`.

Invariants are enforced, not assumed: double free, freeing a page that
is still shared (refcount > 1), retaining a free page, parking a
referenced page, swapping out a shared or free page, and
over-draining the swapped count all raise. `assert_quiesced()` is the
engine-shutdown leak check: after drain/abort every page must be FREE
or CACHED — and no preempted request's KV may be stranded in the host
tier (swapped count 0).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

__all__ = ["PagePool", "HostPagePool", "TRASH_PAGE", "pages_needed"]

TRASH_PAGE = 0      # reserved: never allocated, absorbs masked writes


class PagePool:
    """Refcounted free-list allocator over page ids 1..num_pages-1
    (0 is trash).

    Allocation is all-or-nothing per request: the scheduler admits a
    request only when its whole page budget is free, so a half-admitted
    request can never wedge the pool. `retain`/`release` move shared
    pages' refcounts for the prefix cache; `park` turns an unreferenced
    page into cache-resident state instead of freeing it.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved trash page)")
        self.num_pages = int(num_pages)
        # LIFO free list: recently freed pages are reused first, which
        # keeps the hot working set of pages small
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self._ref = [0] * self.num_pages
        self._is_cached = [False] * self.num_pages
        self._n_cached = 0
        # logical pages currently living in the host tier (their
        # device pages were freed by swap_out), split by kind: a
        # preempted REQUEST's KV is an obligation that must drain
        # before shutdown, a SPILLED prefix page is legitimate
        # long-lived cache state
        self._n_swapped = 0       # preempted-request pages
        self._n_spilled = 0       # prefix-cache spilled pages

    # -- introspection -----------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Unreferenced-but-resident pages parked by the prefix cache."""
        return self._n_cached

    @property
    def used_pages(self) -> int:
        """Pages referenced by at least one live request."""
        return (self.num_pages - 1) - len(self._free) - self._n_cached

    @property
    def swapped_pages(self) -> int:
        """Outstanding logical pages whose KV lives in the host tier
        (swap_out'ed, not yet restored or dropped), both kinds. Their
        device pages are FREE — this counter tracks the host-side
        obligation."""
        return self._n_swapped + self._n_spilled

    def refcount(self, page: int) -> int:
        self._check_range(page)
        return self._ref[page]

    def is_cached(self, page: int) -> bool:
        self._check_range(page)
        return self._is_cached[page]

    def _check_range(self, p: int):
        if not (0 < p < self.num_pages):
            raise ValueError(f"page id {p} out of range")

    # -- allocation --------------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages at refcount 1, or None (without side effects) if not
        enough free."""
        if n < 0:
            raise ValueError("n must be >= 0")
        if n > len(self._free):
            return None
        taken = self._free[-n:] if n else []
        del self._free[len(self._free) - n:]
        for p in taken:
            self._free_set.discard(p)
            self._ref[p] = 1
        return taken

    # -- sharing (prefix cache) --------------------------------------------
    def retain(self, pages: Iterable[int]):
        """refcount++ on resident pages. A CACHED page leaves the
        cache-resident state (it is referenced again); a FREE page
        cannot be retained — that is a use-after-free."""
        pages = list(pages)
        for p in pages:
            self._check_range(p)
            if p in self._free_set:
                raise ValueError(f"retain of free page {p} "
                                 "(use-after-free)")
        for p in pages:
            if self._is_cached[p]:
                self._is_cached[p] = False
                self._n_cached -= 1
            self._ref[p] += 1

    def release(self, pages: Iterable[int]) -> List[int]:
        """refcount-- on each page; returns the pages that dropped to
        zero. The caller (the prefix cache) decides their fate: `park`
        the tree-resident ones, `free` the rest."""
        pages = list(pages)
        for p in pages:
            self._check_range(p)
            if p in self._free_set or self._ref[p] < 1:
                raise ValueError(f"release of unreferenced page {p}")
        zeroed = []
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                zeroed.append(p)
        return zeroed

    def park(self, pages: Iterable[int]):
        """Mark unreferenced pages cache-resident (the prefix cache's
        LRU pool) instead of freeing them."""
        pages = list(pages)
        for p in pages:
            self._check_range(p)
            if p in self._free_set:
                raise ValueError(f"park of free page {p}")
            if self._ref[p] != 0:
                raise ValueError(f"park of referenced page {p} "
                                 f"(refcount {self._ref[p]})")
            if self._is_cached[p]:
                raise ValueError(f"page {p} already cache-resident")
        for p in pages:
            self._is_cached[p] = True
            self._n_cached += 1

    # -- host-tier swap (overload preemption / prefix spill) ---------------
    def swap_out(self, pages: Iterable[int], spill: bool = False):
        """Declare each page's KV content moved to the host tier: the
        device page returns to the free list (HBM reclaimed — the
        whole point of preemption) and the pool records one
        outstanding SWAPPED logical page per entry. Only a privately
        held page (refcount exactly 1 — a preempted request's own
        page) or a parked cache-resident page (refcount 0, CACHED — a
        spilled prefix page) may swap out; a shared page would be
        swapped out from under its other holders, and swapping a FREE
        page is a double-swap-out / use-after-free. `spill=True`
        marks the page as prefix-cache spill (legitimate long-lived
        cache state) rather than a preempted request's obligation."""
        pages = list(pages)
        for p in pages:
            self._check_range(p)
            if p in self._free_set:
                raise ValueError(
                    f"swap_out of free page {p} (double swap-out or "
                    "use-after-free)")
            if self._ref[p] > 1:
                raise ValueError(
                    f"swap_out of page {p} still shared "
                    f"(refcount {self._ref[p]}); a shared page cannot "
                    "leave the device")
            if self._ref[p] == 0 and not self._is_cached[p]:
                raise ValueError(
                    f"swap_out of unowned page {p} (neither held nor "
                    "cache-resident)")
        for p in pages:
            if self._is_cached[p]:
                self._is_cached[p] = False
                self._n_cached -= 1
            self._ref[p] = 0
            self._free.append(p)
            self._free_set.add(p)
        if spill:
            self._n_spilled += len(pages)
        else:
            self._n_swapped += len(pages)

    def swapped_restored(self, n: int, spill: bool = False):
        """`n` host-resident pages were swapped back in (their content
        restored into freshly allocated device pages): the host-side
        obligation shrinks."""
        self._drain_swapped(n, spill, "restore")

    def drop_swapped(self, n: int, spill: bool = False):
        """`n` host-resident pages were discarded without restore (the
        preempted request was cancelled / timed out / aborted, or a
        spilled prefix page was evicted from the host tier)."""
        self._drain_swapped(n, spill, "drop")

    def _drain_swapped(self, n: int, spill: bool, what: str):
        n = int(n)
        if n < 0:
            raise ValueError("n must be >= 0")
        have = self._n_spilled if spill else self._n_swapped
        if n > have:
            raise ValueError(
                f"{what} of {n} swapped pages but only "
                f"{have} are outstanding")
        if spill:
            self._n_spilled -= n
        else:
            self._n_swapped -= n

    # -- freeing -----------------------------------------------------------
    def free(self, pages: Iterable[int]):
        """Return pages to the free list. Raises on double free and on
        freeing a page some OTHER holder still references (refcount
        > 1): a shared page must be `release`d, never freed through."""
        pages = list(pages)
        for p in pages:
            self._check_range(p)
            if p in self._free_set:
                raise ValueError(f"double free of page {p}")
            if self._ref[p] > 1:
                raise ValueError(
                    f"free of page {p} still referenced "
                    f"(refcount {self._ref[p]}); release shared pages "
                    "instead of freeing through them")
        for p in pages:
            if self._is_cached[p]:
                self._is_cached[p] = False
                self._n_cached -= 1
            self._ref[p] = 0
            self._free.append(p)
            self._free_set.add(p)

    # -- invariants --------------------------------------------------------
    def assert_quiesced(self):
        """Engine-shutdown leak check: every page FREE or CACHED (no
        request reference survived retirement), no preempted REQUEST's
        KV stranded in the host tier (every request-kind SWAPPED page
        restored or dropped — the prefix cache's deliberately SPILLED
        pages are legitimate long-lived cache state and may remain),
        and the accounting closes: free + cached == allocatable pool
        size."""
        leaked = [p for p in range(1, self.num_pages) if self._ref[p] > 0]
        if leaked:
            raise RuntimeError(
                f"page leak: pages {leaked} still referenced after "
                "shutdown (refcounts "
                f"{[self._ref[p] for p in leaked]})")
        if self._n_swapped:
            raise RuntimeError(
                f"host-tier leak: {self._n_swapped} preempted "
                "request page(s) neither restored nor dropped after "
                "shutdown")
        if len(self._free) + self._n_cached != self.num_pages - 1:
            raise RuntimeError(
                f"page accounting broken: free {len(self._free)} + "
                f"cached {self._n_cached} != pool size "
                f"{self.num_pages - 1}")


class HostPagePool:
    """The HOST-RAM page tier: a capacity-bounded store of whole-page
    KV payloads (one opaque array per page — the engine stores
    `[n_layers, 2, page_size, H, D]` blocks).

    This is stage 1 of the ROADMAP's fleet-scale prefix cache: cache /
    preemption capacity becomes host RAM, not HBM. `store` admits a
    payload and returns a host slot id (or None when full — the caller
    falls back to recompute-on-resume or plain eviction); `load`
    returns the payload for swap-in; `free` releases the slot. Slot
    invariants mirror PagePool's: loading or freeing a slot that is
    not live raises (a swap-in of a freed page is a use-after-free,
    never silent garbage).

    `store_pending` admits pages whose bytes are still ON THE DEVICE
    (the engine's gathered copies of them): the slots are live at once,
    `start_pending` sets the copies off towards host RAM, and the
    payloads arrive when the copy is COLLECTED — by a `load` of one of
    its slots or by `collect_pending`, whichever comes first. A slot
    freed before that never gets its payload."""

    def __init__(self, num_pages: int):
        if num_pages < 0:
            raise ValueError("num_pages must be >= 0")
        self.num_pages = int(num_pages)
        self._data: Dict[int, object] = {}
        self._next = 0
        self._free: List[int] = []
        # copies not yet collected, oldest first: each `_data` entry of
        # its slots IS the `_PendingCopy` until then
        self._pending: List[_PendingCopy] = []

    @property
    def used_pages(self) -> int:
        return len(self._data)

    @property
    def free_pages(self) -> int:
        return self.num_pages - len(self._data)

    @property
    def pending_pages(self) -> int:
        """Pages of the copies not yet collected (what they hold on the
        device, freed slots included)."""
        return sum(len(copy.slots) for copy in self._pending)

    @property
    def unstarted_pages(self) -> int:
        """Pages of the pending copies not yet set off."""
        return sum(len(copy.slots) for copy in self._pending
                   if not copy.started)

    def _take_slot(self) -> int:
        if self._free:
            return self._free.pop()
        self._next += 1
        return self._next - 1

    def store(self, payload) -> Optional[int]:
        """Admit one page payload; returns its host slot id, or None
        (no side effects) when the tier is full."""
        if len(self._data) >= self.num_pages:
            return None
        slot = self._take_slot()
        self._data[slot] = payload
        return slot

    def store_pending(self, n: int, start, fetch) -> List[int]:
        """Admit `n` pages of one copy that is yet to cross to host
        RAM; returns their host slots, taken as `n` calls of `store`
        would take them. `start()` sets the copy off without waiting
        for it, and is called once: by `start_pending`, or at
        collection if that comes first; `fetch()` is called once, at
        collection: it blocks until the copy has arrived and returns
        the `n` payloads in slot order. The caller asks
        `free_pages` first: more than fit raises."""
        if n > self.free_pages:
            raise ValueError(
                f"store_pending of {n} pages into {self.free_pages} "
                "free host slots")
        copy = _PendingCopy(start, fetch,
                            [self._take_slot() for _ in range(n)])
        for slot in copy.slots:
            self._data[slot] = copy
        self._pending.append(copy)
        return list(copy.slots)

    def start_pending(self):
        """Set off every copy that has not been started yet."""
        for copy in self._pending:
            copy.start()

    def _collect(self, copy: "_PendingCopy"):
        copy.start()        # a load that comes before `start_pending`
        payloads = copy.fetch()
        self._pending.remove(copy)
        for slot, payload in zip(copy.slots, payloads):
            if self._data.get(slot) is copy:    # not freed meanwhile
                self._data[slot] = payload

    def collect_pending(self):
        """Bring every pending copy into host RAM, oldest first."""
        while self._pending:
            self._collect(self._pending[0])

    def load(self, slot: int):
        """Payload of a live slot (the swap-in read); collects the
        slot's copy first if that is still pending. Raises on a slot
        that was never stored or already freed."""
        if slot not in self._data:
            raise ValueError(
                f"load of dead host page {slot} (swap-in of a freed "
                "page)")
        if isinstance(self._data[slot], _PendingCopy):
            self._collect(self._data[slot])
        return self._data[slot]

    def free(self, slot: int):
        """Release a live slot. Raises on double free."""
        if slot not in self._data:
            raise ValueError(f"double free of host page {slot}")
        del self._data[slot]
        self._free.append(slot)


class _PendingCopy:
    """One device-to-host copy on its way and the host slots its pages
    were given."""
    __slots__ = ("_start", "fetch", "slots")

    def __init__(self, start, fetch, slots: List[int]):
        self._start = start
        self.fetch = fetch
        self.slots = slots

    @property
    def started(self) -> bool:
        return self._start is None

    def start(self):
        """Sets the copy off, the first time it is called."""
        if self._start is not None:
            self._start()
            self._start = None


def pages_needed(prompt_len: int, max_new_tokens: int,
                 page_size: int) -> int:
    """Admission budget: pages covering every position the request can
    legitimately occupy (prompt + full output allowance)."""
    return -(-(int(prompt_len) + int(max_new_tokens)) // int(page_size))


