"""Automatic prefix cache: a token-id radix tree over the paged KV pool.

Production traffic is dominated by shared prefixes — system prompts,
few-shot templates, multi-turn conversations re-sending their history.
The paged KV pool (serving/paging.py) stores KV at page granularity
precisely so those prefixes can be SHARED: the KV vector written for
position p is a deterministic function of tokens[0..p], so any two
requests whose token ids agree on [0, L) can point their page tables at
the same physical pages for those positions and skip prefilling them.

Structure
---------
A radix tree over page-aligned token spans. Each node's edge covers
exactly one FULL page: `page_size` consecutive token ids mapped to one
page id in the shared per-layer pools; a path root->node spells a
page-aligned token prefix and the list of page ids holding its KV.
Children are keyed by the next page's token ids (exact-match dict hop),
so a lookup costs O(prompt_len / page_size) dict probes. Divergence
inside a page is NOT shared at page granularity — two prompts that
split mid-page get separate pages — which is what keeps sharing free of
partial-page aliasing.

Leaves may additionally carry PARTIAL pages: a page whose first
`len(tokens) < page_size` positions are valid (the tail of a finished
request). A new prompt that matches into a partial page (or into the
head of a full page) cannot attach it directly — the request will keep
writing KV into that page's remaining positions — so the match is
granted COPY-ON-WRITE: the engine allocates a fresh page, performs one
single-page device copy, and the page table points at the private copy.
A shared page is never written through.

Lifecycle
---------
- `acquire(prompt, max_new)` — admission: longest-prefix match, then
  refcount++ the matched full pages (zero prefill work, zero copies),
  allocate the fresh tail (evicting LRU unreferenced leaves first under
  page pressure), and return a `PrefixGrant` with the page-table order
  and the number of cached tokens. Refusal (even after eviction) has no
  side effects — admission backpressure degrades to exactly the
  cache-off behavior.
- `insert(tokens, pages, valid)` — retirement of a normally finished
  request: its full pages become tree nodes (the partial tail page a
  partial leaf) so multi-turn follow-ups hit; pages already in the tree
  are deduplicated (the request's duplicate copy is freed). All of the
  request's references are dropped; pages that hit refcount 0 are
  PARKED as cache-resident rather than freed.
- `release(pages)` — retirement of cancelled/aborted/timed-out
  requests: refcount--; tree pages park, private pages free.
- `spill(need)` — the HOST-RAM tier (stage 1 of the ROADMAP's
  fleet-scale prefix cache): under page pressure, unreferenced parked
  pages are SPILLED to host memory before anything is dropped — the
  device page frees (PagePool.swap_out), the node stays in the tree
  with a host slot instead of a device page, and a later match
  RESTORES it (swap-in into a freshly allocated page) instead of
  re-prefilling. Wired by the engine via `set_host_tier`; without it
  spill is a no-op and eviction behaves exactly as before.
- `evict(need)` — leaf-to-root LRU: only unreferenced leaves (and
  partial pages) are freed, oldest last-use first; a node referenced by
  any running request is never touched. Eviction happens inside
  `acquire` AFTER spilling and before admission backpressure, so a
  cold or thrashing cache behaves exactly like no cache at all.

The compiled decode/prefill programs never see any of this: hits, COW
and eviction only change which page ids the host page tables carry.

Fleet fabric (serving/fabric.py) extends the same tree across
replicas: `collect_chain`/`graft` move one committed page chain
between two trees (disaggregated prefill handoff), `snapshot`/`load`
move the WHOLE tree across an engine restart (warm deploys), and
`fingerprints` summarizes the tree as hashed page-aligned prefixes
for the router's affinity ranking. All of them speak the engine's
opaque page payloads (`_extract_page` blocks) — the tree never looks
inside a page.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .paging import PagePool, TRASH_PAGE, pages_needed

__all__ = ["RadixPrefixCache", "PrefixGrant",
           "resolve_prefix_cache_flag", "shared_prefix_groups"]


def shared_prefix_groups(page_tables, q_len):
    """Prefix-sharing groups for one engine step (the grouped-walk
    operands of `ragged_paged_attention_grouped`): rows whose page
    tables carry IDENTICAL page ids for a leading span are attending
    the same physical pages — the radix cache attached them — and the
    kernel can stream that span once per group instead of once per
    row.

    `page_tables` is the host page table [S, max_pages] int32 (trash
    page 0 marks unallocated entries), `q_len` [S] the step's per-row
    live query counts (rows at q_len 0 idle this step and are never
    grouped). Rows are partitioned by recursive refinement: all rows
    sharing page 0, split at the first column where they diverge (a
    mid-span COW page is private by construction, so the COW'd row
    falls out of the group exactly at its divergence point). Returns
    (group_id [S], group_leader [S], group_cnt [S]) int32 — row ->
    group, group -> representative row, group -> shared page count
    (0 for singletons; group ids are compact but arbitrary). Shared
    pages always hold committed KV at or below every member's pos (a
    prefix match never exceeds the prompt), which is the operand
    contract the two-phase kernel assumes."""
    pt = np.asarray(page_tables)
    q_len = np.asarray(q_len)
    S, mp = pt.shape
    group_id = np.arange(S, dtype=np.int32)
    group_leader = np.zeros(S, dtype=np.int32)
    group_cnt = np.zeros(S, dtype=np.int32)
    next_gid = [0]

    def close(rows, depth):
        g = next_gid[0]
        next_gid[0] += 1
        for r in rows:
            group_id[r] = g
        group_leader[g] = rows[0]
        group_cnt[g] = depth if len(rows) >= 2 else 0

    def best(rows, depth):
        """Best grouping of `rows` (which share pages [0, depth)):
        either keep them ONE group closed at this depth, or split at
        the first divergence and group the sub-buckets deeper —
        whichever saves more page reads ((members - 1) * shared_span
        per group). Returns (savings, [(rows, span), ...])."""
        if len(rows) == 1:
            return 0, [(rows, 0)]
        if depth >= mp:
            return (len(rows) - 1) * depth, [(rows, depth)]
        buckets: Dict[int, List[int]] = {}
        for r in rows:
            buckets.setdefault(int(pt[r, depth]), []).append(r)
        if len(buckets) == 1:
            page = next(iter(buckets))
            if page != TRASH_PAGE:
                return best(rows, depth + 1)   # still together
            return (len(rows) - 1) * depth, [(rows, depth)]
        keep = (len(rows) - 1) * depth         # one group, close here
        split_sav, split_plan = 0, []
        for page, sub in sorted(buckets.items()):
            if page == TRASH_PAGE:
                s, p = ((len(sub) - 1) * depth, [(sub, depth)])
            elif len(sub) == 1:
                s, p = 0, [(sub, 0)]
            else:
                s, p = best(sub, depth + 1)
            split_sav += s
            split_plan.extend(p)
        if keep >= split_sav:
            return keep, [(rows, depth)]
        return split_sav, split_plan

    live = [r for r in range(S)
            if q_len[r] > 0 and pt[r, 0] != TRASH_PAGE]
    buckets: Dict[int, List[int]] = {}
    for r in live:
        buckets.setdefault(int(pt[r, 0]), []).append(r)
    for page, rows in sorted(buckets.items()):
        if len(rows) == 1:
            close(rows, 0)
        else:
            _, plan = best(rows, 1)
            for sub, span in plan:
                close(sub, span)
    live_set = set(live)
    for r in range(S):
        if r not in live_set:
            g = next_gid[0]
            next_gid[0] += 1
            group_id[r] = g
            group_leader[g] = r
            group_cnt[g] = 0
    return group_id, group_leader, group_cnt


def resolve_prefix_cache_flag(override=None) -> bool:
    """Whether the engine runs the automatic prefix cache: an explicit
    `ServingEngine(prefix_cache=...)` wins; otherwise the
    PADDLE_TPU_PREFIX_CACHE env var (default on)."""
    import os
    if override is not None:
        if isinstance(override, bool):
            return override
        flag = str(override)
    else:
        flag = os.environ.get("PADDLE_TPU_PREFIX_CACHE", "on")
    low = flag.strip().lower()
    if low in ("on", "1", "true", "yes"):
        return True
    if low in ("off", "0", "false", "no"):
        return False
    raise ValueError(
        "PADDLE_TPU_PREFIX_CACHE / prefix_cache must be on|off, "
        f"got {flag!r}")


class _Node:
    """One radix edge: a full page of `page_size` token ids. A node
    whose content was spilled to the host tier keeps `page=None` and a
    `host` slot id until a match restores it."""

    __slots__ = ("tokens", "page", "parent", "children", "partials",
                 "last_used", "host", "pin_until")

    def __init__(self, tokens: Optional[np.ndarray], page: Optional[int],
                 parent: Optional["_Node"]):
        self.tokens = tokens          # int64 [page_size]; None at root
        self.page = page              # pool page id; None at root/spilled
        self.parent = parent
        self.children: Dict[bytes, "_Node"] = {}
        self.partials: List["_Partial"] = []
        self.last_used = 0
        self.host = None              # host-tier slot id when spilled
        self.pin_until = 0.0          # session-pin TTL deadline (clock)


class _Partial:
    """A leaf-only partially filled page: positions [0, len(tokens))
    of `page` hold valid KV for `tokens` (< page_size of them)."""

    __slots__ = ("tokens", "page", "last_used")

    def __init__(self, tokens: np.ndarray, page: int):
        self.tokens = tokens
        self.page = page
        self.last_used = 0


@dataclass
class PrefixGrant:
    """Everything the engine needs to admit a cache-hit request:
    `pages` in page-table order (shared fulls, then the COW copy if
    any, then fresh tail pages), the prefill cursor start
    (`cached_len`), and the pending single-page COW copy. `cow_src`
    stays refcount-protected until the engine reports the copy done
    via `RadixPrefixCache.cow_done`."""

    pages: List[int]
    cached_len: int
    cow_src: Optional[int] = None
    cow_dst: Optional[int] = None
    matched_full_pages: int = 0
    fresh_pages: List[int] = field(default_factory=list)


def _tok(seq) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(seq).reshape(-1),
                                dtype=np.int64)


def _common_prefix(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.size, b.size)
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


class RadixPrefixCache:
    """Radix-tree prefix cache over one engine's `PagePool`.

    Single-threaded by construction, like everything else that touches
    page tables: the engine calls it only between compiled steps.
    """

    def __init__(self, pool: PagePool, page_size: int, clock=None):
        self.pool = pool
        self.page_size = int(page_size)
        # injectable clock for the session-pin TTL tier (tests drive
        # expiry deterministically; the engine passes its own clock)
        self._clock = clock if clock is not None else time.monotonic
        self.root = _Node(None, None, None)
        # TENANT ISOLATION (multi-tenant LoRA serving): the tree is
        # namespaced by adapter id — KV written under adapter i is a
        # function of (tokens, adapter i's weights), so an identical
        # prompt under adapter j must MISS it. One root per adapter
        # id; adapter 0 (the base model) keeps the classic root.
        self._roots: Dict[int, _Node] = {0: self.root}
        # page id -> owning _Node/_Partial, for release() routing and
        # O(1) "is this page tree-resident"
        self._owner: Dict[int, object] = {}
        self._tick = itertools.count(1)
        # counters (mirrored into ServingMetrics at step boundaries)
        self.lookups = 0
        self.hits = 0
        self.cached_tokens_total = 0
        self.evicted_pages_total = 0
        self.cow_copies_total = 0
        self.inserted_pages_total = 0
        self.spilled_pages_total = 0
        self.restored_pages_total = 0
        # host tier callbacks (engine-wired; None = no host tier):
        # _host_store(pages) -> host slots of the first len(slots) of
        # them, fewer than asked when the tier is full (copies the
        # device pages' KV to host RAM, all pages of one spill in one
        # call; the cache then swap_out's each page it has a slot for),
        # _host_load(host_slot) -> device page or None (allocates a
        # fresh page, restores into it, returns it PARKED cache-
        # resident), _host_drop(host_slot) (discard a spilled page's
        # host copy — evicted from the tree while swapped),
        # _spill_walk(need) -> context manager around one spill's walk
        # of the tree (the engine's `serving::spill` span and account,
        # the same as the pages' copy)
        self._host_store = None
        self._host_load = None
        self._host_drop = None
        self._spill_walk = contextlib.nullcontext
        self._n_spilled = 0

    # -- introspection -----------------------------------------------------
    @property
    def tree_pages(self) -> int:
        """Pages the radix tree currently indexes (referenced or
        cache-resident)."""
        return len(self._owner)

    @property
    def spilled_nodes(self) -> int:
        """Tree nodes whose page currently lives in the host tier."""
        return self._n_spilled

    def set_host_tier(self, store, load, drop,
                      spill_walk=contextlib.nullcontext):
        """Wire the host-RAM page tier (engine callbacks — see the
        attribute docs in __init__). With these set, page pressure
        SPILLS parked pages to host before evicting, and a match on a
        spilled node swap-ins instead of falling back to prefill."""
        self._host_store = store
        self._host_load = load
        self._host_drop = drop
        self._spill_walk = spill_walk

    @property
    def pinned_pages(self) -> int:
        """Device-resident tree pages currently under an unexpired
        session pin (the `prefix_pinned_pages` gauge)."""
        now = self._clock()
        count = 0
        stack = list(self._roots.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page is not None and node.pin_until > now:
                count += 1
        return count

    def stats(self) -> dict:
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "cached_tokens": self.cached_tokens_total,
            "evicted_pages": self.evicted_pages_total,
            "cow_copies": self.cow_copies_total,
            "inserted_pages": self.inserted_pages_total,
            "spilled_pages": self.spilled_pages_total,
            "restored_pages": self.restored_pages_total,
            "spilled_nodes": self._n_spilled,
            "pinned_pages": self.pinned_pages,
            "tree_pages": self.tree_pages,
            "resident_pages": self.pool.cached_pages,
            "hit_rate": (self.hits / self.lookups) if self.lookups
            else None,
        }

    def _touch(self, obj):
        obj.last_used = next(self._tick)

    def _root_for(self, adapter_id: int) -> _Node:
        """The adapter's namespace root (created on first use —
        adapter id joins the match key, so tenant A's pages are
        unreachable from tenant B's walks by construction)."""
        root = self._roots.get(int(adapter_id))
        if root is None:
            root = self._roots[int(adapter_id)] = _Node(None, None,
                                                        None)
        return root

    # -- matching ----------------------------------------------------------
    def _match_full(self, tok: np.ndarray, limit: int, acquire: bool
                    = True, root: Optional[_Node] = None
                    ) -> Tuple[_Node, List[int], int]:
        """Walk full-page edges: returns (last node, matched page ids,
        matched token count). Only whole pages match here; `limit`
        caps the match so at least one prompt token always prefills
        (the sampler needs the last token's logits). With `acquire`
        (the reservation path) each matched page is RETAINED as it is
        walked — so the restore/spill machinery below can never touch
        the match in progress — and a SPILLED node on the path is
        RESTORED from the host tier (swap-in into a fresh device
        page, spilling another LRU page to make room if needed); if
        restore fails (host tier gone / truly no page) the walk stops
        there and the tail simply prefills. `acquire=False` (the
        side-effect-free lookup probe) counts spilled spans as
        matchable without touching anything."""
        ps = self.page_size
        node, pages, depth = (self.root if root is None else root,
                              [], 0)
        while depth + ps <= limit:
            child = node.children.get(tok[depth:depth + ps].tobytes())
            if child is None:
                break
            if child.page is None:            # spilled to host
                if not acquire:
                    node = child
                    depth += ps
                    continue
                if not self._restore(child):
                    break
            node = child
            if acquire:
                self.pool.retain([child.page])
            pages.append(child.page)
            depth += ps
            self._touch(child)
        return node, pages, depth

    def _restore(self, node: _Node) -> bool:
        """Swap a spilled node's page back in from the host tier. The
        engine's load callback returns the restored device page
        already PARKED (cache-resident, refcount 0) so the caller's
        retain path treats it exactly like any other tree page."""
        if self._host_load is None:
            return False
        page = self._host_load(node.host)
        if page is None:
            return False
        node.page = page
        node.host = None
        self._owner[page] = node
        self._n_spilled -= 1
        self.restored_pages_total += 1
        return True

    def _best_tail(self, node: _Node, tail: np.ndarray
                   ) -> Tuple[int, Optional[int]]:
        """Best copy-on-write candidate below `node` for the remaining
        (sub-page) prompt tokens: a partial leaf or the head of a full
        child page sharing the longest prefix with `tail`. Returns
        (matched token count, source page id)."""
        best_k, best_page, best_obj = 0, None, None
        for part in node.partials:
            k = _common_prefix(tail, part.tokens)
            if k > best_k:
                best_k, best_page, best_obj = k, part.page, part
        for child in node.children.values():
            if child.page is None:
                continue      # spilled: not a COW source on device
            k = _common_prefix(tail, child.tokens)
            if k > best_k:
                best_k, best_page, best_obj = k, child.page, child
        if best_obj is not None:
            self._touch(best_obj)
        return best_k, best_page

    def lookup(self, prompt, adapter_id: int = 0) -> int:
        """Side-effect-free probe: how many tokens of `prompt` the
        cache could serve right now (full pages — device or spilled —
        plus the best COW tail) within `adapter_id`'s namespace."""
        tok = _tok(prompt)
        limit = max(0, tok.size - 1)
        node, _, depth = self._match_full(
            tok, limit, acquire=False, root=self._root_for(adapter_id))
        k, _ = self._best_tail(node, tok[depth:limit])
        return depth + k

    # -- admission ---------------------------------------------------------
    def acquire(self, prompt, max_new_tokens: int,
                adapter_id: int = 0) -> Optional[PrefixGrant]:
        """Longest-prefix match + page reservation for one request.
        On success every page in the grant holds one reference for the
        request (shared pages refcount++, fresh pages refcount 1, the
        COW source an extra protection ref until `cow_done`). On
        refusal — only when even evicting every unreferenced cached
        page cannot cover the fresh tail — nothing changed."""
        ps = self.page_size
        tok = _tok(prompt)
        plen = tok.size
        self.lookups += 1
        limit = plen - 1        # >= 1 token must prefill for logits
        node, shared, depth = self._match_full(
            tok, limit, root=self._root_for(adapter_id))
        cow_k, cow_src = self._best_tail(node, tok[depth:limit])
        total = pages_needed(plen, max_new_tokens, ps)
        need_fresh = total - len(shared)
        # the matched pages are already retained (the walk retains as
        # it goes, protecting them from the spill/eviction below and
        # from later admissions at this same boundary); only the COW
        # source still needs its protection reference
        if cow_src is not None:
            self.pool.retain([cow_src])
        fresh = self.pool.alloc(need_fresh)
        if fresh is None:
            # page pressure: SPILL parked pages to the host tier first
            # (their KV survives, a later match swap-ins instead of
            # re-prefilling), then EVICT whatever pressure remains
            short = need_fresh - self.pool.free_pages
            short -= self.spill(short)
            if short > 0:
                self.evict(short)
            fresh = self.pool.alloc(need_fresh)
        if fresh is None and cow_src is not None:
            # the COW claim can be the very page blocking admission: a
            # request whose budget spans the whole pool retains its
            # COW source, which spill/evict then must skip — a
            # permanent self-deadlock at the queue head. A partial-
            # page match is never worth a refusal: forfeit the claim
            # (the page parks, becoming spillable/evictable again) and
            # admit with the shorter full-page match instead.
            self.release([cow_src])
            cow_src, cow_k = None, 0
            short = need_fresh - self.pool.free_pages
            short -= self.spill(short)
            if short > 0:
                self.evict(short)
            fresh = self.pool.alloc(need_fresh)
        if fresh is None:
            # roll back: the match returns to exactly its prior state
            self.release(shared)
            return None
        cached = depth + cow_k
        if cached:
            self.hits += 1
            self.cached_tokens_total += cached
        grant = PrefixGrant(
            pages=shared + fresh, cached_len=cached,
            matched_full_pages=len(shared), fresh_pages=fresh)
        if cow_src is not None:
            self.cow_copies_total += 1
            grant.cow_src = cow_src
            # the fresh page covering page index len(shared) — the one
            # the table points at for the partially-cached span
            grant.cow_dst = fresh[0]
        return grant

    def cow_done(self, grant: PrefixGrant):
        """The engine finished the single-page device copy: drop the
        COW source's protection reference."""
        if grant.cow_src is not None:
            self.release([grant.cow_src])
            grant.cow_src = None

    # -- retirement --------------------------------------------------------
    def release(self, pages: List[int]):
        """Drop one reference per page; pages that hit refcount 0 park
        (tree-resident) or free (private)."""
        zeroed = self.pool.release(pages)
        park = [p for p in zeroed if p in self._owner]
        if park:
            self.pool.park(park)
        gone = [p for p in zeroed if p not in self._owner]
        if gone:
            self.pool.free(gone)

    def insert(self, tokens, pages: List[int], valid: int,
               adapter_id: int = 0):
        """Index a finished request's written pages so future prompts
        hit — within `adapter_id`'s namespace: the KV is a function
        of the adapter's weights too, so tenants never see each
        other's pages. `tokens` is its prompt + generated ids,
        `valid` how many positions actually hold KV (prompt_len +
        emitted tokens); trailing unconsumed budget pages are simply
        freed. Duplicates (another request cached the same span
        first) are freed, the tree keeps its original. Finally drops
        ALL of the request's page references."""
        ps = self.page_size
        tok = _tok(tokens)
        valid = int(valid)
        if valid > tok.size or valid > len(pages) * ps:
            raise ValueError(
                f"valid={valid} exceeds tokens ({tok.size}) or page "
                f"capacity ({len(pages) * ps})")
        node = self._root_for(adapter_id)
        n_full = valid // ps
        for i in range(n_full):
            span = tok[i * ps:(i + 1) * ps]
            key = span.tobytes()
            child = node.children.get(key)
            if child is None:
                page = pages[i]
                child = _Node(np.array(span), page, node)
                node.children[key] = child
                self._owner[page] = child
                self.inserted_pages_total += 1
            node = child
            self._touch(node)
        rem = valid - n_full * ps
        if rem > 0:
            ptoks = np.array(tok[n_full * ps:valid])
            page = pages[n_full]
            if page not in self._owner and self._tail_is_new(node, ptoks):
                part = _Partial(ptoks, page)
                node.partials.append(part)
                self._owner[page] = part
                self.inserted_pages_total += 1
                self._touch(part)
        self.release(pages)

    def _tail_is_new(self, node: _Node, ptoks: np.ndarray) -> bool:
        """A partial tail is worth keeping only if no resident page
        already covers it (an equal-or-longer partial, or a full child
        whose head matches)."""
        for part in node.partials:
            if part.tokens.size >= ptoks.size and \
                    _common_prefix(part.tokens, ptoks) == ptoks.size:
                return False
        for child in node.children.values():
            if _common_prefix(child.tokens, ptoks) == ptoks.size:
                return False
        return True

    # -- session pinning ---------------------------------------------------
    def _pinned(self, node: _Node) -> bool:
        return node.pin_until > self._clock()

    def pin(self, tokens, ttl_s: float, adapter_id: int = 0) -> int:
        """Session pinning: hold the full-page chain covering `tokens`
        in a TTL tier between "referenced" and "evictable" — pinned
        pages are skipped by LRU eviction AND host-tier spill until
        the deadline passes, so a chat session's turn-2 follow-up hits
        warm device KV by contract, not by LRU luck. Re-pinning
        extends the deadline (max, never shortens); an EXPIRED pin
        needs no sweep — `_pinned` compares against the injectable
        clock, so the node simply becomes ordinary LRU fodder again.
        Returns the number of pages pinned."""
        if ttl_s <= 0:
            return 0
        deadline = self._clock() + float(ttl_s)
        tok = _tok(tokens)
        ps = self.page_size
        node = self._root_for(adapter_id)
        pinned = 0
        for i in range(tok.size // ps):
            child = node.children.get(tok[i * ps:(i + 1) * ps].tobytes())
            if child is None:
                break
            child.pin_until = max(child.pin_until, deadline)
            self._touch(child)
            pinned += 1
            node = child
        return pinned

    # -- spill (host tier) -------------------------------------------------
    def spill(self, need: int) -> int:
        """Move up to `need` unreferenced parked FULL pages to the
        host tier, LRU first: the device page frees
        (PagePool.swap_out) but the tree node survives with a host
        slot — a later match restores it instead of re-prefilling.
        Any node (leaf or interior) may spill; only its PAGE moves,
        the tree structure stays walkable. Returns the number of
        device pages actually freed (0 without a wired host tier)."""
        if need <= 0 or self._host_store is None:
            return 0
        found = []
        with self._spill_walk(need):    # the walk of the whole tree
            stack = list(self._roots.values())   # every tenant namespace
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if (node.tokens is not None and node.page is not None
                        and self.pool.refcount(node.page) == 0
                        and not self._pinned(node)):
                    found.append((node.last_used, id(node), node))
            victims = [node for _, _, node
                       in heapq.nsmallest(need, found)]
        if not victims:
            return 0
        # one call for the whole spill: the tier answers with the
        # slots of as many pages, from the front, as it has room for
        slots = self._host_store([node.page for node in victims])
        for node, slot in zip(victims, slots):
            self.pool.swap_out([node.page], spill=True)
            del self._owner[node.page]
            node.host = slot
            node.page = None
            self._n_spilled += 1
            self.spilled_pages_total += 1
        return len(slots)

    # -- eviction ----------------------------------------------------------
    def _evictable(self, obj) -> bool:
        if isinstance(obj, _Partial):
            return self.pool.refcount(obj.page) == 0
        if obj.children or obj.partials:
            return False
        if self._pinned(obj):
            return False      # session-pinned: TTL tier, not LRU
        if obj.page is None:
            return True       # spilled leaf: only a host copy to drop
        return self.pool.refcount(obj.page) == 0

    def evict(self, need: int) -> int:
        """Free at least `need` unreferenced cached pages, LRU leaves
        first, walking leaf-to-root as parents become childless. Pages
        referenced by running requests are never touched. Returns the
        number of pages actually freed."""
        if need <= 0:
            return 0
        # seed the heap with every current leaf candidate (across
        # every tenant namespace — eviction is global LRU; isolation
        # is a MATCHING property, not a placement one)
        heap = []
        stack = list(self._roots.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            for part in node.partials:
                heapq.heappush(heap, (part.last_used, id(part), part,
                                      node))
            if node.tokens is not None and self._evictable(node):
                heapq.heappush(heap, (node.last_used, id(node), node,
                                      node.parent))
        freed = 0
        while freed < need and heap:
            _, _, obj, parent = heapq.heappop(heap)
            if isinstance(obj, _Partial):
                if obj not in parent.partials or \
                        self.pool.refcount(obj.page) != 0:
                    continue
                parent.partials.remove(obj)
            else:
                if obj.parent is None or not self._evictable(obj) or \
                        parent.children.get(obj.tokens.tobytes()) is not obj:
                    continue
                del parent.children[obj.tokens.tobytes()]
                obj.parent = None
            if getattr(obj, "page", None) is None:
                # spilled node: only its host copy exists — drop it.
                # Frees no device page, but may unblock the parent.
                self._host_drop(obj.host)
                obj.host = None
                self._n_spilled -= 1
            else:
                del self._owner[obj.page]
                self.pool.free([obj.page])
                self.evicted_pages_total += 1
                freed += 1
            # the parent may have just become an evictable leaf
            # (tokens None = a namespace root, never evictable)
            if parent.tokens is not None and self._evictable(parent):
                heapq.heappush(heap, (parent.last_used, id(parent),
                                      parent, parent.parent))
        return freed

    def clear(self) -> int:
        """Drop every unreferenced cached page — device-resident AND
        spilled (e.g. tests forcing a cold cache). Session pins do
        NOT survive a clear (it is the explicit drop-everything
        escape hatch); referenced nodes do."""
        stack = list(self._roots.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            node.pin_until = 0.0
        return self.evict(self.tree_pages + self._n_spilled)

    # -- fleet fabric (serving/fabric.py) ----------------------------------
    def fingerprints(self, limit: int = 4096) -> set:
        """Hashed summary of every page-aligned prefix this tree can
        serve — the per-replica summary the router ranks prefix
        affinity against. Each full-page edge contributes one CRC
        chained from its ancestors' spans and seeded by the adapter id
        (`fabric.fp_step`/`fp_seed` — byte-identical to the router's
        `prompt_fingerprints` walk over a prompt). Spilled nodes count:
        a match restores them, which is the whole point. BFS so a
        `limit` cap keeps the SHALLOW prefixes — the ones most prompts
        share — when the tree outgrows the summary budget."""
        from collections import deque

        from .fabric import fp_seed, fp_step
        out: set = set()
        queue = deque((root, fp_seed(aid))
                      for aid, root in self._roots.items())
        while queue and len(out) < limit:
            node, fp = queue.popleft()
            for child in node.children.values():
                cfp = fp_step(fp, child.tokens)
                out.add(cfp)
                if len(out) >= limit:
                    break
                queue.append((child, cfp))
        return out

    def collect_chain(self, tokens, adapter_id: int = 0
                      ) -> Tuple[int, List[Tuple[str, int]]]:
        """The resident page chain covering `tokens`' full pages, for
        the transfer path: walks full-page edges WITHOUT acquiring or
        restoring, returning (covered token count, [("page", id) |
        ("host", slot), ...]) — the engine reads device pages with its
        swap-out program and host slots straight from the host pool,
        so a spilled node ships without a device round-trip. Stops at
        the first miss (a transfer is one contiguous chain or
        nothing). Single-threaded like every other tree call: the
        chain stays valid until the next engine step."""
        ps = self.page_size
        tok = _tok(tokens)
        node = self._root_for(adapter_id)
        refs: List[Tuple[str, int]] = []
        depth = 0
        while depth + ps <= tok.size:
            child = node.children.get(tok[depth:depth + ps].tobytes())
            if child is None:
                break
            if child.page is not None:
                refs.append(("page", child.page))
            elif child.host is not None:
                refs.append(("host", child.host))
            else:
                break
            node = child
            depth += ps
            self._touch(child)
        return depth, refs

    def graft(self, tokens, payloads: List, valid: int,
              adapter_id: int = 0, *, alloc_restore) -> int:
        """`insert`'s twin for pages arriving from ANOTHER replica:
        index a transferred chain so the very next `acquire` hits it.
        `payloads` are opaque engine page payloads (one per page of
        `tokens[:valid]`); `alloc_restore(payload)` is the engine
        callback that allocates a device page (spilling/evicting under
        pressure), writes the payload into it, and returns it PARKED —
        or None, which ends the graft at that depth (a partial graft
        is still a valid shorter prefix; the chain property holds
        because grafting proceeds root-ward first). Spans the tree
        already holds are deduplicated without spending a page —
        re-transfer of a popular prefix costs nothing device-side.
        Returns the number of pages actually grafted."""
        ps = self.page_size
        tok = _tok(tokens)
        valid = int(valid)
        if valid > tok.size or valid > len(payloads) * ps:
            raise ValueError(
                f"valid={valid} exceeds tokens ({tok.size}) or "
                f"payload capacity ({len(payloads) * ps})")
        node = self._root_for(adapter_id)
        n_full = valid // ps
        grafted = 0
        for i in range(n_full):
            span = tok[i * ps:(i + 1) * ps]
            key = span.tobytes()
            child = node.children.get(key)
            if child is None:
                page = alloc_restore(payloads[i])
                if page is None:
                    return grafted
                child = _Node(np.array(span), page, node)
                node.children[key] = child
                self._owner[page] = child
                self.inserted_pages_total += 1
                grafted += 1
            node = child
            self._touch(node)
        rem = valid - n_full * ps
        if rem > 0 and n_full < len(payloads) and \
                self._tail_is_new(node, tok[n_full * ps:valid]):
            page = alloc_restore(payloads[n_full])
            if page is not None:
                part = _Partial(np.array(tok[n_full * ps:valid]), page)
                node.partials.append(part)
                self._owner[page] = part
                self.inserted_pages_total += 1
                self._touch(part)
                grafted += 1
        return grafted

    def snapshot(self, extract_page, host_payload=None) -> dict:
        """Serialize the whole tree — structure AND page contents —
        into a plain host-side record for warm restarts. Every node
        (device-resident via `extract_page(page)`, spilled via
        `host_payload(slot)`) becomes one entry {adapter, parent
        index, token span, opaque payload}; parents always precede
        children so `load` rebuilds in one pass. A node whose payload
        is unreachable (host tier dropped it) is skipped WITH its
        subtree — a chain with a hole is not a prefix. Meant for
        quiesced engines (the router snapshots after drain), but only
        reads pages, so a live snapshot is merely a stale one."""
        nodes: List[dict] = []
        for aid, root in sorted(self._roots.items()):
            stack: List[Tuple[object, int]] = [(root, -1)]
            while stack:
                node, pidx = stack.pop()
                if node.tokens is None:
                    midx = -1
                else:
                    if node.page is not None:
                        payload = extract_page(node.page)
                    elif node.host is not None and \
                            host_payload is not None:
                        payload = host_payload(node.host)
                    else:
                        continue
                    if payload is None:
                        continue
                    midx = len(nodes)
                    nodes.append({"adapter": aid, "parent": pidx,
                                  "tokens": np.array(node.tokens),
                                  "payload": payload,
                                  "partial": False})
                for part in node.partials:
                    pay = extract_page(part.page)
                    if pay is not None:
                        nodes.append({"adapter": aid, "parent": midx,
                                      "tokens": np.array(part.tokens),
                                      "payload": pay, "partial": True})
                for child in node.children.values():
                    stack.append((child, midx))
        return {"version": 1, "page_size": self.page_size,
                "nodes": nodes}

    def load(self, snap: dict, *, alloc_restore) -> int:
        """Rebuild a `snapshot` into THIS tree (typically empty — a
        fresh engine warming from its predecessor), parent-first, with
        the same `alloc_restore` contract and dedup as `graft`. An
        entry whose page cannot be allocated is dropped with its
        descendants (they never find their parent placed); everything
        restored is parked cache-resident, so the first prompts after
        a deploy hit instead of re-prefilling. Returns pages
        restored."""
        if snap.get("version") != 1:
            raise ValueError(
                f"prefix snapshot version {snap.get('version')!r} "
                "not supported")
        if int(snap.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"prefix snapshot page_size {snap.get('page_size')} "
                f"!= cache page_size {self.page_size}")
        restored = 0
        placed: Dict[int, _Node] = {}
        for i, ent in enumerate(snap["nodes"]):
            pidx = int(ent["parent"])
            if pidx < 0:
                parent = self._root_for(int(ent["adapter"]))
            else:
                parent = placed.get(pidx)
                if parent is None:
                    continue
            toks = _tok(ent["tokens"])
            if ent.get("partial"):
                if not self._tail_is_new(parent, toks):
                    continue
                page = alloc_restore(ent["payload"])
                if page is None:
                    continue
                part = _Partial(np.array(toks), page)
                parent.partials.append(part)
                self._owner[page] = part
                self.inserted_pages_total += 1
                self._touch(part)
                restored += 1
            else:
                key = toks.tobytes()
                child = parent.children.get(key)
                if child is None:
                    page = alloc_restore(ent["payload"])
                    if page is None:
                        continue
                    child = _Node(np.array(toks), page, parent)
                    parent.children[key] = child
                    self._owner[page] = child
                    self.inserted_pages_total += 1
                    restored += 1
                placed[i] = child
                self._touch(child)
        return restored
