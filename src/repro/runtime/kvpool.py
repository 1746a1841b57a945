"""Shared paged KV pool — the software shared-L1 for serving slots.

MemPool's defining choice is that 256 PEs share one global, multi-banked
L1 scratchpad instead of owning private slices (arXiv 2303.17742); a
core's working set lives wherever a bank is free, and the interconnect
makes every bank one hop away. The serving analogue built here: the model
KV cache stops being a private per-slot rectangle and becomes ONE global
pool of fixed-size KV pages ("banks"). Each slot owns only a small page
table; attention reads/writes are routed through it on device
(`models/attention.paged_update_cache` / `paged_decode_attention`), and
slot refill becomes page allocation + table install instead of a full
cache-zero pass.

Three host-side pieces live in this module:

* `PagePool` — the allocator: a free list over pages `1..n_pages-1`
  (page 0 is the reserved *trash page*, see below) with per-page
  refcounts. `alloc` raises the typed `PoolExhausted` so the session can
  requeue instead of crash; `release` decrements and returns the pages
  that actually became free.
* `PrefixCache` — copy-on-write prefix sharing. Completed requests
  publish their *fully written* prompt pages keyed by a rolling hash of
  page-aligned token prefixes; a later request with the same preamble
  maps those pages read-only (refcount++) and skips their prefill
  entirely — the TTFT collapse for shared system prompts. A shared page
  is never written: the session skips exactly the tokens the shared
  pages cover, so writes land at positions >= the shared region. The one
  exception is an exact full-prompt hit, where the last prompt token
  must still be re-fed (its output is the first sampled token) and would
  write inside a shared page — that page is COW-forked: a fresh page is
  allocated and the shared page's contents device-copied before install.
* `PagedKV` — the per-session façade the `ServeSession` driver talks to:
  `admit(slot, prompt, max_new)` builds the slot's table row (shared +
  fresh pages, prefill-skip count, pending COW copies), `release(slot)`
  returns everything and re-points the row at the trash page, and
  `stats()` reports pool occupancy / pages shared / prefill tokens
  skipped for the serving report.

Why a trash page: the session cell steps ALL slots whenever any slot is
live (`engine.session_chunk_fn`), so a finished slot keeps scatter-
writing K/V at its frozen position every chunk. Its released pages may
already belong to another request, so release must re-point the dead
slot's table at a page nobody reads — page 0. Reads from stale/garbage
pages are harmless (masked attention gives them exactly-zero softmax
weight); only NaN survives the mask (0 * NaN), which is why pages freed
from a corrupted slot are scrubbed on device before reuse.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

TRASH_PAGE = 0


class PoolExhausted(RuntimeError):
    """Typed allocation failure: the pool has fewer free pages than the
    request needs. Carries the shortfall so the scheduler can reason
    about it (requeue / shed) instead of crashing the session."""

    def __init__(self, needed: int, free: int):
        super().__init__(f"KV pool exhausted: need {needed} pages, "
                         f"{free} free")
        self.needed = needed
        self.free = free


class PagePool:
    """Free-list page allocator with per-page refcounts.

    Pages are integer ids in `[1, n_pages)`; page 0 is the reserved trash
    page and is never handed out. A page's refcount is the number of slot
    tables + prefix-cache entries pointing at it; `release` only frees a
    page when the count hits zero (shared prefix pages survive their
    first owner).
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page 0 is reserved), "
                             f"got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.refcount = np.zeros(n_pages, np.int32)
        self.refcount[TRASH_PAGE] = 1          # pinned forever
        self._free: list[int] = list(range(n_pages - 1, 0, -1))
        # pages that may hold NaN (freed from a corrupted slot); the
        # session scrubs these on device before they are handed out again
        self.dirty: set[int] = set()
        # pages whose content failed an integrity check: permanently out
        # of circulation (they count as used capacity, never re-enter the
        # free list — the bank is fenced off, the cluster keeps serving)
        self.quarantined: set[int] = set()
        self.allocs = 0
        self.alloc_failures = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def alloc(self, n: int) -> list[int]:
        """Take `n` fresh pages (refcount 1 each) or raise `PoolExhausted`
        without taking any."""
        if n < 0:
            raise ValueError(f"alloc of {n} pages")
        if n > len(self._free):
            self.alloc_failures += 1
            raise PoolExhausted(n, len(self._free))
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert self.refcount[p] == 0, f"page {p} double-allocated"
            self.refcount[p] = 1
        self.allocs += n
        return pages

    def ref(self, pages) -> None:
        """Add one reference to each page (prefix-cache share)."""
        for p in pages:
            if p == TRASH_PAGE:
                continue
            assert self.refcount[p] > 0, f"ref of free page {p}"
            self.refcount[p] += 1

    def release(self, pages) -> list[int]:
        """Drop one reference per page; returns the pages that became
        free (refcount hit zero) in release order."""
        freed = []
        for p in pages:
            if p == TRASH_PAGE:
                continue
            assert self.refcount[p] > 0, f"release of free page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                if p in self.quarantined:
                    continue               # fenced off: never reallocated
                self._free.append(p)
                freed.append(p)
        return freed

    def quarantine(self, page: int) -> None:
        """Fence a page off permanently: it never re-enters the free list
        (current holders drop their references normally; the page just
        stays dead afterwards)."""
        page = int(page)
        if page == TRASH_PAGE:
            return
        self.quarantined.add(page)
        if self.refcount[page] == 0 and page in self._free:
            self._free.remove(page)

    def mark_dirty(self, pages) -> None:
        self.dirty.update(int(p) for p in pages if p != TRASH_PAGE)

    def take_dirty_free(self) -> list[int]:
        """Dirty pages that are currently free — the scrub set. Clears
        the returned pages' dirty marks."""
        out = [p for p in sorted(self.dirty) if self.refcount[p] == 0]
        self.dirty.difference_update(out)
        return out

    def stats(self) -> dict:
        return {"n_pages": self.n_pages, "page_size": self.page_size,
                "used_pages": self.used_pages,
                "free_pages": self.free_pages,
                "occupancy_pct": 100.0 * self.used_pages /
                max(self.n_pages - 1, 1),
                "allocs": self.allocs,
                "alloc_failures": self.alloc_failures,
                "quarantined_pages": len(self.quarantined)}


def _page_key(prev_key: bytes, tokens: np.ndarray) -> bytes:
    """Rolling hash chain: key of page k = H(key of page k-1 || tokens)."""
    h = hashlib.blake2b(prev_key, digest_size=16)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


def page_digests(arrays, n: int) -> list[bytes]:
    """Content checksum per page from a page-major device readback.

    `arrays` is what the session's `page_read_fn` returns: one array per
    pageable cache leaf, each with the page axis first (shape (n, ...)).
    The digest of page j folds page j of every leaf, so any single leaf's
    corruption changes it."""
    host = [np.asarray(a) for a in arrays]
    out = []
    for j in range(n):
        h = hashlib.blake2b(digest_size=16)
        for a in host:
            h.update(np.ascontiguousarray(a[j]).tobytes())
        out.append(h.digest())
    return out


@dataclasses.dataclass
class _PrefixEntry:
    page: int
    tokens: np.ndarray     # the page's token content (page_size,)
    parent: bytes = b"root"    # chain key of the previous page's entry
    hits: int = 0
    last_used: int = 0     # logical tick of the last insert/match touch


class PrefixCache:
    """Hash-chained map from page-aligned token prefixes to pool pages.

    `insert(tokens, pages)` publishes the fully written prompt pages of a
    completed request (each gains a cache reference so it outlives its
    owner); `match(tokens)` walks the chain and returns the longest run
    of shared pages covering a prefix of `tokens`. Under memory pressure
    `evict(n_pages)` drops entries cold-first (LRU by logical touch
    tick), preferring pages the cache is the sole owner of — evicting
    those actually frees memory, instead of only reclaiming pages that
    already had no references.
    """

    def __init__(self, pool: PagePool):
        self.pool = pool
        self._chain: dict[bytes, _PrefixEntry] = {}
        self._order: list[bytes] = []          # insertion order (stable)
        self.hits = 0
        self.misses = 0
        self.evictions = 0        # entries dropped under memory pressure
        self._tick = 0            # logical clock for LRU recency

    def _touch(self) -> int:
        self._tick += 1
        return self._tick

    def __len__(self) -> int:
        return len(self._chain)

    def insert(self, tokens: np.ndarray, pages) -> int:
        """Publish the fully covered prompt pages. Returns how many new
        pages were published (already-cached prefixes are skipped)."""
        ps = self.pool.page_size
        tokens = np.asarray(tokens, np.int32)
        n_full = min(tokens.size // ps, len(pages))
        key = b"root"
        published = 0
        for k in range(n_full):
            page_toks = tokens[k * ps:(k + 1) * ps]
            parent, key = key, _page_key(key, page_toks)
            if key in self._chain:
                self._chain[key].last_used = self._touch()   # re-warmed
                continue                        # prefix already published
            page = int(pages[k])
            if page == TRASH_PAGE or page in self.pool.quarantined:
                break
            self.pool.ref([page])
            self._chain[key] = _PrefixEntry(page, page_toks.copy(), parent,
                                            last_used=self._touch())
            self._order.append(key)
            published += 1
        return published

    def match(self, tokens: np.ndarray) -> list[int]:
        """Longest chain of cached pages covering a prefix of `tokens`
        (bit-exact token match, not just hash match). Bumps refcounts is
        NOT done here — the caller refs the pages it actually installs."""
        ps = self.pool.page_size
        tokens = np.asarray(tokens, np.int32)
        key = b"root"
        out: list[int] = []
        for k in range(tokens.size // ps):
            page_toks = tokens[k * ps:(k + 1) * ps]
            key = _page_key(key, page_toks)
            e = self._chain.get(key)
            if e is None or not np.array_equal(e.tokens, page_toks):
                break
            e.hits += 1
            e.last_used = self._touch()
            out.append(e.page)
        if out:
            self.hits += 1
        else:
            self.misses += 1
        return out

    def evict(self, n_pages: int) -> list[int]:
        """Drop cache entries until `n_pages` pages were freed (or the
        cache is empty), coldest first (LRU by last insert/match touch).
        Returns the freed page ids.

        Entries whose page the cache is the *sole* owner of go first:
        dropping one of those actually frees a page, where dropping an
        entry still shared with a running slot frees nothing now and
        only loses future reuse — those are the last resort. Dropping an
        entry cascades to its chain descendants (a suffix is unreachable
        without its prefix), which LRU order already favours: a match
        touches every entry on its path, so a parent is never colder
        than its children."""
        freed: list[int] = []
        while self._chain and len(freed) < n_pages:
            key = min(
                self._chain,
                key=lambda k: (
                    int(self.pool.refcount[self._chain[k].page]) > 1,
                    self._chain[k].last_used))
            freed += self._drop_chain(key)
        return freed

    def _drop_chain(self, key: bytes) -> list[int]:
        """Evict one entry and (transitively) its descendants; returns
        the pages that became free."""
        doomed = {key}
        changed = True
        while changed:
            changed = False
            for k, e in self._chain.items():
                if k not in doomed and e.parent in doomed:
                    doomed.add(k)
                    changed = True
        freed: list[int] = []
        for k in doomed:
            e = self._chain.pop(k)
            self._order.remove(k)
            self.evictions += 1
            freed += self.pool.release([e.page])
        return freed

    def drop_page(self, page: int) -> list[int]:
        """Remove every chain entry routed through `page` — and, because
        a chain suffix is meaningless without its prefix, every entry
        downstream of one (transitively via `parent` links). Releases the
        dropped entries' cache references; returns the pages that became
        free."""
        doomed = {k for k, e in self._chain.items() if e.page == page}
        changed = bool(doomed)
        while changed:
            changed = False
            for k, e in self._chain.items():
                if k not in doomed and e.parent in doomed:
                    doomed.add(k)
                    changed = True
        freed: list[int] = []
        for k in doomed:
            e = self._chain.pop(k)
            self._order.remove(k)
            freed += self.pool.release([e.page])
        return freed

    def clear(self) -> list[int]:
        return self.evict(len(self._chain))


@dataclasses.dataclass
class SlotAlloc:
    """What `PagedKV.admit` hands the session for one slot."""

    table: np.ndarray            # (pages_per_slot,) int32 page ids
    prefill_skip: int            # prompt tokens covered by shared pages
    shared_pages: int            # pages mapped read-only from the cache
    cow_copies: list[tuple[int, int]]   # (src, dst) device page copies


class PagedKV:
    """Per-session paged-KV manager: pool + prefix cache + slot tables.

    The session driver calls `admit` at refill boundaries (may raise
    `PoolExhausted` — the request stays queued), `release` whenever a
    slot retires (done, cancelled, shed, killed, quarantined), and
    `publish` when a request completes cleanly to seed the prefix cache.
    All bookkeeping is host-side numpy; the device only ever sees the
    int32 table rows.
    """

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 pages_per_slot: int, *, prefix_cache: bool = True,
                 recurrent: bool = False):
        """`recurrent`: the cache also holds per-slot recurrent state,
        which shared pages cannot restore, so no prefix is matched or
        published whatever `prefix_cache` says (`stats()["prefix_reuse"]`
        says why)."""
        self.pool = PagePool(n_pages, page_size)
        reuse = prefix_cache and not recurrent
        self.prefix = PrefixCache(self.pool) if reuse else None
        self.prefix_reuse = ("on" if reuse else
                             "off: recurrent state" if prefix_cache
                             else "off")
        self.n_slots = int(n_slots)
        self.pages_per_slot = int(pages_per_slot)
        # owned: the references this slot must drop on release (includes a
        # COW fork's source page, which stays alive while the copy is
        # pending); table: the page ids the device actually addresses.
        self._slot_owned: list[list[int]] = [[] for _ in range(n_slots)]
        self._slot_table: list[list[int]] = [[] for _ in range(n_slots)]
        self._slot_prompt: list[np.ndarray | None] = [None] * n_slots
        # counters for stats()
        self.pages_shared_total = 0
        self.prefill_skipped_tokens = 0
        self.cow_forks = 0
        # per-page content checksums, stamped at publish (integrity)
        self.checksums: dict[int, bytes] = {}
        self.integrity_checks = 0
        self.integrity_violations = 0
        self.integrity_repairs = 0
        self._scrub_cursor = 0

    # -- admission -----------------------------------------------------------
    def admit(self, slot: int, prompt: np.ndarray, max_new: int,
              *, verify=None) -> SlotAlloc:
        """Build slot's page table for `prompt` + up to `max_new` output
        tokens. Shared prefix pages are mapped read-only; the remainder
        is freshly allocated. Raises `PoolExhausted` (allocating nothing)
        when the pool cannot cover the fresh pages even after evicting
        prefix-cache entries.

        `verify(pages) -> bad_pages` is the integrity hook: when set,
        prefix-matched pages are content-checked against their publish
        checksums *before* they are shared. Corrupt pages are
        quarantined (chain dropped), the match is retried — it now stops
        at the clean prefix — and the request proceeds with fresh pages
        instead: repair by recompute, never a crash."""
        assert not self._slot_owned[slot], f"slot {slot} already mapped"
        ps = self.pool.page_size
        prompt = np.asarray(prompt, np.int32)
        total_tokens = prompt.size + max_new
        n_total = -(-total_tokens // ps)       # ceil
        if n_total > self.pages_per_slot:
            raise ValueError(
                f"request needs {n_total} pages > pages_per_slot "
                f"{self.pages_per_slot} (prompt {prompt.size} + "
                f"max_new {max_new}, page_size {ps})")

        shared = self.prefix.match(prompt) if self.prefix else []
        if shared and verify is not None:
            bad = list(verify(shared))
            if bad:
                for p in bad:
                    self.quarantine_page(p)
                # the poisoned chain is gone; only the clean prefix (if
                # any) can match now — the rest re-prefills from tokens
                shared = self.prefix.match(prompt) if self.prefix else []
                self.integrity_repairs += 1
        # the final prompt token must be re-fed (its forward pass emits
        # the first sampled token), so never skip the whole prompt; an
        # exact full-coverage hit COW-forks the page the re-fed token
        # writes into.
        skip = min(len(shared) * ps, max(prompt.size - 1, 0))
        fork_last = bool(shared) and len(shared) * ps > skip
        n_fresh = n_total - len(shared) + (1 if fork_last else 0)

        # hold the matched pages across a possible eviction (the prefix
        # cache may otherwise free exactly the pages we are about to map)
        self.pool.ref(shared)
        try:
            fresh = self.pool.alloc(n_fresh)
        except PoolExhausted:
            if self.prefix is not None:
                evicted = self.prefix.evict(n_fresh - self.pool.free_pages)
                self._purge_checksums(evicted)
            try:
                fresh = self.pool.alloc(n_fresh)
            except PoolExhausted:
                self.pool.release(shared)       # allocate-nothing contract
                raise

        cow: list[tuple[int, int]] = []
        mapped = list(shared)
        if fork_last:
            src, dst = mapped[-1], fresh[0]
            mapped[-1] = dst                    # table points at the copy;
            cow.append((src, dst))              # src stays owned (ref held)
            self.cow_forks += 1
        pages = mapped + fresh[(1 if fork_last else 0):]
        table = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
        table[:len(pages)] = pages
        self._slot_owned[slot] = shared + fresh
        self._slot_table[slot] = pages
        self._slot_prompt[slot] = prompt
        self.pages_shared_total += len(shared)
        self.prefill_skipped_tokens += skip
        return SlotAlloc(table=table, prefill_skip=skip,
                         shared_pages=len(shared), cow_copies=cow)

    # -- retirement ----------------------------------------------------------
    def publishable_pages(self, slot: int) -> list[int]:
        """The slot's fully written prompt pages — the set `publish` would
        seed the prefix cache with (and the set whose content the session
        digests for the integrity stamp)."""
        if self.prefix is None or self._slot_prompt[slot] is None:
            return []
        ps = self.pool.page_size
        prompt = self._slot_prompt[slot]
        n_full = min(prompt.size // ps, len(self._slot_table[slot]))
        return [p for p in self._slot_table[slot][:n_full]
                if p != TRASH_PAGE]

    def publish(self, slot: int, *, digests: "dict[int, bytes] | None"
                = None) -> int:
        """Seed the prefix cache with the slot's fully written prompt
        pages (call on clean request completion, before `release`).
        `digests` stamps each page's content checksum; a page that
        already carries a stamp keeps it (re-stamping a shared page from
        possibly-corrupted current content would mask the corruption)."""
        if self.prefix is None or self._slot_prompt[slot] is None:
            return 0
        published = self.prefix.insert(self._slot_prompt[slot],
                                       self._slot_table[slot])
        for page, digest in (digests or {}).items():
            if int(page) in self.pool.quarantined:
                continue
            self.checksums.setdefault(int(page), digest)
        return published

    def release(self, slot: int, *, dirty: bool = False) -> list[int]:
        """Return the slot's pages to the pool (shared pages survive as
        long as other references remain). `dirty=True` marks the freed
        pages for a device scrub before reuse (NaN corruption). Returns
        the freed page ids."""
        owned = self._slot_owned[slot]
        self._slot_owned[slot] = []
        self._slot_table[slot] = []
        self._slot_prompt[slot] = None
        freed = self.pool.release(owned)
        self._purge_checksums(freed)
        if dirty:
            self.pool.mark_dirty(freed)
        return freed

    # -- integrity -----------------------------------------------------------
    def _purge_checksums(self, pages) -> None:
        """Stamps die with the content: a freed page's next occupant has
        different bytes, and a stale stamp would read as corruption."""
        for p in pages:
            self.checksums.pop(int(p), None)

    def verify(self, pages, digests) -> list[int]:
        """Compare current content digests against the publish stamps.
        Returns the pages whose content changed (unstamped pages are
        skipped — nothing to compare against)."""
        bad = []
        for p, d in zip(pages, digests):
            want = self.checksums.get(int(p))
            if want is None:
                continue
            self.integrity_checks += 1
            if d != want:
                bad.append(int(p))
        return bad

    def quarantine_page(self, page: int) -> list[int]:
        """Detected corruption on `page`: fence it off in the pool, drop
        every prefix chain routed through it (transitively — a suffix
        without its prefix is meaningless), and purge dead stamps. Slots
        currently mapping the page keep running (attention through a
        perturbed-but-finite page is the *old* failure mode; new sharers
        are what this protects). Returns pages freed by the chain drop."""
        page = int(page)
        self.integrity_violations += 1
        self.pool.quarantine(page)        # before drop: release() routes
        freed = []                        # around the free list
        if self.prefix is not None:
            freed = self.prefix.drop_page(page)
        self._purge_checksums(freed)
        self.checksums.pop(page, None)
        return freed

    def scrub_candidates(self, limit: int) -> list[int]:
        """Round-robin slice of the stamped pages for the background
        integrity scrub (a few per chunk boundary keeps the cost bounded
        while every published page is eventually re-checked)."""
        pages = sorted(self.checksums)
        if not pages or limit <= 0:
            return []
        n = min(int(limit), len(pages))
        out = [pages[(self._scrub_cursor + i) % len(pages)]
               for i in range(n)]
        self._scrub_cursor = (self._scrub_cursor + n) % len(pages)
        return out

    def reset(self) -> None:
        """Forget everything (wedge recovery: the device pool was rebuilt
        from scratch, so every table, page, and prefix entry is void)."""
        for s in range(self.n_slots):
            self._slot_owned[s] = []
            self._slot_table[s] = []
            self._slot_prompt[s] = None
        self.pool = PagePool(self.pool.n_pages, self.pool.page_size)
        if self.prefix is not None:
            evictions = self.prefix.evictions   # lifetime counter survives
            self.prefix = PrefixCache(self.pool)
            self.prefix.evictions = evictions
        self.checksums = {}
        self._scrub_cursor = 0

    def page_gauges(self) -> tuple[int, int]:
        """(pages some slot's table addresses, shared pages once; pages
        only the prefix cache holds). A page in use is referenced by slots,
        by the cache, or by both, or is fenced off by quarantine with no
        reference left, so the cache's own pages are what remains of the
        used ones once the slots' and the fenced ones are taken out."""
        held = len(set().union(*self._slot_table))
        owned = len(set().union(*self._slot_owned))
        fenced = sum(1 for p in self.pool.quarantined
                     if self.pool.refcount[p] == 0)
        return held, self.pool.used_pages - owned - fenced

    def slot_pages(self, slot: int) -> list[int]:
        """The page ids the slot's device table addresses (table order)."""
        return list(self._slot_table[slot])

    def match_len(self, prompt) -> int:
        """Reusable-prefix length in tokens — the scheduler's page-level
        admission score (peek only: no refcounts, no hit accounting)."""
        if self.prefix is None:
            return 0
        ps = self.pool.page_size
        tokens = np.asarray(prompt, np.int32)
        key, n = b"root", 0
        for k in range(tokens.size // ps):
            page_toks = tokens[k * ps:(k + 1) * ps]
            key = _page_key(key, page_toks)
            e = self.prefix._chain.get(key)
            if e is None or not np.array_equal(e.tokens, page_toks):
                break
            n += ps
        return n

    def match_pages(self, prompt) -> int:
        """Measured full-page prefix overlap — `match_len` in pages."""
        return self.match_len(prompt) // self.pool.page_size

    # -- durability ----------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-able image of every host-side structure: pool refcounts /
        free list / dirty + quarantine sets, slot tables + prompts, the
        prefix chain (keys, parents, token content), checksums, counters.
        Bit-exact round-trip with `load_snapshot`."""
        return {
            "refcount": self.pool.refcount.tolist(),
            "free": list(self.pool._free),
            "dirty": sorted(self.pool.dirty),
            "quarantined": sorted(self.pool.quarantined),
            "allocs": self.pool.allocs,
            "alloc_failures": self.pool.alloc_failures,
            "slot_owned": [list(o) for o in self._slot_owned],
            "slot_table": [list(t) for t in self._slot_table],
            "slot_prompt": [None if p is None else p.tolist()
                            for p in self._slot_prompt],
            "chain": None if self.prefix is None else [
                {"key": k.hex(), "parent": e.parent.hex(),
                 "page": e.page, "tokens": e.tokens.tolist(),
                 "hits": e.hits, "last_used": e.last_used}
                for k in self.prefix._order
                for e in (self.prefix._chain[k],)],
            "prefix_hits": 0 if self.prefix is None else self.prefix.hits,
            "prefix_misses": (0 if self.prefix is None
                              else self.prefix.misses),
            "prefix_evictions": (0 if self.prefix is None
                                 else self.prefix.evictions),
            "prefix_tick": (0 if self.prefix is None
                            else self.prefix._tick),
            "checksums": {str(p): d.hex()
                          for p, d in sorted(self.checksums.items())},
            "pages_shared_total": self.pages_shared_total,
            "prefill_skipped_tokens": self.prefill_skipped_tokens,
            "cow_forks": self.cow_forks,
            "integrity_checks": self.integrity_checks,
            "integrity_violations": self.integrity_violations,
            "integrity_repairs": self.integrity_repairs,
            "scrub_cursor": self._scrub_cursor,
        }

    def load_snapshot(self, d: dict) -> None:
        """Rebuild the pool/cache/tables in place from `snapshot()`."""
        self.pool.refcount = np.asarray(d["refcount"], np.int32)
        self.pool._free = [int(p) for p in d["free"]]
        self.pool.dirty = {int(p) for p in d["dirty"]}
        self.pool.quarantined = {int(p) for p in d.get("quarantined", [])}
        self.pool.allocs = int(d["allocs"])
        self.pool.alloc_failures = int(d["alloc_failures"])
        self._slot_owned = [[int(p) for p in o] for o in d["slot_owned"]]
        self._slot_table = [[int(p) for p in t] for t in d["slot_table"]]
        self._slot_prompt = [None if p is None else np.asarray(p, np.int32)
                             for p in d["slot_prompt"]]
        if self.prefix is not None:
            self.prefix._chain = {}
            self.prefix._order = []
            for rec in (d["chain"] or []):
                key = bytes.fromhex(rec["key"])
                self.prefix._chain[key] = _PrefixEntry(
                    int(rec["page"]),
                    np.asarray(rec["tokens"], np.int32),
                    bytes.fromhex(rec["parent"]), int(rec["hits"]),
                    last_used=int(rec.get("last_used", 0)))
                self.prefix._order.append(key)
            self.prefix.hits = int(d.get("prefix_hits", 0))
            self.prefix.misses = int(d.get("prefix_misses", 0))
            self.prefix.evictions = int(d.get("prefix_evictions", 0))
            self.prefix._tick = int(d.get("prefix_tick", 0))
        self.checksums = {int(p): bytes.fromhex(h)
                          for p, h in d.get("checksums", {}).items()}
        self.pages_shared_total = int(d["pages_shared_total"])
        self.prefill_skipped_tokens = int(d["prefill_skipped_tokens"])
        self.cow_forks = int(d["cow_forks"])
        self.integrity_checks = int(d.get("integrity_checks", 0))
        self.integrity_violations = int(d.get("integrity_violations", 0))
        self.integrity_repairs = int(d.get("integrity_repairs", 0))
        self._scrub_cursor = int(d.get("scrub_cursor", 0))

    def stats(self) -> dict:
        out = dict(self.pool.stats())
        out.update(prefix_reuse=self.prefix_reuse,
                   pages_shared=self.pages_shared_total,
                   prefill_skipped_tokens=self.prefill_skipped_tokens,
                   cow_forks=self.cow_forks,
                   integrity_checks=self.integrity_checks,
                   integrity_violations=self.integrity_violations,
                   integrity_repairs=self.integrity_repairs)
        if self.prefix is not None:
            out.update(prefix_entries=len(self.prefix),
                       prefix_hits=self.prefix.hits,
                       prefix_misses=self.prefix.misses,
                       evictions=self.prefix.evictions)
        return out
