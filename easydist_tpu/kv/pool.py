"""Refcounted page-pool allocator over one preallocated HBM arena.

`PagePool` is pure host bookkeeping: the device arena (`kv/arena.py`: a
{"k","v"} pytree of one [n_pages, heads, page_tokens, head_dim] leaf per
layer) is allocated once by the owner (GenerationSession via
`models.init_kv_pages`) and threaded through compiled steps as an argument
donated leaf by leaf; the pool tracks
which of its `n_pages` page slots are free, how many holders reference
each live page, and the utilization counters serving metrics report.

Refcount semantics: a page's count is (# live sequences whose page table
maps it) + (1 if the prefix trie holds a committed node for it).  `alloc`
hands out a free page at refcount 1; `share` bumps (trie commit, prefix
restore, fleet import of an already-present page); `release` drops and
reclaims at zero.  Shared pages are never written by serving (restored
prefixes are whole aligned pages; writes only land at positions past the
prefix, in pages the sequence allocated itself), so sharing needs no
device copy — `ensure_exclusive` exists for callers that DO intend to
write (stress tests, future in-place migration) and is the copy-on-write
fault point.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

__all__ = ["PagePool"]


class PagePool:
    """Free-list allocator for `n_pages` fixed `page_tokens`-token KV
    pages of `page_bytes` bytes each (k + v, all layers)."""

    def __init__(self, n_pages: int, page_tokens: int, page_bytes: int = 0):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        if page_bytes < 0:
            raise ValueError(f"page_bytes must be >= 0, got {page_bytes}")
        self.n_pages = n_pages
        self.page_tokens = page_tokens
        self.page_bytes = page_bytes
        # LIFO free list: recently freed pages are reused first, keeping
        # the hot working set of arena rows small
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        # an int array, so that the audit reads it without a copy
        self._refcount = np.zeros(n_pages, dtype=np.int64)
        self.allocs = 0
        self.frees = 0
        self.shares = 0
        self.peak_in_use = 0

    # ---------------------------------------------------------- allocation
    @property
    def sentinel(self) -> int:
        """The never-valid page id page tables use for unmapped entries:
        one past the arena, so scatter-with-drop ignores writes through it
        and clipped gathers read a real (masked) row."""
        return self.n_pages

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self) -> int:
        """Pop a free page at refcount 1.  Raises when the arena is
        exhausted — callers gate on `n_free` (admission reserves a
        sequence's worst-case pages up front, evicting unpinned trie
        nodes first), so hitting this is a bookkeeping bug."""
        if not self._free:
            raise RuntimeError(
                f"page pool exhausted: all {self.n_pages} pages live "
                f"(admission should have reserved before allocating)")
        page = self._free.pop()
        self._refcount[page] = 1
        self.allocs += 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return page

    def share(self, page: int) -> int:
        """Add a holder to a live page (prefix restore mapping it into
        another sequence's table, trie commit, fleet import hit).
        Returns the new refcount."""
        self._check_live(page, "share")
        self._refcount[page] += 1
        self.shares += 1
        return int(self._refcount[page])

    def release(self, page: int) -> int:
        """Drop one holder; the page returns to the free list when the
        last holder releases.  Returns the remaining refcount."""
        self._check_live(page, "release")
        self._refcount[page] -= 1
        if self._refcount[page] == 0:
            self._free.append(page)
            self.frees += 1
        return int(self._refcount[page])

    def refcount(self, page: int) -> int:
        if not 0 <= page < self.n_pages:
            raise ValueError(f"page {page} out of range [0, {self.n_pages})")
        return int(self._refcount[page])

    @property
    def refcounts(self) -> np.ndarray:
        """Every page's count, by page id: the audit's view (KV001
        counts the table's and the trie's holders against it)."""
        return np.asarray(self._refcount)

    def ensure_exclusive(self, page: int) -> Optional[int]:
        """Copy-on-write fault point: if `page` is shared (refcount > 1),
        allocate a fresh page for the caller to copy into and drop the
        caller's hold on the shared one; return the new page id.  Returns
        None when the page is already exclusive.  The serving path never
        triggers this (it never writes shared pages); stress tests and
        future in-place migration do."""
        self._check_live(page, "ensure_exclusive")
        if self._refcount[page] == 1:
            return None
        fresh = self.alloc()
        self.release(page)
        return fresh

    def _check_live(self, page: int, op: str) -> None:
        if not 0 <= page < self.n_pages:
            raise ValueError(
                f"{op}: page {page} out of range [0, {self.n_pages})")
        if self._refcount[page] <= 0:
            raise ValueError(f"{op}: page {page} is free (refcount "
                             f"{self._refcount[page]}) — use-after-free")

    # ----------------------------------------------------------- reporting
    def stats(self) -> Dict[str, int]:
        return {"n_pages": self.n_pages, "page_tokens": self.page_tokens,
                "page_bytes": self.page_bytes, "in_use": self.in_use,
                "free": self.n_free, "allocs": self.allocs,
                "frees": self.frees, "shares": self.shares,
                "peak_in_use": self.peak_in_use}

    def check_invariants(self) -> List[str]:
        """Refcount/byte audit (analyze KV001 wraps these into findings):
        free-list entries must be unique in-range pages at refcount 0,
        live pages must hold positive counts, and the arena byte total
        must equal mapped + free page bytes (conservation — no page is
        both free and mapped, none is lost).

        A consistent pool is decided by `consistent()`, a fixed number
        of array passes whatever the arena's size; `list_problems()`
        walks the pool page by page only to word a failure."""
        return [] if self.consistent() else self.list_problems()

    def consistent(self) -> bool:
        """True exactly where `list_problems()` would return []: the
        same invariants as a few passes over the refcounts and the free
        list."""
        n = self.n_pages
        refcount = self.refcounts
        if refcount.shape != (n,):
            return False
        free = np.asarray(self._free, dtype=np.int64)
        if free.size and not 0 <= free.min() <= free.max() < n:
            return False
        # once on the free list at refcount 0, off it at a count above 0
        times_free = np.bincount(free, minlength=n)
        if (refcount < 0).any() or (times_free != (refcount == 0)).any():
            return False
        return n * self.page_bytes \
            == (self.in_use + self.n_free) * self.page_bytes

    def list_problems(self) -> List[str]:
        """The listed walk: one line per violated invariant, in the
        order the free list and then the pages are met."""
        problems: List[str] = []
        seen = set()
        for page in self._free:
            if not 0 <= page < self.n_pages:
                problems.append(f"free list holds out-of-range page {page}")
                continue
            if page in seen:
                problems.append(f"free list holds page {page} twice "
                                f"(double free)")
            seen.add(page)
            if self._refcount[page] != 0:
                problems.append(
                    f"free page {page} has refcount {self._refcount[page]} "
                    f"(freed while still referenced)")
        for page in range(self.n_pages):
            if page not in seen and self._refcount[page] <= 0:
                problems.append(
                    f"page {page} has refcount {self._refcount[page]} but "
                    f"is not on the free list (leaked page)")
        arena_bytes = self.n_pages * self.page_bytes
        accounted = (self.in_use + self.n_free) * self.page_bytes
        if arena_bytes != accounted:
            problems.append(
                f"byte conservation drift: arena {arena_bytes} != "
                f"mapped+free {accounted}")
        return problems
