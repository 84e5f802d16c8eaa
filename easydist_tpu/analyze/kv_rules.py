"""Layer 7: paged-KV auditor.

KV001 — the page-table/refcount consistency audit over the paged decode
cache (kv/pool.py + kv/table.py + serve/generation.py's `_PagedPool`).
The paged layout's safety rests entirely on host bookkeeping: the device
only ever sees an int32 table and a flat arena, so a bookkeeping bug does
not crash — it silently serves one sequence another sequence's K/V, or
writes a live page after it was handed to someone else.  This audit
cross-checks the three structures against each other:

  * every table entry points at a LIVE page (refcount >= 1) inside the
    arena — an entry at a freed page means attention is reading memory
    the allocator may hand out again mid-generation;
  * no page is mapped by more holders than its refcount — two sequences
    mapping one page with refcount 1 means the first retire frees it
    under the second (the "two live sequences without refcount >= 2"
    failure);
  * every trie-committed page reference is live, and counts toward the
    page's refcount alongside its table occurrences;
  * the pool's own free-list/byte-conservation invariants hold
    (`PagePool.check_invariants`: double frees, leaked pages, arena
    bytes != mapped + free bytes), and the table's shape/contiguity
    invariants hold (`PageTable.check_invariants`: a hole inside a row's
    live prefix gathers an unmasked garbage page).

A consistent triple is DECIDED in a fixed number of array passes
(`page_table_consistent`); the listed walk (`list_page_table_findings`)
runs only on one that fails, to say what is wrong and who holds what.

Wired as a session hook like SERVE001/002: `GenerationSession` calls
`check_page_table` at the first decode round and at every retire — the
transitions where refcount drift would next cause a wrong free.
"""

from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from .findings import Finding, make_finding


def _trie_pages(trie):
    """(page id, trie node) of every committed page reference; an array
    commit (no page id) has nothing to audit."""
    if trie is None:
        return
    for tnode in trie._walk():
        pid = tnode.kv.get("page") if isinstance(tnode.kv, dict) else None
        if pid is not None:
            yield pid, tnode


def page_table_consistent(pool, table, trie=None) -> bool:
    """True exactly where `list_page_table_findings` would return []:
    the pool's and the table's own invariants (`consistent()` of each),
    then every page a table row or a trie node holds inside the arena
    at a refcount of at least its holders.  A fixed number of array
    passes over `table.array`, the refcounts and the free list whatever
    their size; only the trie is walked, node by node."""
    if not (pool.consistent() and table.consistent()):
        return False
    held = table.array[table.array != table.sentinel].astype(np.int64)
    in_trie = [pid for pid, _ in _trie_pages(trie)]
    if in_trie:
        held = np.concatenate([held, np.asarray(in_trie, dtype=np.int64)])
    if held.size == 0:
        return True
    if not 0 <= held.min() <= held.max() < pool.n_pages:
        return False
    holders = np.bincount(held, minlength=pool.n_pages)
    return bool((pool.refcounts >= holders).all())


def audit_page_table(pool, table, trie=None, node: str = "kv",
                     on_path: Optional[Callable[[str], None]] = None
                     ) -> List[Finding]:
    """KV001 over a live (`PagePool`, `PageTable`[, `PrefixCache` of
    {"page": id} references]) triple.  Returns one finding per violated
    invariant; [] when the bookkeeping is consistent.

    A consistent pool is decided by `page_table_consistent`, in a fixed
    number of array passes whatever the pool's size; the listed walk
    (`list_page_table_findings`) runs only to word a failure, so its
    findings, their order and their messages are what they always were.
    `on_path`, if given, is told which of the two ran: "vector" or
    "listed"."""
    sound = page_table_consistent(pool, table, trie)
    if on_path is not None:
        on_path("vector" if sound else "listed")
    return [] if sound else list_page_table_findings(pool, table, trie,
                                                     node)


def list_page_table_findings(pool, table, trie=None,
                             node: str = "kv") -> List[Finding]:
    """The listed walk of KV001: every slot's mapped pages and every
    trie reference by name, so that a finding says who holds what."""
    findings: List[Finding] = []
    for problem in pool.list_problems():
        findings.append(make_finding("KV001", node, f"pool: {problem}"))
    for problem in table.list_problems():
        findings.append(make_finding("KV001", node, f"table: {problem}"))

    # holders per page: table occurrences across all slots + trie refs
    holders = {}
    for slot in range(table.max_slots):
        for pid in table.mapped(slot):
            holders.setdefault(pid, []).append(f"slot{slot}")
    for pid, tnode in _trie_pages(trie):
        holders.setdefault(pid, []).append(f"trie@depth{tnode.depth}")

    for pid, who in sorted(holders.items()):
        if not 0 <= pid < pool.n_pages:
            findings.append(make_finding(
                "KV001", node,
                f"page {pid} (held by {', '.join(who)}) is outside the "
                f"arena [0, {pool.n_pages})"))
            continue
        rc = pool.refcount(pid)
        if rc < 1:
            findings.append(make_finding(
                "KV001", node,
                f"page {pid} is mapped by {', '.join(who)} but has "
                f"refcount {rc} (freed under a live holder — the "
                f"allocator can hand it to another sequence)"))
        elif rc < len(who):
            findings.append(make_finding(
                "KV001", node,
                f"page {pid} has {len(who)} holders "
                f"({', '.join(who)}) but refcount {rc}: the first "
                f"release frees it under the remaining holders"))
    return findings
