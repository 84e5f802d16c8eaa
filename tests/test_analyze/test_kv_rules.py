"""Layer 7 paged-KV auditor goldens: KV001 fires exactly once per
violated invariant on known-bad pool/table/trie fixtures, yields zero
findings on clean ones (including a real drained paged session), and the
`check_page_table` hook raises under `analyze_raise` and demotes to
logging with the escape hatch."""

import random
import zlib

import jax
import numpy as np
import pytest

from easydist_tpu import config as edconfig
from easydist_tpu.analyze import audit_page_table, check_page_table
from easydist_tpu.analyze.findings import AnalysisError
from easydist_tpu.analyze.kv_rules import (list_page_table_findings,
                                           page_table_consistent)
from easydist_tpu.kv import PagePool, PageTable
from easydist_tpu.models import gpt
from easydist_tpu.serve import GenerationSession, PrefixCache, ServeConfig

CHUNK = 4


def _rig(n_pages=8, n_slots=2, max_pages=4):
    pool = PagePool(n_pages, CHUNK, page_bytes=64)
    table = PageTable(n_slots, max_pages, n_pages)
    return pool, table


class TestCleanFixtures:
    def test_empty_is_clean(self):
        pool, table = _rig()
        assert audit_page_table(pool, table) == []

    def test_consistent_sharing_is_clean(self):
        # one page in two slots AND the trie, refcount 3: consistent
        pool, table = _rig()
        trie = PrefixCache(CHUNK, 1 << 12)
        pid = pool.alloc()
        table.map(0, 0, pid)
        pool.share(pid)
        table.map(1, 0, pid)
        pool.share(pid)
        trie.commit([], [1, 2, 3, 4], {"page": pid}, nbytes=64)
        assert audit_page_table(pool, table, trie=trie) == []

    def test_bucketed_array_commits_are_ignored(self):
        # a trie carrying array KV (the bucketed layout) has no page
        # references to audit
        import numpy as np
        pool, table = _rig()
        trie = PrefixCache(CHUNK, 1 << 12)
        trie.commit([], [1, 2, 3, 4],
                    {"k": np.zeros((1, 2, CHUNK, 8), np.float32),
                     "v": np.zeros((1, 2, CHUNK, 8), np.float32)})
        assert audit_page_table(pool, table, trie=trie) == []

    def test_drained_paged_session_is_clean(self):
        # zero false positives on the real thing: a paged session after
        # mixed-length traffic, audited with its own live structures
        cfg = gpt.GPTConfig.tiny()
        params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
        # max_decode_slots matches the other serve tests' sessions so the
        # process memo shares ONE set of compiled paged programs in-suite
        sc = ServeConfig(decode_buckets=(32,), max_decode_slots=2,
                         prefill_chunk=8, prefill_batch=2)
        sess = GenerationSession.for_gpt(params, cfg, config=sc)
        for p in ([1, 2, 3], list(range(1, 18)), [5] * 9):
            sess.submit(p, max_new_tokens=4)
        sess.run_until_drained()
        pool = next(iter(sess._pools.values()))
        assert audit_page_table(pool.pool, pool.table,
                                trie=pool.trie) == []


class TestKnownBad:
    def test_two_holders_one_refcount_fires_once(self):
        # the golden known-bad: two table rows map one page but only one
        # reference was taken — the first retire frees it under the
        # survivor.  KV001, exactly once.
        pool, table = _rig()
        pid = pool.alloc()
        table.map(0, 0, pid)
        table.map(1, 0, pid)          # no pool.share(pid)!
        findings = audit_page_table(pool, table, node="golden")
        assert len(findings) == 1
        f = findings[0]
        assert f.rule_id == "KV001" and f.severity == "error"
        assert f.node == "golden"
        assert "first release frees it" in f.message

    def test_freed_page_under_live_table_entry(self):
        pool, table = _rig()
        pid = pool.alloc()
        table.map(0, 0, pid)
        pool.release(pid)             # freed under the mapping
        findings = audit_page_table(pool, table)
        assert any("freed under a live holder" in f.message
                   for f in findings)
        assert all(f.rule_id == "KV001" for f in findings)

    def test_trie_reference_counts_as_holder(self):
        pool, table = _rig()
        trie = PrefixCache(CHUNK, 1 << 12)
        pid = pool.alloc()
        table.map(0, 0, pid)
        trie.commit([], [1, 2, 3, 4], {"page": pid}, nbytes=64)
        # trie holds it too, but nobody shared: 2 holders, refcount 1
        findings = audit_page_table(pool, table, trie=trie)
        assert len(findings) == 1
        assert "trie@depth" in findings[0].message

    def test_out_of_arena_page(self):
        pool, table = _rig()
        table.array[0, 0] = 5         # never allocated; also a "hole"-free
        pool_small = PagePool(4, CHUNK)  # arena [0, 4): 5 is outside
        findings = audit_page_table(pool_small, table)
        assert any("outside the arena" in f.message for f in findings)

    def test_hole_in_row_prefix_reported_via_table_invariants(self):
        pool, table = _rig()
        pid = pool.alloc()
        table.array[0, 1] = pid       # entry 0 left sentinel: a hole
        findings = audit_page_table(pool, table)
        assert any(f.message.startswith("table:") for f in findings)


class TestHook:
    def test_raises_under_analyze_raise(self):
        pool, table = _rig()
        pid = pool.alloc()
        table.map(0, 0, pid)
        table.map(1, 0, pid)
        with pytest.raises(AnalysisError, match="KV001"):
            check_page_table(pool, table)

    def test_escape_hatch_demotes_to_logging(self, monkeypatch):
        monkeypatch.setattr(edconfig, "analyze_raise", False)
        pool, table = _rig()
        pid = pool.alloc()
        table.map(0, 0, pid)
        table.map(1, 0, pid)
        findings = check_page_table(pool, table)
        assert len(findings) == 1 and findings[0].rule_id == "KV001"

    def test_clean_returns_empty(self):
        pool, table = _rig()
        assert check_page_table(pool, table) == []

    def test_session_audit_fires_on_corruption(self, monkeypatch):
        # corrupt a LIVE paged session's bookkeeping mid-flight: the
        # retire-time hook must catch it
        cfg = gpt.GPTConfig.tiny()
        params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
        # max_decode_slots matches the other serve tests' sessions so the
        # process memo shares ONE set of compiled paged programs in-suite
        sc = ServeConfig(decode_buckets=(32,), max_decode_slots=2,
                         prefill_chunk=8, prefill_batch=2)
        sess = GenerationSession.for_gpt(params, cfg, config=sc)
        sess.submit([1, 2, 3, 4, 5], max_new_tokens=6)
        sess.step()                   # prefill admitted, slot live
        pool = next(iter(sess._pools.values()))
        # double-map the slot's first page into another slot's row
        live = next(r for r in range(pool.table.max_slots)
                    if int(pool.table.array[r, 0]) != pool.table.sentinel)
        pid = int(pool.table.array[live, 0])
        pool.table.map((live + 1) % pool.table.max_slots, 0, pid)
        with pytest.raises(AnalysisError, match="KV001"):
            sess.run_until_drained()


# --------------------------------------------------------------------------
# The vectorised decision against the listed walk: `audit_page_table` must
# return, message for message and in order, what `list_page_table_findings`
# returns, and `page_table_consistent` must hold exactly where the walk
# finds nothing — over seeded pools, clean or with one to three of the
# faults the tests above build by hand.

GEOMETRIES = {"4x4_over_24": (4, 4, 24),
              "64x16_over_1024": (64, 16, 1024),
              "256x16_over_1152": (256, 16, 1152)}
POOLS_PER_CASE = 8      # x 3 geometries x 11 kinds = 264 pools


def _window(table):
    """The part of the array the table's own methods address (a drifted
    array is larger)."""
    return table.array[:table.max_slots, :table.max_pages]


def _live_entries(table):
    window = _window(table)
    return [int(p) for p in window[(window >= 0)
                                   & (window < table.sentinel)]]


def _room(rng, table, spare=0):
    """(slot, idx) of the first unmapped window of a hole-free row with
    `spare` more windows after it."""
    rooms = []
    for slot, row in enumerate(_window(table)):
        n = int((row != table.sentinel).sum())
        if n + spare < table.max_pages \
                and (row[:n] != table.sentinel).all():
            rooms.append((slot, n))
    return rng.choice(rooms)


def _tokens(rng):
    return [rng.randrange(1 << 30) for _ in range(CHUNK)]


def _holder_without_refcount(rng, pool, table, trie):
    slot, idx = _room(rng, table)
    table.array[slot, idx] = rng.choice(_live_entries(table))


def _freed_under_live_entry(rng, pool, table, trie):
    pid = rng.choice([p for p in _live_entries(table)
                      if pool._refcount[p] > 0])
    while pool._refcount[pid] > 0:
        pool.release(pid)


def _twice_on_free_list(rng, pool, table, trie):
    pool._free.append(rng.choice(pool._free))


def _free_page_with_refcount(rng, pool, table, trie):
    pool._refcount[rng.choice(pool._free)] = rng.randint(1, 3)


def _leaked_page(rng, pool, table, trie):
    pool._refcount[pool.alloc()] = rng.choice((0, -1))


def _entry_outside_arena(rng, pool, table, trie):
    slot, idx = _room(rng, table)
    table.array[slot, idx] = table.sentinel + rng.randint(1, 5)


def _negative_entry(rng, pool, table, trie):
    slot, idx = _room(rng, table)
    table.array[slot, idx] = -rng.randint(1, 5)


def _hole_in_prefix(rng, pool, table, trie):
    slot, idx = _room(rng, table, spare=1)
    table.array[slot, idx + 1] = pool.alloc()


def _trie_reference_without_hold(rng, pool, table, trie):
    pid = rng.choice(_live_entries(table) + pool._free)
    trie.commit([], _tokens(rng), {"page": pid}, nbytes=64)


def _drifted_shape(rng, pool, table, trie):
    rows = table.array.shape[0]
    table.array = np.concatenate(
        [table.array, np.full((rows, 1), table.sentinel, np.int32)], axis=1)


# each with the words its finding is known by
FAULTS = {f.__name__.lstrip("_"): (f, words) for f, words in (
    (_holder_without_refcount, "first release frees it"),
    (_freed_under_live_entry, "freed under a live holder"),
    (_twice_on_free_list, "double free"),
    (_free_page_with_refcount, "freed while still referenced"),
    (_leaked_page, "leaked page"),
    (_entry_outside_arena, "outside the arena"),
    (_negative_entry, "entries outside [0,"),
    (_hole_in_prefix, "hole inside the live prefix"),
    (_trie_reference_without_hold, "trie@depth"),
    (_drifted_shape, "table shape drifted"))}


def _sound_pool(rng, slots, max_pages, n_pages, with_trie):
    """A consistent pool at half its arena: three rows in four live with
    1..max_pages windows, some first pages shared between rows, and (with
    a trie) a committed path over one row's first pages."""
    pool = PagePool(n_pages, CHUNK, page_bytes=64)
    table = PageTable(slots, max_pages, n_pages)
    trie = PrefixCache(CHUNK, 1 << 30) if with_trie else None
    first_pages = []
    for slot in rng.sample(range(slots), (3 * slots) // 4):
        for idx in range(rng.randint(1, max_pages)):
            if pool.in_use >= n_pages // 2:
                break
            if idx == 0 and first_pages and rng.random() < 0.2:
                pid = rng.choice(first_pages)
                pool.share(pid)
            else:
                pid = pool.alloc()
            if idx == 0:
                first_pages.append(pid)
            table.map(slot, idx, pid)
    if trie is not None:
        path = []
        for pid in table.mapped(rng.choice(
                [s for s in range(slots) if table.n_mapped(s)]))[:3]:
            path.append(trie.commit(path, _tokens(rng), {"page": pid},
                                    nbytes=64))
            pool.share(pid)
    return pool, table, trie


@pytest.mark.parametrize("kind", ["clean", *FAULTS])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_vector_audit_equals_listed_walk(geometry, kind):
    for i in range(POOLS_PER_CASE):
        rng = random.Random(zlib.crc32(f"{geometry}/{kind}/{i}".encode()))
        with_trie = i % 2 == 0 or kind == "trie_reference_without_hold"
        pool, table, trie = _sound_pool(rng, *GEOMETRIES[geometry],
                                        with_trie)
        assert list_page_table_findings(pool, table, trie) == []
        if kind != "clean":
            inject, words = FAULTS[kind]
            inject(rng, pool, table, trie)
            assert any(words in f.message for f in
                       list_page_table_findings(pool, table, trie))
            others = [k for k in FAULTS if trie is not None
                      or k != "trie_reference_without_hold"]
            for extra in rng.sample(others, rng.randint(0, 2)):
                FAULTS[extra][0](rng, pool, table, trie)

        listed = list_page_table_findings(pool, table, trie, node="p")
        paths = []
        got = audit_page_table(pool, table, trie=trie, node="p",
                               on_path=paths.append)
        assert got == listed
        assert (listed == []) == (kind == "clean")
        assert paths == ["listed" if listed else "vector"]
        assert page_table_consistent(pool, table, trie) == (listed == [])
        # ... and each structure's own half the same way
        for part in (pool, table):
            assert part.check_invariants() == part.list_problems()
            assert part.consistent() == (part.list_problems() == [])
