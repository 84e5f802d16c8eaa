"""Layer 7 paged-KV auditor goldens: KV001 fires exactly once per
violated invariant on known-bad pool/table/trie fixtures, yields zero
findings on clean ones (including a real drained paged session), and the
`check_page_table` hook raises under `analyze_raise` and demotes to
logging with the escape hatch."""

import jax
import pytest

from easydist_tpu import config as edconfig
from easydist_tpu.analyze import audit_page_table, check_page_table
from easydist_tpu.analyze.findings import AnalysisError
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
