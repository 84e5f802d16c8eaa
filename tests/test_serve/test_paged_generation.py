"""The session's paged KV pool: bitwise greedy parity against the
uncached re-forward loop (prefix cache on/off, single-device and tp=2),
ONE compiled decode/prefill signature across mixed lengths, zero-copy
prefix restore, slot/page recycling, fleet handoff, KV gauges, and config
validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.jaxfront.mesh import make_device_mesh
from easydist_tpu.models import gpt, llama
from easydist_tpu.serve import GenerationSession, ServeConfig


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny()
    params = llama.llama_init(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _uncached_greedy(params, cfg, prompt, n_new):
    cur = list(prompt)
    out = []
    for _ in range(n_new):
        logits = gpt.gpt_apply(params, cfg, jnp.asarray([cur]))
        nxt = int(jnp.argmax(logits[0, len(cur) - 1]))
        out.append(nxt)
        cur.append(nxt)
    return out


def _config(**kw):
    kw.setdefault("decode_buckets", (32,))
    # slot count matches test_generation.py's sessions so the sessions
    # below reuse the signatures that file already compiled into the
    # process-wide program memo (a private slot count would re-trace
    # every program just for this file)
    kw.setdefault("max_decode_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_batch", 2)
    return ServeConfig(**kw)


def _run(params, cfg, prompts, n_new=5, mesh=None, factory=None, **kw):
    factory = factory or GenerationSession.for_gpt
    sess = factory(params, cfg, config=_config(**kw), mesh=mesh)
    futs = [sess.submit(p, max_new_tokens=n_new) for p in prompts]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs], sess


MIXED = [[3, 14, 15, 9, 2],                     # shorter than one chunk
         [5, 6, 7, 8, 9, 10, 11, 12, 13],       # crosses a chunk
         [1, 2],
         [9] * 20]                              # crosses a page mid-decode


class TestPagedGreedyParity:
    def test_paged_matches_uncached(self, model):
        cfg, params = model
        paged, _ = _run(params, cfg, MIXED)
        for prompt, ids in zip(MIXED, paged):
            assert ids == _uncached_greedy(params, cfg, prompt, 5)

    def test_prefix_cache_off_parity(self, model):
        cfg, params = model
        on, _ = _run(params, cfg, MIXED)
        off, _ = _run(params, cfg, MIXED, enable_prefix_cache=False)
        assert off == on

    def test_shared_prefix_restore_parity(self, model):
        # followers ride the leader's trie pages (zero-copy restore);
        # their tokens must be bitwise what a cache-off session (which
        # recomputes every prefix through the same compiled programs)
        # produces for the same prompts
        cfg, params = model
        shared = list(range(1, 17))
        prompts = [shared + [20], shared + [21], shared + [22]]
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config())
        lead = sess.submit(prompts[0], max_new_tokens=4)
        sess.run_until_drained()
        follow = [sess.submit(p, max_new_tokens=4) for p in prompts[1:]]
        sess.run_until_drained()
        got = [f.result(timeout=5)["ids"] for f in [lead] + follow]
        control, _ = _run(params, cfg, prompts, n_new=4,
                          enable_prefix_cache=False)
        assert got == control
        assert sess.metrics.counter("copy_on_restore_bytes_saved") > 0

    def test_tp2_parity(self, model, cpu_devices):
        cfg, params = model
        mesh = make_device_mesh((2,), ("tp",), devices=cpu_devices[:2])
        single, _ = _run(params, cfg, MIXED)
        tp2, _ = _run(params, cfg, MIXED, mesh=mesh)
        assert tp2 == single

    def test_llama_gqa_parity(self, llama_model):
        # GQA paged gather (kv_heads < heads) against the eager
        # re-forward reference on the page-crossing prompt — the one
        # whose decode round walks more than one page per kv head
        cfg, params = llama_model
        paged, _ = _run(params, cfg, MIXED,
                        factory=GenerationSession.for_llama)
        cur, want = list(MIXED[3]), []
        for _ in range(5):
            logits = llama.llama_apply(params, cfg, jnp.asarray([cur]))
            nxt = int(jnp.argmax(logits[0, len(cur) - 1]))
            want.append(nxt)
            cur.append(nxt)
        assert paged[3] == want


class TestSignatureConstancy:
    def test_one_decode_one_prefill_signature(self, model, monkeypatch):
        # arbitrary lengths collapse onto ONE page-granular pool: one
        # compiled decode step and one compiled prefill chunk serve
        # every mix.
        # The signature caches are shared process-wide through the
        # session memo (keyed on model config + mesh), so other tests
        # over the same tiny model would leak their signatures into the
        # absolute counts below — isolate with a fresh memo.
        from easydist_tpu.serve import generation as _gen

        monkeypatch.setattr(_gen, "_COMPILED_MEMO", {})
        cfg, params = model
        _, sess = _run(params, cfg, MIXED, n_new=6)
        assert sess.stats()["decode_signatures"]["size"] == 1
        assert sess.stats()["prefill_signatures"]["size"] == 1
        # and they keep serving a second wave of new lengths
        futs = [sess.submit([7] * n, max_new_tokens=3)
                for n in (1, 6, 15, 23)]
        sess.run_until_drained()
        for f in futs:
            assert f.result(timeout=5)["finish_reason"] == "length"
        assert sess.stats()["decode_signatures"]["size"] == 1
        assert sess.stats()["prefill_signatures"]["size"] == 1


class TestZeroCopyRestore:
    def test_restore_is_host_side_only(self, model, monkeypatch):
        # the restore is a table-mapping operation: a session that
        # restored a prefix has built its chunk and its decode program
        # and no other (a fresh memo: the programs are shared by name)
        from easydist_tpu.serve import generation as _gen

        monkeypatch.setattr(_gen, "_COMPILED_MEMO", {})
        cfg, params = model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config())
        shared = list(range(1, 17))
        a = sess.submit(shared + [20], max_new_tokens=3)
        sess.run_until_drained()
        b = sess.submit(shared + [21], max_new_tokens=3)
        sess.run_until_drained()
        assert a.result(timeout=5)["finish_reason"] == "length"
        assert b.result(timeout=5)["finish_reason"] == "length"
        assert sess.metrics.counter("copy_on_restore_bytes_saved") > 0
        assert set(sess._paged_cs) == {"chunk", "decode"}
        assert "restore" not in sess._paged_defs

    def test_saved_bytes_match_restored_pages(self, model):
        cfg, params = model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config())
        shared = list(range(1, 17))           # 2 whole pages of 8
        sess.submit(shared + [20], max_new_tokens=3)
        sess.run_until_drained()
        assert sess.metrics.counter("copy_on_restore_bytes_saved") == 0
        sess.submit(shared + [21], max_new_tokens=3)
        sess.run_until_drained()
        pool = next(iter(sess._pools.values()))
        assert sess.metrics.counter("copy_on_restore_bytes_saved") == \
            2 * pool.page_bytes


class TestRecycling:
    def test_more_requests_than_slots_recycles_pages(self, model):
        cfg, params = model
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab, size=3 + i % 7).tolist()
                   for i in range(8)]
        ids, sess = _run(params, cfg, prompts, n_new=4)
        for prompt, got in zip(prompts, ids):
            assert got == _uncached_greedy(params, cfg, prompt, 4)
        st = sess.stats()["buckets"][32]
        assert st["active"] == 0 and st["kv_table_mapped"] == 0
        # drained: only trie-held pages remain in use
        pool = next(iter(sess._pools.values()))
        trie_pages = sum(1 for n in pool.trie._walk()
                         if isinstance(n.kv, dict) and "page" in n.kv)
        assert st["kv_pool"]["in_use"] == trie_pages

    def test_evacuate_releases_pages(self, model):
        cfg, params = model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config())
        futs = [sess.submit(p, max_new_tokens=10) for p in MIXED]
        sess.step()                          # mid-flight
        sess.evacuate()
        for f in futs:
            assert not f.done() or f.result()["finish_reason"] in \
                ("evacuated", "length")
        st = sess.stats()["buckets"].get(32)
        if st is not None:
            assert st["kv_table_mapped"] == 0


class TestCapacityWall:
    @pytest.mark.parametrize("spec_k", [0, 2])
    def test_a_sequence_that_reaches_the_cap_retires_bucket_full(
            self, model, spec_k):
        # 28 prompt tokens in a cap of 32: the first token and four decoded
        # ones fill it, whatever `max_new_tokens` asked for — also under
        # speculation, whose verify window (pos + k + 1) no longer fits
        # the row near the wall, so it rides the plain round beside the
        # short row's verify rounds
        cfg, params = model
        at_wall, short = ([7, 8, 9] * 10)[:28], [7, 8, 9, 7, 8, 9, 7]
        sess = GenerationSession.for_gpt(
            params, cfg, config=_config(speculate_k=spec_k))
        futs = [sess.submit(p, max_new_tokens=10) for p in (at_wall, short)]
        sess.run_until_drained()
        full, fits = (f.result(timeout=5) for f in futs)
        assert full["finish_reason"] == "bucket_full"
        assert full["ids"] == _uncached_greedy(params, cfg, at_wall, 5)
        assert fits["finish_reason"] == "length"
        assert fits["ids"] == _uncached_greedy(params, cfg, short, 10)
        assert sess.stats()["buckets"][32]["kv_table_mapped"] == 0
        assert (sess.metrics.counter("verify_steps") > 0) == bool(spec_k)


class TestKvMetrics:
    def test_gauges_surface(self, model):
        cfg, params = model
        _, sess = _run(params, cfg, MIXED)
        snap = sess.metrics.snapshot()
        assert snap["gauges"]["kv_pages_in_use"] >= 0
        assert 0.0 < snap["gauges"]["kv_page_utilization"] <= 1.0
        st = sess.stats()["buckets"][32]
        assert st["kv_pool"]["n_pages"] > 0
        assert st["kv_pool"]["allocs"] >= st["kv_pool"]["frees"]

    def test_a_round_counts_the_pages_under_its_live_rows(self, model):
        # what the paged decode kernel walks a layer, against the pool's
        # bucket: a request of L prompt tokens decodes its tokens 2..n at
        # positions L, L + 1, ..., each under pos // 8 + 1 pages of 8, and
        # every round runs 2 slots of a 4-page bucket
        cfg, params = model
        n_new = 5
        _, sess = _run(params, cfg, MIXED, n_new=n_new)
        counters = sess.metrics.snapshot()["counters"]
        assert counters["decode_pages_walked"] == sum(
            (len(p) + r) // 8 + 1 for p in MIXED for r in range(n_new - 1))
        assert counters["decode_pages_bucket"] == \
            counters["decode_steps"] * 2 * 4
        assert counters["tokens_generated"] == len(MIXED) * (n_new - 1)

    def test_gauge_tracks_pool_occupancy(self, model):
        # 12 prompt + 4 new = 16 tokens: exactly 2 pages reserved at
        # admission (the peak); the final decode round retires the slot,
        # so the last gauge sample sees only the trie-committed prefix
        # page (12 // 8 = 1 whole chunk) still resident.  Default config
        # on purpose: unique slot counts would compile a private decode
        # signature instead of sharing the file's memoized programs
        cfg, params = model
        sess = GenerationSession.for_gpt(
            params, cfg, config=_config())
        sess.submit(list(range(1, 13)), max_new_tokens=4)
        sess.run_until_drained()
        pool = next(iter(sess._pools.values()))
        assert pool.pool.stats()["peak_in_use"] == 2
        assert pool.pool.in_use == 1
        assert sess.metrics.snapshot()["gauges"]["kv_pages_in_use"] == 1


class TestFleetHandoff:
    SHARED = list(range(1, 17))

    def _leader(self, params, cfg):
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config(),
                                         replica_id="lead")
        sess.submit(self.SHARED + [20], max_new_tokens=3)
        sess.run_until_drained()
        return sess

    def test_export_import_parity(self, model):
        # exports materialize {"page": id} refs into real chunk arrays,
        # so a path means the same to an arena that numbers its pages
        # otherwise
        cfg, params = model
        lead = self._leader(params, cfg)
        path = lead.export_prefix_path(self.SHARED + [21])
        assert path and all(set(kv) == {"k", "v"} for _, kv in path)
        dst_sess = GenerationSession.for_gpt(params, cfg,
                                             config=_config(),
                                             replica_id="dst")
        assert dst_sess.import_prefix_path(self.SHARED + [21], path) == \
            len(path)
        fut = dst_sess.submit(self.SHARED + [21], max_new_tokens=3)
        dst_sess.run_until_drained()
        assert fut.result(timeout=5)["ids"] == \
            _uncached_greedy(params, cfg, self.SHARED + [21], 3)

    def test_hot_pages_roundtrip(self, model):
        cfg, params = model
        lead = self._leader(params, cfg)
        hot = lead.export_hot_pages()
        dst = GenerationSession.for_gpt(params, cfg,
                                        config=_config(),
                                        replica_id="dst")
        assert dst.import_hot_pages(hot) > 0
        fut = dst.submit(self.SHARED + [22], max_new_tokens=3)
        dst.run_until_drained()
        assert fut.result(timeout=5)["ids"] == \
            _uncached_greedy(params, cfg, self.SHARED + [22], 3)
        assert dst.metrics.counter("copy_on_restore_bytes_saved") > 0


class TestConfigValidation:
    def test_bad_layout_rejected(self):
        with pytest.raises(ValueError, match="kv_layout"):
            ServeConfig(decode_buckets=(32,), kv_layout="ragged")

    def test_page_tokens_must_match_trie_chunk(self):
        with pytest.raises(ValueError, match="kv_page_tokens"):
            ServeConfig(decode_buckets=(32,), prefill_chunk=8,
                        kv_page_tokens=4)

    def test_negative_arena_rejected(self):
        with pytest.raises(ValueError, match="kv_arena_pages"):
            ServeConfig(decode_buckets=(32,), kv_arena_pages=-1)
