"""GenerationSession: continuous-batching KV-cached decode over the
jaxfront signature cache — greedy parity, slot recycling, signature
constancy, donation audit (SERVE001), config validation, metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.analyze import audit_decode_donation, check_decode_donation
from easydist_tpu.jaxfront import easydist_compile
from easydist_tpu.jaxfront.mesh import make_device_mesh
from easydist_tpu.models import gpt
from easydist_tpu.serve import (GenerationSession, RequestTooLargeError,
                                ServeConfig, kv_cache_specs)


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
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


def _session(cfg, params, **kw):
    sc = kw.pop("config", None) or ServeConfig(decode_buckets=(cfg.seq,),
                                               max_decode_slots=2)
    return GenerationSession.for_gpt(params, cfg, config=sc, **kw)


class TestGreedyParity:
    def test_single_request(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        prompt = [3, 14, 15, 9, 2]
        fut = sess.submit(prompt, max_new_tokens=6)
        sess.run_until_drained()
        out = fut.result(timeout=5)
        assert out["ids"] == _uncached_greedy(params, cfg, prompt, 6)
        assert out["finish_reason"] == "length"

    def test_more_requests_than_slots_recycles(self, model):
        """6 requests through 2 slots: retirements must free slots so
        later requests are admitted mid-flight, and every request's ids
        still match its own uncached loop."""
        cfg, params = model
        sess = _session(cfg, params)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab, size=3 + i % 4).tolist()
                   for i in range(6)]
        futs = [sess.submit(p, max_new_tokens=4) for p in prompts]
        sess.run_until_drained()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=5)["ids"] == \
                _uncached_greedy(params, cfg, p, 4)
        st = sess.stats()
        assert st["pending"] == 0
        assert st["buckets"][cfg.seq]["active"] == 0
        assert st["buckets"][cfg.seq]["free"] == 2

    def test_eos_retires_early(self, model):
        cfg, params = model
        prompt = [3, 14, 15, 9, 2]
        ref = _uncached_greedy(params, cfg, prompt, 8)
        eos = ref[2]  # a token the greedy run is known to produce
        sess = _session(cfg, params, eos_id=eos)
        fut = sess.submit(prompt, max_new_tokens=8)
        sess.run_until_drained()
        out = fut.result(timeout=5)
        assert out["finish_reason"] == "eos"
        # generation stops at the FIRST occurrence of eos, inclusive
        assert out["ids"] == ref[:ref.index(eos) + 1]

    def test_tp2_sharded_cache_parity(self, model):
        cfg, params = model
        ref_sess = _session(cfg, params)
        prompt = [7, 1, 4, 4]
        rf = ref_sess.submit(prompt, max_new_tokens=5)
        ref_sess.run_until_drained()
        mesh = make_device_mesh((2,), ("tp",), devices=jax.devices()[:2])
        sess = _session(cfg, params, mesh=mesh)
        fut = sess.submit(prompt, max_new_tokens=5)
        sess.run_until_drained()
        assert fut.result(timeout=5)["ids"] == \
            rf.result(timeout=5)["ids"]


class TestSignatureCache:
    def test_one_compiled_decode_step_across_tokens(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        # the store is shared with earlier same-model sessions via the
        # process-level compile memo, so count growth, not absolute size
        base = sess.stats()["decode_signatures"]
        f1 = sess.submit([1, 2, 3], max_new_tokens=5)
        sess.run_until_drained()
        sigs_after_first = sess.stats()["decode_signatures"]["size"]
        f2 = sess.submit([9, 8, 7, 6, 5], max_new_tokens=7)
        sess.run_until_drained()
        st = sess.stats()["decode_signatures"]
        assert sigs_after_first == st["size"] <= base["size"] + 1
        # looked up once, by the pool's first round: every later round
        # launched the program the pool holds
        assert st["hits"] + st["misses"] == \
            base["hits"] + base["misses"] + 1
        assert sess.metrics.counter("decode_steps") >= 10
        f1.result(timeout=5), f2.result(timeout=5)

    def test_prefill_signatures_closed_by_padding(self, model):
        """Prompt lengths 2..8 collapse into the pow2 prefill pads."""
        cfg, params = model
        sess = _session(cfg, params)
        base = sess.stats()["prefill_signatures"]["size"]  # shared store
        for n in (2, 3, 5, 7, 8):
            sess.submit(list(range(1, n + 1)), max_new_tokens=2)
        sess.run_until_drained()
        # pads: 8 (for <=8) only -> at most one NEW prefill signature
        assert sess.stats()["prefill_signatures"]["size"] <= base + 1


class TestDonationAudit:
    def test_default_build_is_clean(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        fut = sess.submit([5, 6], max_new_tokens=3)
        sess.run_until_drained()
        fut.result(timeout=5)
        # the program the rounds launched, as the pool holds it
        res = sess._pools[cfg.seq].held["decode"]
        assert res.name == "_decode_paged"
        assert audit_decode_donation(res) == []

    def test_fires_exactly_once_without_donation(self, model):
        cfg, params = model

        def _decode(pool, prm, token, pos):
            pool, logits = gpt.gpt_decode_step(prm, cfg, pool, token, pos)
            return pool, jnp.argmax(logits, -1).astype(jnp.int32)

        c = easydist_compile(_decode, donate_state=False)
        res = c.get_compiled(gpt.init_kv_cache(cfg, 2, cfg.seq), params,
                             jnp.zeros((2,), jnp.int32),
                             jnp.zeros((2,), jnp.int32))
        findings = audit_decode_donation(res)
        assert len(findings) == 1
        assert findings[0].rule_id == "SERVE001"
        assert findings[0].severity == "warning"
        # the hook logs but never raises (slow, not wrong)
        assert len(check_decode_donation(res)) == 1

    def test_kv_cache_specs_shards_heads(self):
        specs = kv_cache_specs("tp")
        assert specs["k"][2] == "tp" and specs["v"][2] == "tp"
        assert specs["k"][0] is None and specs["k"][3] is None


class TestAdmissionAndConfig:
    def test_prompt_too_large_rejected(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        with pytest.raises(RequestTooLargeError):
            sess.submit(list(range(cfg.seq)), max_new_tokens=1)

    def test_empty_prompt_and_bad_max_new(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        with pytest.raises(ValueError):
            sess.submit([], max_new_tokens=1)
        with pytest.raises(ValueError):
            sess.submit([1], max_new_tokens=0)

    def test_bucket_beyond_model_seq_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="decode_buckets"):
            GenerationSession.for_gpt(
                params, cfg,
                config=ServeConfig(decode_buckets=(cfg.seq * 2,)))

    @pytest.mark.parametrize("kw", [
        dict(decode_buckets=()),
        dict(decode_buckets=(0,)),
        dict(kv_cache_dtype="not-a-dtype"),
        dict(max_decode_slots=0),
    ])
    def test_serveconfig_validation(self, kw):
        with pytest.raises(ValueError):
            ServeConfig(**kw)

    def test_serveconfig_accepts_new_knobs(self):
        sc = ServeConfig(decode_buckets=(128, 512),
                         kv_cache_dtype="bfloat16", max_decode_slots=4)
        assert sc.decode_buckets == (128, 512)

    def test_kv_cache_dtype_applied(self, model):
        cfg, params = model
        sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                         kv_cache_dtype="bfloat16")
        sess = GenerationSession.for_gpt(params, cfg, config=sc)
        fut = sess.submit([1, 2, 3], max_new_tokens=2)
        sess.run_until_drained()
        fut.result(timeout=5)
        assert all(leaf.dtype == jnp.bfloat16
                   for leaf in sess._pools[cfg.seq].arena["k"])


class TestMetrics:
    def test_decode_metrics_recorded(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        futs = [sess.submit([1, 2, 3], max_new_tokens=4),
                sess.submit([4, 5], max_new_tokens=4)]
        sess.run_until_drained()
        [f.result(timeout=5) for f in futs]
        snap = sess.metrics.snapshot()
        # 8 tokens total; 2 came from the prefills' argmax
        assert snap["counters"]["tokens_generated"] == 6
        assert snap["counters"]["requests_submitted"] == 2
        assert snap["counters"]["requests_completed"] == 2
        assert snap["counters"]["prefills"] == 2
        assert 0.0 < snap["gauges"]["decode_slot_occupancy"] <= 1.0
        assert snap["latency"]["per_token"]["count"] > 0

    def test_metrics_export_to_perfdb(self, model):
        cfg, params = model
        sess = _session(cfg, params)
        fut = sess.submit([1, 2], max_new_tokens=2)
        sess.run_until_drained()
        fut.result(timeout=5)
        db = sess.metrics.export(sub_key="generation_test", persist=False)
        hist = db.get_op_perf("serving", "generation_test")
        assert hist and "per_token" in hist[-1]["latency"]


def _chunked_config(cfg, **kw):
    kw.setdefault("decode_buckets", (cfg.seq,))
    kw.setdefault("max_decode_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_batch", 2)
    return ServeConfig(**kw)


class TestPrefixReuse:
    def test_prefix_on_off_bitwise_identical(self, model):
        """The prefix cache is a pure latency optimization: cache-on and
        cache-off sessions emit identical greedy ids, and both match the
        full uncached re-forward."""
        cfg, params = model
        rng = np.random.RandomState(7)
        shared = rng.randint(0, cfg.vocab, size=16).tolist()
        prompts = [shared + [i + 1] for i in range(4)]

        outs = {}
        for on in (True, False):
            sess = _session(cfg, params, config=_chunked_config(
                cfg, enable_prefix_cache=on))
            # first prompt alone so its chunks are committed before the
            # others look them up
            f0 = sess.submit(prompts[0], max_new_tokens=4)
            sess.run_until_drained()
            futs = [sess.submit(p, max_new_tokens=4) for p in prompts[1:]]
            sess.run_until_drained()
            outs[on] = [f0.result(timeout=5)["ids"]] + [
                f.result(timeout=5)["ids"] for f in futs]
            if on:
                st = sess.stats()["buckets"][cfg.seq]["prefix_cache"]
                assert st["hits"] >= 6        # 3 followers x 2 chunks
                assert st["nodes"] >= 2
            else:
                assert sess.stats()["buckets"][cfg.seq][
                    "prefix_cache"] is None
        assert outs[True] == outs[False]
        for p, ids in zip(prompts, outs[True]):
            assert ids == _uncached_greedy(params, cfg, p, 4)

    def test_hit_rate_and_padding_metrics(self, model):
        cfg, params = model
        sess = _session(cfg, params, config=_chunked_config(cfg))
        shared = list(range(1, 17))
        sess.submit(shared + [20], max_new_tokens=2)
        sess.run_until_drained()
        assert sess.metrics.prefix_cache_hit_rate() == 0.0
        sess.submit(shared + [21], max_new_tokens=2)
        sess.run_until_drained()
        # follower reused 16 of (17+17-1) admitted prefill tokens
        assert sess.metrics.prefix_cache_hit_rate() == \
            pytest.approx(16 / 34)
        # padded slots (rows x chunk per call) never undershoot real work
        assert sess.metrics.prefill_padding_ratio() >= 1.0
        snap = sess.metrics.snapshot()
        assert snap["prefix_cache_hit_rate"] == pytest.approx(16 / 34)
        assert snap["prefill_padding_ratio"] >= 1.0
        assert snap["latency"]["ttft"]["count"] == 2

    def test_chunked_prefill_single_signature(self, model):
        """Prompt lengths 2..17 all run through ONE compiled chunk
        program (fixed [rows, chunk] window) — no per-length retraces."""
        cfg, params = model
        sess = _session(cfg, params, config=_chunked_config(cfg))
        base = sess.stats()["prefill_signatures"]  # shared store
        for n in (2, 3, 7, 9, 17):
            sess.submit(list(range(1, n + 1)), max_new_tokens=2)
        sess.run_until_drained()
        sig = sess.stats()["prefill_signatures"]
        assert sig["size"] <= base["size"] + 1
        assert sig["misses"] <= base["misses"] + 1
        assert sess.metrics.counter("prefill_chunks") >= 4

    def test_ttft_recorded_per_request(self, model):
        cfg, params = model
        sess = _session(cfg, params, config=_chunked_config(cfg))
        for _ in range(3):
            sess.submit([4, 8, 2], max_new_tokens=2)
        sess.run_until_drained()
        assert sess.metrics.snapshot()["latency"]["ttft"]["count"] == 3


class TestSlotReuseDeterminism:
    def test_readmit_into_freed_slots_is_bitwise_deterministic(self, model):
        """Retire one slot via EOS and one by filling its bucket, re-admit
        a queued prompt into the freed slot mid-flight, and require its
        ids to be bitwise identical to a fresh session's."""
        cfg, params = model
        rng = np.random.RandomState(3)
        p_eos = rng.randint(0, cfg.vocab, size=5).tolist()
        ref_eos = _uncached_greedy(params, cfg, p_eos, 8)
        # EOS = the first reference token that has not occurred before it
        # (random weights repeat themselves: under jax 0.9 this reference
        # is [2, 2, 2, ...], and an EOS taken at a fixed index fired on the
        # first token) — the slot retires after n_eos tokens
        n_eos = next(i for i in range(1, 8)
                     if ref_eos[i] not in ref_eos[:i]) + 1
        eos = ref_eos[n_eos - 1]
        p_full = rng.randint(0, cfg.vocab, size=9).tolist()
        full_new = cfg.seq - len(p_full)      # runs into the bucket wall
        p_next = rng.randint(0, cfg.vocab, size=11).tolist()

        sess = _session(cfg, params, config=_chunked_config(cfg))
        f_eos = sess.submit(p_eos, max_new_tokens=8, eos_id=eos)
        f_full = sess.submit(p_full, max_new_tokens=full_new)
        f_next = sess.submit(p_next, max_new_tokens=5)  # queued: slots busy
        # step until the EOS retirement frees a slot and p_next is
        # admitted while p_full is still decoding (mid-flight re-admit)
        for _ in range(200):
            sess.step()
            pool = sess._pools[cfg.seq]
            if not sess._pending and f_eos.done():
                break
        assert f_eos.done() and not f_full.done()
        sess.run_until_drained()
        assert f_eos.result(timeout=5)["finish_reason"] == "eos"
        assert f_eos.result(timeout=5)["ids"] == ref_eos[:n_eos]
        assert f_full.result(timeout=5)["ids"] == \
            _uncached_greedy(params, cfg, p_full, full_new)

        fresh = _session(cfg, params, config=_chunked_config(cfg))
        f_ref = fresh.submit(p_next, max_new_tokens=5)
        fresh.run_until_drained()
        assert f_next.result(timeout=5)["ids"] == \
            f_ref.result(timeout=5)["ids"]
        assert f_next.result(timeout=5)["ids"] == \
            _uncached_greedy(params, cfg, p_next, 5)


class TestInterleaveBound:
    def test_prefill_pressure_bounded_per_step(self, model):
        """With prefill_chunks_per_step=1 a 3-chunk prompt cannot finish
        prefill in one step, and the live request still decodes every
        step (decode p99 stays bounded during long prefills)."""
        cfg, params = model
        sess = _session(cfg, params, config=_chunked_config(
            cfg, prefill_chunks_per_step=1))
        f_live = sess.submit([5, 9, 2], max_new_tokens=20)
        sess.step()                           # admit + prefill + 1 decode
        pool = sess._pools[cfg.seq]
        assert pool.n_active == 1
        sess.submit(list(range(1, 18)), max_new_tokens=2)  # 3 chunks
        live_before = len(sess._pools[cfg.seq].slots)
        tokens = sess.step()
        assert len(pool.jobs) == 1            # prefill NOT finished
        assert tokens >= 1                    # the live slot still decoded
        sess.step()
        assert len(pool.jobs) == 1            # chunk 2 of 3 ran
        sess.run_until_drained()
        assert f_live.result(timeout=5)["ids"] == \
            _uncached_greedy(params, cfg, [5, 9, 2], 20)
