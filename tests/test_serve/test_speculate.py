"""Speculative decoding: the draft/verify/accept path must be a pure
speed knob — bitwise greedy parity vs plain decode for gpt and llama,
1-device and tp=2, across mid-stream accept/reject boundaries; plus
drafter units, verify write locality, rollback page release, signature
closure, knob validation, and the speculation metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.jaxfront.mesh import make_device_mesh
from easydist_tpu.models import gpt, llama
from easydist_tpu.serve import GenerationSession, ServeConfig
from easydist_tpu.serve.speculate import (NGramDrafter, SmallModelDrafter,
                                          accept_length)

# repetitive prompts the n-gram drafter can actually draft from (tiny
# random models fall into greedy cycles fast, so these ALSO produce
# accepting rounds mid-stream — the parity tests cross accept/reject
# boundaries, not just all-reject rounds)
REPETITIVE = [[5, 6, 5, 6, 5, 6, 5], [9, 3, 9, 3, 9, 3, 9, 3, 9],
              [1, 2, 3, 1, 2, 3, 1]]


@pytest.fixture(scope="module")
def gpt_model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny()
    params = llama.llama_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _config(spec_k=0, **kw):
    base = dict(decode_buckets=(32,), max_decode_slots=2,
                prefill_chunk=8, prefill_batch=2, speculate_k=spec_k)
    base.update(kw)
    return ServeConfig(**base)


def _drain(sess, prompts, max_new):
    futs = [sess.submit(p, max_new_tokens=max_new) for p in prompts]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs]


def _uncached_greedy(params, cfg, prompt, n_new):
    cur = list(prompt)
    out = []
    for _ in range(n_new):
        logits = gpt.gpt_apply(params, cfg, jnp.asarray([cur]))
        out.append(int(jnp.argmax(logits[0, len(cur) - 1])))
        cur.append(out[-1])
    return out


# --------------------------------------------------------------- units
class TestAcceptRule:
    def test_full_partial_none(self):
        assert accept_length([1, 2, 3], [1, 2, 3]) == 3
        assert accept_length([1, 2, 3], [1, 2, 9]) == 2
        assert accept_length([1, 2, 3], [9, 2, 3]) == 0
        assert accept_length([], [1, 2]) == 0

    def test_never_counts_past_first_mismatch(self):
        # a re-match AFTER a mismatch must not resurrect acceptance
        assert accept_length([1, 9, 3], [1, 2, 3]) == 1


class TestNGramDrafter:
    def test_finds_trailing_ngram_continuation(self):
        d = NGramDrafter()
        # trailing [5, 6] occurred before, followed by 7, 8
        assert d.propose(0, [5, 6, 7, 8, 5, 6], 2) == [7, 8]

    def test_prefers_longest_ngram_and_most_recent(self):
        d = NGramDrafter()
        # trailing [1, 2] occurs twice earlier; the MOST RECENT prior
        # occurrence (followed by 9) wins over the older one (3)
        assert d.propose(0, [1, 2, 3, 1, 2, 9, 1, 2], 1) == [9]

    def test_none_without_recurrence(self):
        d = NGramDrafter()
        assert d.propose(0, [1, 2, 3, 4, 5], 3) is None

    def test_pure_function_of_sequence(self):
        d = NGramDrafter()
        seq = [4, 4, 2, 4, 4]
        assert d.propose(0, seq, 3) == d.propose(99, list(seq), 3)

    def test_bad_ngram_bounds_raise(self):
        with pytest.raises(ValueError):
            NGramDrafter(max_ngram=1, min_ngram=2)


class TestSmallModelDrafter:
    def test_proposes_draft_models_own_greedy(self, gpt_model):
        cfg, params = gpt_model
        d = SmallModelDrafter(
            params,
            model_decode=lambda p, c, t, pos: gpt.gpt_decode_step(
                p, cfg, c, t, pos),
            init_cache=lambda b, L: gpt.init_kv_cache(cfg, b, L),
            max_len=cfg.seq)
        prompt = [3, 14, 15, 9, 2]
        got = d.propose(0, prompt, 4)
        want = _uncached_greedy(params, cfg, prompt, 4)
        assert got == want

    def test_resyncs_after_rejected_drafts(self, gpt_model):
        """When the committed sequence diverges from what was fed
        (rejected drafts), the cursor rolls back to the common prefix
        and proposals still match a fresh drafter's — cache rewind by
        overwrite is exact."""
        cfg, params = gpt_model
        mk = lambda: SmallModelDrafter(
            params,
            model_decode=lambda p, c, t, pos: gpt.gpt_decode_step(
                p, cfg, c, t, pos),
            init_cache=lambda b, L: gpt.init_kv_cache(cfg, b, L),
            max_len=cfg.seq)
        stale, fresh = mk(), mk()
        prompt = [3, 14, 15, 9, 2]
        stale.propose(0, prompt, 4)     # feeds prompt + its own drafts
        committed = prompt + [1, 7]     # target went another way
        assert stale.propose(0, committed, 3) == \
            fresh.propose(0, committed, 3)

    def test_forget_drops_state(self, gpt_model):
        cfg, params = gpt_model
        d = SmallModelDrafter(
            params,
            model_decode=lambda p, c, t, pos: gpt.gpt_decode_step(
                p, cfg, c, t, pos),
            init_cache=lambda b, L: gpt.init_kv_cache(cfg, b, L),
            max_len=cfg.seq)
        d.propose(0, [1, 2, 3], 2)
        assert 0 in d._states
        d.forget(0)
        assert 0 not in d._states


class TestVerifyWriteLocality:
    def test_verify_writes_only_at_pos_window(self, gpt_model):
        """The verify step must leave committed rows (< pos) bitwise
        untouched — that is what makes a 'rollback' by the cursor's
        not advancing correct — and only write [pos, pos+k+1)."""
        cfg, params = gpt_model
        k = 3
        rng = np.random.RandomState(0)
        cache = {kk: jnp.asarray(rng.randn(*v.shape), v.dtype)
                 for kk, v in gpt.init_kv_cache(cfg, 1, cfg.seq).items()}
        p = 10
        tokens = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
        new, logits = gpt.gpt_verify_step(params, cfg, cache, tokens,
                                          jnp.asarray([p], jnp.int32))
        assert logits.shape == (1, k + 1, cfg.vocab)
        for kk in ("k", "v"):
            old_a, new_a = np.asarray(cache[kk]), np.asarray(new[kk])
            assert (old_a[:, :, :, :p] == new_a[:, :, :, :p]).all()
            assert (old_a[:, :, :, p + k + 1:] ==
                    new_a[:, :, :, p + k + 1:]).all()
            assert not (old_a[:, :, :, p:p + k + 1] ==
                        new_a[:, :, :, p:p + k + 1]).all()


# ------------------------------------------------------- greedy parity
class TestGreedyParityGPT:
    def test_matches_uncached_and_plain(self, gpt_model):
        cfg, params = gpt_model
        plain = _drain(GenerationSession.for_gpt(
            params, cfg, config=_config()), REPETITIVE, 12)
        spec = _drain(GenerationSession.for_gpt(
            params, cfg, config=_config(spec_k=3)), REPETITIVE, 12)
        assert spec == plain
        for p, ids in zip(REPETITIVE, spec):
            assert ids == _uncached_greedy(params, cfg, p, 12)

    def test_paged_matches_plain_and_releases_rollback_pages(
            self, gpt_model):
        """A verify round that spills past the slot's page reservation
        must run the rollback path (unmap_tail + page release) and leave
        the stream untouched.  Which rounds the n-gram drafter and the
        acceptance gate let through depends on the random model's greedy
        cycles (under jax 0.9 no round reached the spill), so the drafter
        here is a stub: it always proposes a token the target never
        picks, every request's FIRST verify round runs (the gate has no
        history yet), accepts nothing and commits one token."""
        cfg, params = gpt_model
        # 12-token prompts + max_new 4 -> 2-page reservations (page = 8
        # tokens); the first verify at pos 12 writes k + 1 = 5 rows up to
        # pos 16 and so maps a third page, with the slot still alive after
        prompts = [[5, 6] * 6, [9, 3] * 6]
        config = dict(enable_prefix_cache=False)  # no trie-held pages
        plain = _drain(GenerationSession.for_gpt(
            params, cfg, config=_config(**config)), prompts, 4)
        wrong = next(t for t in range(cfg.vocab)
                     if all(t not in ids for ids in plain))

        class NeverRight:
            def propose(self, request_id, ids, k):
                return [wrong] * k

            def forget(self, request_id):
                pass

        sess = GenerationSession.for_gpt(
            params, cfg, config=_config(spec_k=4, **config),
            drafter=NeverRight())
        spec = _drain(sess, prompts, 4)
        assert spec == plain
        m = sess.stats()["metrics"]["counters"]
        assert m.get("verify_steps", 0) > 0
        assert m.get("draft_tokens_accepted", 0) == 0
        assert m.get("speculative_rollback_pages_released", 0) > 0
        # rollback returned every spill page: all arena pages free again
        pool = sess._pools[max(sess.config.decode_buckets)]
        assert pool.pool.in_use == 0

    def test_eos_mid_verify_round_retires_exactly(self, gpt_model):
        """eos appearing INSIDE an accepted run must stop the commit
        walk at eos, same stream as plain decode."""
        cfg, params = gpt_model
        prompt = REPETITIVE[0]
        ref = _uncached_greedy(params, cfg, prompt, 12)
        eos = ref[len(ref) // 2]
        plain = GenerationSession.for_gpt(params, cfg, config=_config(),
                                          eos_id=eos)
        pf = plain.submit(prompt, max_new_tokens=12)
        plain.run_until_drained()
        spec = GenerationSession.for_gpt(
            params, cfg, config=_config(spec_k=3), eos_id=eos)
        sf = spec.submit(prompt, max_new_tokens=12)
        spec.run_until_drained()
        assert sf.result(timeout=5)["ids"] == pf.result(timeout=5)["ids"]
        assert sf.result(timeout=5)["finish_reason"] == "eos"
        assert pf.result(timeout=5)["finish_reason"] == "eos"

    def test_tp2_spec_parity(self, gpt_model):
        cfg, params = gpt_model
        ref = _drain(GenerationSession.for_gpt(
            params, cfg, config=_config(spec_k=3)), REPETITIVE[:2], 8)
        mesh = make_device_mesh((2,), ("tp",), devices=jax.devices()[:2])
        got = _drain(GenerationSession.for_gpt(
            params, cfg, config=_config(spec_k=3), mesh=mesh),
            REPETITIVE[:2], 8)
        assert got == ref


class TestGreedyParityLlama:
    def test_matches_plain(self, llama_model):
        cfg, params = llama_model
        plain = _drain(GenerationSession.for_llama(
            params, cfg, config=_config()), REPETITIVE, 10)
        spec = _drain(GenerationSession.for_llama(
            params, cfg, config=_config(spec_k=3)), REPETITIVE, 10)
        assert spec == plain

    def test_draft_model_drafter_parity(self, llama_model):
        """A second tiny llama as drafter: different weights, different
        proposals — identical committed stream."""
        cfg, params = llama_model
        dcfg = llama.LlamaConfig.tiny(dim=16, heads=2, kv_heads=1,
                                      ffn_dim=32, layers=1)
        dparams = llama.llama_init(dcfg, jax.random.PRNGKey(1))
        plain = _drain(GenerationSession.for_llama(
            params, cfg, config=_config()), REPETITIVE[:2], 8)
        spec = _drain(GenerationSession.for_llama(
            params, cfg,
            config=_config(spec_k=3, speculate_drafter="draft_model"),
            draft_model=(dparams, dcfg)), REPETITIVE[:2], 8)
        assert spec == plain


class TestDraftModelDrafterGPT:
    def test_self_draft_accepts_everything(self, gpt_model):
        """The target model drafting for itself accepts every draft —
        the acceptance-rate ceiling, and a strong end-to-end check that
        verify positions line up with decode positions."""
        cfg, params = gpt_model
        sess = GenerationSession.for_gpt(
            params, cfg,
            config=_config(spec_k=3, speculate_drafter="draft_model"),
            draft_model=(params, cfg))
        ids = _drain(sess, [REPETITIVE[0]], 10)[0]
        assert ids == _uncached_greedy(params, cfg, REPETITIVE[0], 10)
        m = sess.stats()["metrics"]
        assert m["gauges"]["acceptance_rate"] == pytest.approx(1.0)


# ------------------------------------------------- signatures & config
class TestSignatureClosure:
    def test_one_verify_signature_per_bucket(self, gpt_model):
        cfg, params = gpt_model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config(spec_k=3))
        base = (sess.stats()["verify_signatures"] or {}).get("size", 0)
        _drain(sess, REPETITIVE, 12)
        _drain(sess, [[2, 8, 2, 8, 2, 8]], 10)
        st = sess.stats()["verify_signatures"]
        assert st["size"] <= base + 1
        assert st["misses"] <= base + 1

    def test_paged_one_verify_signature_total(self, gpt_model):
        cfg, params = gpt_model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config(spec_k=4))
        base = (sess.stats()["verify_signatures"] or {}).get("size", 0)
        _drain(sess, REPETITIVE, 9)
        st = sess.stats()["verify_signatures"]
        assert st["size"] <= base + 1

    def test_spec_off_session_reports_no_verify_sigs(self, gpt_model):
        cfg, params = gpt_model
        sess = GenerationSession.for_gpt(params, cfg, config=_config())
        _drain(sess, [[1, 2, 3]], 3)
        # the shared memo may carry another session's verify programs;
        # a spec-off session just never compiles or runs one
        assert sess._spec_k == 0 and sess._drafter is None


class TestKnobValidation:
    def test_negative_k_rejected(self):
        with pytest.raises(ValueError, match="speculate_k"):
            _config(spec_k=-1)

    def test_unknown_drafter_rejected(self):
        with pytest.raises(ValueError, match="speculate_drafter"):
            _config(spec_k=2, speculate_drafter="oracle")

    def test_k_must_leave_bucket_headroom(self):
        with pytest.raises(ValueError, match="headroom"):
            _config(spec_k=31)  # k + 1 == smallest bucket

    def test_draft_model_without_drafter_rejected(self, gpt_model):
        cfg, params = gpt_model
        with pytest.raises(ValueError, match="drafter"):
            GenerationSession.for_gpt(
                params, cfg,
                config=_config(spec_k=2,
                               speculate_drafter="draft_model"))


class TestSpeculationMetrics:
    def test_counters_and_gauges(self, gpt_model):
        cfg, params = gpt_model
        sess = GenerationSession.for_gpt(params, cfg,
                                         config=_config(spec_k=3))
        _drain(sess, REPETITIVE, 12)
        m = sess.stats()["metrics"]
        c, g = m["counters"], m["gauges"]
        assert c["verify_steps"] > 0
        assert c["draft_tokens_proposed"] > 0
        assert 0 < c["draft_tokens_accepted"] <= c["draft_tokens_proposed"]
        assert 0.0 < g["acceptance_rate"] <= 1.0
        assert g["acceptance_rate"] == pytest.approx(
            c["draft_tokens_accepted"] / c["draft_tokens_proposed"])
        # committed verify tokens count toward tokens_generated (the
        # per-request first token comes from prefill, not decode)
        assert c["tokens_generated"] == 3 * (12 - 1)
