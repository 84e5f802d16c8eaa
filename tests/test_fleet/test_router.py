"""FleetRouter end-to-end over real GenerationSessions: bitwise parity
with a single session (plain, disaggregated-prefill, and drain-mid-stream
traffic), affinity co-location, breaker-aware eligibility, zero-downtime
drain with hot-page migration, and admission errors."""

import jax
import numpy as np
import pytest

from easydist_tpu.fleet import (FleetConfig, FleetRouter, InProcessTransport)
from easydist_tpu.models import gpt
from easydist_tpu.resilience.breaker import OPEN
from easydist_tpu.serve import (CircuitOpenError, GenerationSession,
                                QueueFullError, ReplicaDrainingError,
                                RequestTooLargeError, ServeConfig)

# chunk/batch shapes match test_serve/test_generation.py's sessions so the
# programs come out of the process-wide memo instead of a private
# signature family compiled just for test_fleet
CHUNK = 8


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(model, rid, **kw):
    cfg, params = model
    kw.setdefault("prefill_batch", 2)
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     prefill_chunk=CHUNK, breaker_failure_threshold=3,
                     **kw)
    return GenerationSession.for_gpt(params, cfg, config=sc,
                                     replica_id=rid)


def _reference(model, prompts, max_new):
    sess = _mk(model, "ref")
    futs = [sess.submit(p, max_new_tokens=max_new) for p in prompts]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs]


def _prompts(cfg, n=5, seed=1, shared_len=9):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab, size=shared_len).tolist()
    return [shared + rng.randint(0, cfg.vocab, size=2 + i % 3).tolist()
            for i in range(n)]


class TestParity:
    def test_fleet_matches_single_session(self, model):
        cfg, _ = model
        prompts = _prompts(cfg)
        want = _reference(model, prompts, 5)
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        router.run_until_drained()
        out = [f.result(timeout=5) for f in futs]
        assert [o["ids"] for o in out] == want
        assert all(o["finish_reason"] == "length" for o in out)
        assert all(o["replica_id"] in ("d0", "d1") for o in out)

    def test_disaggregated_prefill_parity(self, model):
        """Page-aligned prefixes prefill on a dedicated replica and hand
        off through the manifest-verified transport; outputs stay
        bitwise-identical to the single-session run."""
        cfg, _ = model
        prompts = _prompts(cfg, seed=2)
        want = _reference(model, prompts, 5)
        tp = InProcessTransport()
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")],
                             prefill_replicas=[_mk(model, "p0")],
                             transport=tp)
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert router.metrics.counter("prefill_handoffs") > 0
        assert tp.pages_moved > 0
        # every transfer carried a verified manifest
        assert all(m["pages"] for m in tp.manifests)

    def test_short_prompt_skips_disaggregation(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0")],
                             prefill_replicas=[_mk(model, "p0")])
        fut = router.submit([1, 2, 3], max_new_tokens=3)  # under one page
        router.run_until_drained()
        assert fut.result(timeout=5)["ids"] == \
            _reference(model, [[1, 2, 3]], 3)[0]
        assert router.metrics.counter("prefill_handoffs") == 0


class TestRouting:
    def test_warm_prefix_colocates(self, model):
        """After the first request warms one replica's trie, affinity
        scoring sends every same-prefix follow-up to that replica."""
        cfg, _ = model
        prompts = _prompts(cfg, n=4, seed=3)
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        f0 = router.submit(prompts[0], max_new_tokens=3)
        router.run_until_drained()
        f0.result(timeout=5)
        first = router.decision_log[0]["replica_id"]
        for p in prompts[1:]:
            router.submit(p, max_new_tokens=3)
        router.run_until_drained()
        warm = [d for d in router.decision_log[1:]
                if d["affinity_tokens"] > 0]
        assert warm, "follow-ups saw no affinity"
        assert all(d["replica_id"] == first for d in warm)

    def test_cold_prefixes_route_by_hash_deterministically(self, model):
        cfg, _ = model
        router_a = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        router_b = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        prompts = _prompts(cfg, n=3, seed=4, shared_len=CHUNK)
        picks_a = [router_a._route(p, i).replica_id
                   for i, p in enumerate(prompts)]
        picks_b = [router_b._route(p, i).replica_id
                   for i, p in enumerate(prompts)]
        assert picks_a == picks_b  # sticky, not random

    def test_open_breaker_excluded(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        rep = router.replica("d0")
        for _ in range(rep.session.config.breaker_failure_threshold):
            rep.breaker.record_failure()
        assert rep.breaker.state == OPEN
        prompts = _prompts(cfg, n=3, seed=5)
        futs = [router.submit(p, max_new_tokens=3) for p in prompts]
        router.run_until_drained()
        assert all(f.result(timeout=5)["replica_id"] == "d1" for f in futs)
        # the decision log passes the FLEET001 audit
        from easydist_tpu.analyze import check_fleet_routing

        assert check_fleet_routing(router.decision_log) == []

    def test_all_replicas_ineligible_raises(self, model):
        router = FleetRouter([_mk(model, "d0")])
        rep = router.replica("d0")
        for _ in range(3):
            rep.breaker.record_failure()
        with pytest.raises(CircuitOpenError):
            router.submit([1, 2, 3, 4, 5], max_new_tokens=2)

    def test_random_policy_spreads(self, model):
        cfg, _ = model
        router = FleetRouter(
            [_mk(model, "d0"), _mk(model, "d1")],
            config=FleetConfig(policy="random", seed=0))
        picks = {router._route([1, 2, 3, 4, 5], i).replica_id
                 for i in range(20)}
        assert picks == {"d0", "d1"}


class TestDrain:
    def test_graceful_drain_zero_dropped(self, model):
        """Drain one replica while traffic is live: every future still
        resolves with the single-session ids, the drained replica leaves
        the fleet, and its hot pages land on the survivor."""
        cfg, _ = model
        # two chunks of shared prefix: the drained trie then holds pages
        # the survivor hasn't committed, so the migration is observable
        prompts = _prompts(cfg, n=6, seed=6, shared_len=2 * CHUNK + 1)
        want = _reference(model, prompts, 5)
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        router.step()  # work in flight
        # drain the replica the prefix family landed on: its trie holds
        # committed pages the survivor doesn't, so the hot-page migration
        # is observable regardless of where cold placement hashed to
        drained = router.decision_log[0]["replica_id"]
        survivor = "d1" if drained == "d0" else "d0"
        router.drain(drained, mode="graceful")
        router.run_until_drained()
        out = [f.result(timeout=5) for f in futs]
        assert [o["ids"] for o in out] == want
        assert all(o["finish_reason"] == "length" for o in out)
        assert drained not in router.stats()["replicas"]
        assert router.drain_log and \
            router.drain_log[0]["replica_id"] == drained
        assert router.drain_log[0]["pages_migrated"] > 0
        # new submits after the drain only ever see the survivor
        f = router.submit(prompts[0], max_new_tokens=3)
        router.run_until_drained()
        assert f.result(timeout=5)["replica_id"] == survivor

    def test_evacuate_resumes_bitwise_midstream(self, model):
        """Evacuate retires live decodes with partial ids; the router
        resubmits prompt+partial elsewhere and the concatenation matches
        the uninterrupted run exactly."""
        cfg, _ = model
        prompts = _prompts(cfg, n=4, seed=7)
        want = _reference(model, prompts, 6)
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        futs = [router.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(3):
            router.step()  # generate a few tokens on both replicas
        router.drain("d0", mode="evacuate")
        router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert "d0" not in router.stats()["replicas"]

    def test_draining_session_rejects_direct_submits(self, model):
        sess = _mk(model, "x")
        sess.drain()
        with pytest.raises(ReplicaDrainingError):
            sess.submit([1, 2], max_new_tokens=1)


class TestAdmission:
    def test_queue_full(self, model):
        router = FleetRouter([_mk(model, "d0")],
                             config=FleetConfig(max_queue=2))
        router.submit([1, 2, 3], max_new_tokens=2)
        router.submit([4, 5, 6], max_new_tokens=2)
        with pytest.raises(QueueFullError):
            router.submit([7, 8, 9], max_new_tokens=2)
        router.run_until_drained()

    def test_too_large_prompt(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0")])
        with pytest.raises(RequestTooLargeError):
            router.submit(list(range(cfg.seq + 4)), max_new_tokens=1)


class TestReporting:
    def test_stats_and_metrics_export(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        futs = [router.submit(p, max_new_tokens=3)
                for p in _prompts(cfg, n=3, seed=8)]
        router.run_until_drained()
        [f.result(timeout=5) for f in futs]
        st = router.stats()
        assert set(st["replicas"]) == {"d0", "d1"}
        assert st["inflight"] == 0
        assert st["metrics"]["counters"]["requests_completed"] == 3
        snap = st["replicas"]["d0"]
        assert snap["breaker"]["replica_id"] == "d0"
        db = router.export_metrics(persist=False)
        hist = db.get_op_perf("serving", "engine[d0]")
        assert hist and hist[-1]["replica_id"] == "d0"
        assert db.get_op_perf("serving", "fleet_routing")
