"""Fleet fault tolerance: kill-and-recover parity (crash mid-decode on
gpt/llama, single-device and tp=2 — every recovered
stream bitwise-identical to the uninterrupted run), wedged-replica
detection via the health probe, probe flaps absorbed vs escalated,
prefill-replica crash fallback, poison-request quarantine, revive by
re-registration, and the router's FLEET004/005 audit surfaces staying
clean across all of it."""

import jax
import numpy as np
import pytest

from easydist_tpu.fleet import (FleetConfig, FleetRouter,
                                PoisonRequestError)
from easydist_tpu.jaxfront.mesh import make_device_mesh
from easydist_tpu.models import gpt, llama
from easydist_tpu.resilience import faultinject
from easydist_tpu.serve import GenerationSession, ServeConfig

# every scenario here injects faults and recovers from them; `-m chaos`
# selects exactly this class of test (still tier-1: chaos != slow)
pytestmark = pytest.mark.chaos

CHUNK = 8


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


def _mk(model, rid, factory=None, mesh=None, **kw):
    cfg, params = model
    factory = factory or GenerationSession.for_gpt
    # chunk/batch shapes match test_serve's sessions (and test_router.py)
    # so the programs come out of the process-wide memo instead
    # of a private signature family compiled just for this file
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_batch", 2)
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     breaker_failure_threshold=3, **kw)
    return factory(params, cfg, config=sc, replica_id=rid, mesh=mesh)


def _reference(model, prompts, max_new, **mkkw):
    sess = _mk(model, "ref", **mkkw)
    futs = [sess.submit(p, max_new_tokens=max_new) for p in prompts]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs]


def _prompts(cfg, n=4, seed=1, shared_len=9):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab, size=shared_len).tolist()
    return [shared + rng.randint(0, cfg.vocab, size=2 + i % 3).tolist()
            for i in range(n)]


def _crash_occurrence(router, order, step_no):
    """1-based `fleet.replica.crash` hit that lands on the replica the
    FIRST request routed to, during router step `step_no`: step() hits
    the crash point once per live replica, in registration order, so
    that replica's hit in step k is (k-1)*len(order) + index + 1.
    Targeting a replica known to hold live work makes the recovery
    assertion (`requests_recovered >= 1`) deterministic."""
    target = router.decision_log[0]["replica_id"]
    return (step_no - 1) * len(order) + order.index(target) + 1, target


class _WedgedSession:
    """Alive-but-stuck replica: step() returns without doing any work,
    so no exception ever reaches the breaker — only the health probe's
    liveness heartbeat can catch it.  Everything else delegates to a
    real session (submit still queues, counters still read)."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        return 0


class TestCrashRecovery:
    """The tentpole contract: kill the replica that holds live decodes
    and the recovered streams are token-for-token identical."""

    @pytest.mark.parametrize("kind", ["gpt", "llama"])
    def test_mid_decode_crash_bitwise(self, model, llama_model, kind):
        m = model if kind == "gpt" else llama_model
        factory = (GenerationSession.for_gpt if kind == "gpt"
                   else GenerationSession.for_llama)
        cfg, _ = m
        prompts = _prompts(cfg, seed=11)
        want = _reference(m, prompts, 6, factory=factory)
        router = FleetRouter([_mk(m, "d0", factory),
                              _mk(m, "d1", factory)])
        futs = [router.submit(p, max_new_tokens=6) for p in prompts]
        # crash the loaded replica on its 4th step — decodes are live
        # with partial ids by then, so recovery is a true mid-stream
        # prompt+ids resubmission, not a fresh retry
        occ, target = _crash_occurrence(router, ["d0", "d1"], step_no=4)
        with faultinject.fault_plan(f"fleet.replica.crash@{occ}"):
            router.run_until_drained()
            assert faultinject.stats()["fired"]["fleet.replica.crash"] == 1
            assert faultinject.unfired() == []
        out = [f.result(timeout=5) for f in futs]
        assert [o["ids"] for o in out] == want
        assert all(o["finish_reason"] == "length" for o in out)
        survivor = "d1" if target == "d0" else "d0"
        assert all(o["replica_id"] == survivor for o in out)
        assert target not in router.stats()["replicas"]
        assert router.metrics.counter("replica_crashes") == 1
        assert router.metrics.counter("requests_recovered") >= 1
        assert router.crash_log[0]["replica_id"] == target
        # the decision log stays FLEET001/004-clean: the router never
        # dispatched to the dead replica after the crash
        from easydist_tpu.analyze import check_fleet_routing

        assert check_fleet_routing(router.decision_log) == []

    def test_crash_recovery_tp2(self, model, cpu_devices):
        cfg, _ = model
        mesh = make_device_mesh((2,), ("tp",), devices=cpu_devices[:2])
        prompts = _prompts(cfg, seed=12)
        # full-bucket chunk matches test_generation.py's tp=2 session, so
        # the tp-mesh prefill program is shared, not a private signature
        tp_kw = dict(mesh=mesh, prefill_chunk=cfg.seq, prefill_batch=4)
        want = _reference(model, prompts, 5, **tp_kw)
        router = FleetRouter([_mk(model, "d0", **tp_kw),
                              _mk(model, "d1", **tp_kw)])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        occ, target = _crash_occurrence(router, ["d0", "d1"], step_no=3)
        with faultinject.fault_plan(f"fleet.replica.crash@{occ}"):
            router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert target not in router.stats()["replicas"]
        assert router.metrics.counter("requests_recovered") >= 1

    def test_crash_then_revive_serves_again(self, model):
        """Crash recovery followed by the chaos drill's revive move:
        re-registering the crashed replica id with a fresh session
        clears its DEAD tombstone and it serves traffic again."""
        cfg, _ = model
        prompts = _prompts(cfg, n=3, seed=13)
        want = _reference(model, prompts, 5)
        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        occ, target = _crash_occurrence(router, ["d0", "d1"], step_no=2)
        with faultinject.fault_plan(f"fleet.replica.crash@{occ}"):
            router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        # revive the crashed id with a fresh session; it serves again
        router.add_replica(_mk(model, target))
        assert router.health.state(target) == "alive"
        assert any(e["reason"] == "revived"
                   for e in router.health.events)
        f = router.submit(prompts[0], max_new_tokens=3)
        router.run_until_drained()
        assert f.result(timeout=5)["ids"] == want[0][:3]
        assert target in router.stats()["replicas"]

    def test_prefill_replica_crash_falls_back(self, model):
        """Killing the prefill tier mid-handoff must fall back to direct
        decode-side prefill with zero dropped requests and parity."""
        cfg, _ = model
        prompts = _prompts(cfg, seed=14)
        want = _reference(model, prompts, 5)
        router = FleetRouter([_mk(model, "d0")],
                             prefill_replicas=[_mk(model, "p0")])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        assert router.metrics.counter("prefill_handoffs") > 0
        # step order is registration order (d0 then p0): hit 2 of the
        # first router step is p0's step, before any handoff completes
        with faultinject.fault_plan("fleet.replica.crash@2"):
            router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert "p0" not in router.stats()["replicas"]
        assert router.metrics.counter("handoff_fallbacks") > 0
        assert router.metrics.counter("requests_recovered") > 0


class TestSpeculativeCrashRecovery:
    """Crash a replica mid-speculation: the resumed request re-drafts
    from prompt + committed ids on the survivor (NGramDrafter proposals
    are a pure function of the sequence) and the accept rule is
    self-validating, so the recovered stream matches BOTH the
    uninterrupted speculative run and plain greedy bitwise — losing the
    drafter's in-flight state can only cost speed, never tokens."""

    def test_mid_speculation_crash_bitwise(self, model):
        cfg, _ = model
        # cyclic prompts so the drafter proposes and verify rounds are
        # live (not backed off) when the crash lands
        prompts = [[5, 6, 5, 6, 5, 6, 5], [9, 3, 9, 3, 9, 3, 9],
                   [4, 4, 4, 4, 4], [2, 7, 2, 7, 2, 7]]
        plain = _reference(model, prompts, 10)
        want = _reference(model, prompts, 10, speculate_k=3)
        assert want == plain  # speculation parity, before any fault
        d0 = _mk(model, "d0", speculate_k=3)
        d1 = _mk(model, "d1", speculate_k=3)
        router = FleetRouter([d0, d1])
        futs = [router.submit(p, max_new_tokens=10) for p in prompts]
        occ, target = _crash_occurrence(router, ["d0", "d1"], step_no=2)
        with faultinject.fault_plan(f"fleet.replica.crash@{occ}"):
            router.run_until_drained()
            assert faultinject.stats()["fired"]["fleet.replica.crash"] == 1
            assert faultinject.unfired() == []
        out = [f.result(timeout=5) for f in futs]
        assert [o["ids"] for o in out] == want
        assert target not in router.stats()["replicas"]
        assert router.metrics.counter("requests_recovered") >= 1
        # the survivor really speculated while finishing the recovered
        # streams — the drill exercised draft/verify, not plain decode
        survivor = d1 if target == "d0" else d0
        assert survivor.metrics.snapshot()["counters"]["verify_steps"] > 0


class TestWedgedReplica:
    def test_probe_detects_stall_and_fails_over(self, model):
        """A replica that is alive but makes no progress (step() returns,
        counters frozen, work queued) must go DEAD via the liveness probe
        and its requests must recover bitwise on a survivor."""
        cfg, _ = model
        prompts = _prompts(cfg, n=3, seed=21)
        want = _reference(model, prompts, 4)
        wedged = _WedgedSession(_mk(model, "w0"))
        router = FleetRouter([wedged],
                             config=FleetConfig(miss_budget=2))
        futs = [router.submit(p, max_new_tokens=4) for p in prompts]
        router.add_replica(_mk(model, "d1"))
        router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert "w0" not in router.stats()["replicas"]
        assert router.metrics.counter("replica_crashes") == 1
        assert router.metrics.counter("requests_recovered") == 3
        assert any("health probe" in c["error"]
                   for c in router.crash_log)
        assert any(e["state"] == "dead" and e["replica_id"] == "w0"
                   for e in router.health.events)


class TestProbeFlap:
    def test_single_flap_absorbed(self, model):
        """One false MISS must ride inside the miss budget: the replica
        dips to SUSPECT, real progress clears it, nothing fails over."""
        cfg, _ = model
        prompts = _prompts(cfg, n=3, seed=22)
        want = _reference(model, prompts, 5)
        router = FleetRouter([_mk(model, "d0")])
        futs = [router.submit(p, max_new_tokens=5) for p in prompts]
        router.add_replica(_mk(model, "d1"))
        with faultinject.fault_plan("fleet.probe.flap@1"):
            router.run_until_drained()
            assert faultinject.stats()["fired"]["fleet.probe.flap"] == 1
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert router.metrics.counter("replica_crashes") == 0
        assert router.metrics.counter("requests_recovered") == 0
        assert router.health.state("d0") == "alive"
        states = [e["state"] for e in router.health.events
                  if e["replica_id"] == "d0"]
        assert states == ["suspect", "alive"]

    def test_persistent_flap_escalates_to_failover(self, model):
        """Flaps on every probe of one replica exhaust the budget: the
        replica goes DEAD and its live work recovers bitwise."""
        cfg, _ = model
        prompts = _prompts(cfg, n=3, seed=23)
        want = _reference(model, prompts, 6)
        router = FleetRouter([_mk(model, "d0")],
                             config=FleetConfig(miss_budget=2))
        futs = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.add_replica(_mk(model, "d1"))
        # probe evaluates replicas in sorted order, once per step: hits
        # 1 and 3 are d0's evaluations in steps 1 and 2
        with faultinject.fault_plan(
                "fleet.probe.flap@1,fleet.probe.flap@3"):
            router.run_until_drained()
            assert faultinject.unfired() == []
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert "d0" not in router.stats()["replicas"]
        assert router.metrics.counter("requests_recovered") >= 1


class TestInflightBookkeeping:
    """The router's _Inflight table is bounded: deadline expiry fails
    entries, externally-cancelled futures are swept, and the live count
    is exported as the `router_inflight` gauge."""

    def test_router_inflight_gauge_tracks_live_requests(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0")])
        futs = [router.submit(p, max_new_tokens=3)
                for p in _prompts(cfg, n=3, seed=41)]
        assert router.metrics.snapshot()["gauges"]["router_inflight"] == 3
        router.run_until_drained()
        [f.result(timeout=5) for f in futs]
        assert router.metrics.snapshot()["gauges"]["router_inflight"] == 0
        db = router.export_metrics(persist=False)
        hist = db.get_op_perf("serving", "fleet")
        assert hist and "router_inflight" in hist[-1]["gauges"]

    def test_deadline_expired_inflight_fails_and_is_swept(self, model):
        from easydist_tpu.serve import DeadlineExceededError

        cfg, _ = model
        router = FleetRouter([_mk(model, "d0")])
        fut = router.submit(_prompts(cfg, n=1, seed=42)[0],
                            max_new_tokens=4, deadline_ms=0.01)
        router.run_until_drained()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=5)
        assert router.metrics.counter("requests_timed_out") == 1
        assert router.metrics.counter("requests_failed") == 1
        assert router.stats()["inflight"] == 0

    def test_cancelled_future_is_garbage_collected(self, model):
        cfg, _ = model
        router = FleetRouter([_mk(model, "d0")])
        fut = router.submit(_prompts(cfg, n=1, seed=43)[0],
                            max_new_tokens=8)
        assert fut.cancel()   # caller walked away before any step
        router.step()
        assert router.metrics.counter("inflight_gc") == 1
        assert router.stats()["inflight"] == 0
        router.run_until_drained()   # the session still retires cleanly


class TestPagedHandoffCorruption:
    def test_corrupt_paged_handoff_aborts_before_pool_commit(self, model):
        """A bit-flipped page in a handoff must abort before
        anything touches the destination's PagePool: no page allocated,
        no refcount moved, KV001 bookkeeping still clean — and a clean
        retry afterwards commits normally."""
        from easydist_tpu.analyze import check_page_table
        from easydist_tpu.fleet import (InProcessTransport,
                                        PageCorruptError)

        cfg, _ = model
        prompt = list(range(1, 14))
        src = _mk(model, "src")
        src.submit(prompt, max_new_tokens=2)
        src.run_until_drained()
        path = src.export_prefix_path(prompt)
        assert path, "source trie exported no pages"
        dst = _mk(model, "dst")
        dst.submit([7, 8, 9], max_new_tokens=2)  # materialize the pool
        dst.run_until_drained()
        pool = dst._pools[cfg.seq]
        free_before = pool.pool.n_free
        tp = InProcessTransport()
        with faultinject.fault_plan("fleet.transport.page_corrupt@*"):
            with pytest.raises(PageCorruptError, match="corrupt"):
                tp.send_pages(path, dst, prompt, retries=0)
        assert pool.pool.n_free == free_before       # nothing allocated
        assert check_page_table(pool.pool, pool.table,
                                trie=pool.trie) == []
        assert dst.prefix_affinity(prompt) == 0
        # clean wire afterwards: the same path commits and warms the trie
        assert tp.send_pages(path, dst, prompt) > 0
        assert dst.prefix_affinity(prompt) > 0
        assert check_page_table(pool.pool, pool.table,
                                trie=pool.trie) == []


class TestQuarantine:
    def test_poison_request_quarantined(self, model):
        """A request that crashes `quarantine_after` distinct replicas
        fails structurally instead of rolling through the fleet."""
        cfg, _ = model
        router = FleetRouter(
            [_mk(model, "d0"), _mk(model, "d1"), _mk(model, "d2")],
            config=FleetConfig(quarantine_after=2))
        fut = router.submit(_prompts(cfg, n=1, seed=31)[0],
                            max_new_tokens=4)
        with faultinject.fault_plan("fleet.replica.crash@*"):
            router.step()
        with pytest.raises(PoisonRequestError) as ei:
            fut.result(timeout=5)
        assert ei.value.request_id == 0
        assert len(ei.value.replicas) == 2
        assert router.metrics.counter("requests_quarantined") == 1
        assert router.metrics.counter("requests_failed") == 1
        assert router.stats()["inflight"] == 0

    def test_quarantine_does_not_take_clean_requests(self, model):
        """Only the poison request is rejected; the fleet keeps serving
        everything else after the crashes it caused."""
        cfg, _ = model
        prompts = _prompts(cfg, n=3, seed=32)
        want = _reference(model, prompts, 4)
        router = FleetRouter(
            [_mk(model, "d0"), _mk(model, "d1"), _mk(model, "d2")],
            config=FleetConfig(quarantine_after=2))
        futs = [router.submit(p, max_new_tokens=4) for p in prompts]
        # one crash only: the stranded requests resume on survivors and
        # nothing quarantines, because no request crashed two DISTINCT
        # replicas
        occ, target = _crash_occurrence(
            router, ["d0", "d1", "d2"], step_no=2)
        with faultinject.fault_plan(f"fleet.replica.crash@{occ}"):
            router.run_until_drained()
        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert router.metrics.counter("requests_quarantined") == 0
