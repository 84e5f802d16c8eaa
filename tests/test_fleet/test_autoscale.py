"""Autoscaler control loop over a real FleetRouter: drain-while-ramping
parity, hysteresis/cooldown suppression of flaps, idempotence at target,
and graceful degradation under both catalogued fault points."""

import jax
import numpy as np
import pytest

from easydist_tpu.analyze import audit_scale_decisions
from easydist_tpu.fleet import FleetRouter
from easydist_tpu.models import gpt
from easydist_tpu.resilience import faultinject
from easydist_tpu.serve import GenerationSession, ServeConfig
from easydist_tpu.sim import Autoscaler, AutoscaleConfig

# same shapes as test_router.py so the programs come out of the
# process-wide memo
CHUNK = 8


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(model, rid):
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     prefill_chunk=CHUNK, breaker_failure_threshold=3,
                     prefill_batch=2)
    return GenerationSession.for_gpt(params, cfg, config=sc,
                                     replica_id=rid)


def _prompts(cfg, n=8, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, size=4 + i % 3).tolist()
            for i in range(n)]


def _reference(model, prompts, max_new):
    sess = _mk(model, "ref")
    futs = [sess.submit(p, max_new_tokens=max_new) for p in prompts]
    sess.run_until_drained()
    return [f.result(timeout=5)["ids"] for f in futs]


class _ScriptedPlanner:
    """Planner stub: `target_replicas` returns the scripted value for the
    current call index (last value repeats)."""

    def __init__(self, targets):
        self.targets = list(targets)
        self.calls = 0

    def target_replicas(self, traffic, slo):
        t = self.targets[min(self.calls, len(self.targets) - 1)]
        self.calls += 1
        return t


def _scaler(model, router, targets, **cfg_kw):
    cfg_kw.setdefault("min_replicas", 1)
    cfg_kw.setdefault("max_replicas", 3)
    cfg_kw.setdefault("confirm_evals", 2)
    cfg_kw.setdefault("cooldown_evals", 2)
    sc = Autoscaler(router, spawn=lambda rid: _mk(model, rid),
                    config=AutoscaleConfig(**cfg_kw),
                    planner=_ScriptedPlanner(targets), slo=object())
    sc.set_traffic_hint(object())
    return sc


def _n_live(router):
    return sum(1 for r in router._decode_replicas()
               if not r.session.is_draining)


class TestDrainWhileRamping:
    def test_scale_down_drains_under_live_traffic_bitwise(self, model):
        """The scaler drains a replica while new requests keep arriving;
        nothing drops and committed tokens stay bitwise identical to a
        fixed single-session run."""
        cfg, _ = model
        prompts = _prompts(cfg, n=8)
        want = _reference(model, prompts, 4)

        router = FleetRouter([_mk(model, "d0"), _mk(model, "d1")])
        scaler = _scaler(model, router, targets=[1])
        futs = []
        queue = list(prompts)
        for _ in range(12):
            for _ in range(2):
                if queue:
                    futs.append(router.submit(queue.pop(0),
                                              max_new_tokens=4))
            router.step()
            scaler.evaluate()
        router.run_until_drained()

        out = [f.result(timeout=5) for f in futs]
        assert [o["ids"] for o in out] == want
        assert all(o["finish_reason"] == "length" for o in out)
        assert scaler.stats()["scale_downs"] == 1
        assert _n_live(router) == 1
        assert audit_scale_decisions(scaler.decision_log) == []

    def test_scale_up_joins_mid_stream_bitwise(self, model):
        cfg, _ = model
        prompts = _prompts(cfg, n=8, seed=2)
        want = _reference(model, prompts, 4)

        router = FleetRouter([_mk(model, "d0")])
        scaler = _scaler(model, router, targets=[2])
        futs = []
        queue = list(prompts)
        for _ in range(12):
            if queue:
                futs.append(router.submit(queue.pop(0), max_new_tokens=4))
            router.step()
            scaler.evaluate()
        router.run_until_drained()

        assert [f.result(timeout=5)["ids"] for f in futs] == want
        assert scaler.stats()["scale_ups"] == 1
        assert _n_live(router) == 2


class TestHysteresis:
    def test_confirm_requires_consecutive_agreeing_evals(self, model):
        """A target that flips every tick never accumulates
        `confirm_evals` agreeing observations, so nothing actuates."""
        router = FleetRouter([_mk(model, "h0")])
        scaler = _scaler(model, router, targets=[2, 1, 2, 1, 2, 1, 2, 1])
        for _ in range(8):
            scaler.evaluate()
        st = scaler.stats()
        assert st["actions"] == 0
        reasons = {d["reason"] for d in scaler.decision_log}
        assert "hysteresis_pending" in reasons
        assert "at_target" in reasons

    def test_cooldown_suppresses_opposite_direction(self, model):
        """After a scale-up actuates, an immediate about-face is held for
        `cooldown_evals` ticks (reason=cooldown_suppressed), then still
        needs `confirm_evals` agreeing ticks — so the earliest reversal
        lands outside the SIM002 flap window."""
        router = FleetRouter([_mk(model, "c0")])
        scaler = _scaler(model, router, targets=[2, 2, 1, 1, 1, 1, 1, 1])
        log = scaler.decision_log
        for _ in range(8):
            scaler.evaluate()
        ups = [d for d in log if d["action"] == "scale_up"]
        downs = [d for d in log if d["action"] == "scale_down"]
        assert len(ups) == 1 and len(downs) == 1
        suppressed = [d for d in log
                      if d["reason"] == "cooldown_suppressed"]
        assert len(suppressed) == 2  # cooldown_evals opposite holds
        window = (scaler.config.confirm_evals
                  + scaler.config.cooldown_evals)
        # the gates guarantee a reversal gap of at least the full window
        assert downs[0]["tick"] - ups[0]["tick"] >= window
        assert audit_scale_decisions(log) == []

    def test_idempotent_at_target(self, model):
        """target == current: every tick holds with reason=at_target,
        the spawn factory is never called, and the fleet is untouched."""
        router = FleetRouter([_mk(model, "i0"), _mk(model, "i1")])
        spawned = []

        def spawn(rid):
            spawned.append(rid)
            return _mk(model, rid)

        scaler = Autoscaler(router, spawn=spawn,
                            config=AutoscaleConfig(min_replicas=1,
                                                   max_replicas=3),
                            planner=_ScriptedPlanner([2]), slo=object())
        scaler.set_traffic_hint(object())
        for _ in range(5):
            entry = scaler.evaluate()
            assert entry["action"] == "hold"
            assert entry["reason"] == "at_target"
        assert spawned == []
        assert _n_live(router) == 2
        assert scaler.stats()["actions"] == 0


class TestFaultPoints:
    def test_stale_metrics_degrade_to_hold(self, model):
        """A frozen metrics feed with work in flight trips the staleness
        detector: the loop holds (reason=metrics_stale) instead of acting
        on dead numbers, and recovers once the marker moves."""
        cfg, _ = model
        router = FleetRouter([_mk(model, "s0")])
        scaler = _scaler(model, router, targets=[3], stale_evals=2)
        fut = router.submit(_prompts(cfg)[0], max_new_tokens=6)
        router.step()  # real sample first so the wedged feed can replay it
        scaler.evaluate()
        with faultinject.fault_plan("autoscale.metrics.stale@*"):
            for _ in range(4):
                router.step()
                scaler.evaluate()
            assert faultinject.unfired() == []
        stale = [d for d in scaler.decision_log
                 if d.get("reason") == "metrics_stale"]
        assert stale and all(d["action"] == "hold" for d in stale)
        # feed recovers -> the loop acts again
        router.run_until_drained()
        assert fut.result(timeout=5)["finish_reason"] == "length"
        scaler.evaluate()
        scaler.evaluate()
        assert not scaler.degraded

    def test_scaleup_failure_holds_fleet_consistent(self, model):
        router = FleetRouter([_mk(model, "f0")])
        scaler = _scaler(model, router, targets=[3])
        with faultinject.fault_plan("autoscale.scaleup.fail@1"):
            for _ in range(4):
                router.step()
                scaler.evaluate()
            assert faultinject.unfired() == []
        reasons = [d["reason"] for d in scaler.decision_log]
        assert "scaleup_failed" in reasons
        # the failed spin-up never half-joined; a later tick retries and
        # succeeds (the injected fault was single-shot)
        assert _n_live(router) == 3
        assert all(r.session is not None
                   for r in router._decode_replicas())

    def test_new_fault_points_are_catalogued(self):
        for point in ("autoscale.metrics.stale", "autoscale.scaleup.fail"):
            assert point in faultinject.FAULT_POINTS
            plan = faultinject.parse_plan(f"{point}@2")
            assert plan == {point: 2}
