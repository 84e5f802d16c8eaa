"""GenerationSession lifecycle: drain / evacuate / close semantics the
fleet router builds on — draining rejects submits but retires in-flight
work, evacuate returns bitwise-resumable descriptors, close releases the
pools idempotently."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.models import gpt
from easydist_tpu.serve import (GenerationSession, ReplicaDrainingError,
                                ServeConfig)

CHUNK = 4


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(model, **kw):
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     prefill_chunk=CHUNK)
    return GenerationSession.for_gpt(params, cfg, config=sc, **kw)


def _greedy(model, prompt, n_new):
    cfg, params = model
    cur = list(prompt)
    out = []
    for _ in range(n_new):
        logits = gpt.gpt_apply(params, cfg, jnp.asarray([cur]))
        nxt = int(jnp.argmax(logits[0, len(cur) - 1]))
        out.append(nxt)
        cur.append(nxt)
    return out


class TestDrain:
    def test_drain_retires_inflight_then_rejects(self, model):
        sess = _mk(model)
        prompt = [3, 14, 15, 9, 2]
        fut = sess.submit(prompt, max_new_tokens=4)
        sess.step()
        pages = sess.drain()  # blocks until drained, returns hot pages
        assert fut.result(timeout=5)["ids"] == _greedy(model, prompt, 4)
        assert sess.is_draining and sess.is_drained
        assert pages, "warmed trie exported no pages"
        with pytest.raises(ReplicaDrainingError):
            sess.submit([1, 2], max_new_tokens=1)

    def test_drain_nowait_flips_flag_only(self, model):
        sess = _mk(model)
        fut = sess.submit([5, 6, 7], max_new_tokens=3)
        assert sess.drain(wait=False) is None
        assert sess.is_draining and not sess.is_drained
        sess.run_until_drained()
        assert fut.result(timeout=5)["finish_reason"] == "length"
        assert sess.is_drained

    def test_queue_depth_tracks_lifecycle(self, model):
        sess = _mk(model)
        assert sess.queue_depth == 0
        sess.submit([1, 2, 3], max_new_tokens=2)
        sess.submit([4, 5, 6], max_new_tokens=2)
        assert sess.queue_depth == 2
        sess.run_until_drained()
        assert sess.queue_depth == 0
        assert sess.metrics.snapshot()["gauges"]["queue_depth"] == 0


class TestEvacuate:
    def test_evacuate_returns_resumable_descriptors(self, model):
        sess = _mk(model)
        prompt = [3, 14, 15, 9, 2]
        want = _greedy(model, prompt, 6)
        fut = sess.submit(prompt, max_new_tokens=6)
        for _ in range(3):
            sess.step()  # decode a few tokens
        descs = sess.evacuate()
        out = fut.result(timeout=5)
        assert out["finish_reason"] == "evacuated"
        assert 0 < len(out["ids"]) < 6
        assert out["ids"] == want[:len(out["ids"])]  # bitwise prefix
        assert sess.is_drained
        assert len(descs) == 1
        d = descs[0]
        assert d["prompt"] == prompt and d["ids"] == out["ids"]
        # resuming prompt+partial elsewhere completes the exact sequence
        sess2 = _mk(model)
        fut2 = sess2.submit(d["prompt"] + d["ids"],
                            max_new_tokens=6 - len(d["ids"]))
        sess2.run_until_drained()
        assert out["ids"] + fut2.result(timeout=5)["ids"] == want

    def test_evacuate_pending_request_yields_empty_partial(self, model):
        sess = _mk(model)
        fut = sess.submit([1, 2, 3], max_new_tokens=3)
        descs = sess.evacuate()  # never admitted
        res = fut.result(timeout=5)
        assert (res["ids"], res["finish_reason"]) == ([], "evacuated")
        # never admitted: the timeline has a submit and a finish, no more
        timing = res["timing"]
        assert timing["admit_ns"] is None and timing["token_ns"] == []
        assert timing["submit_ns"] <= timing["finish_ns"]
        assert descs[0]["ids"] == []

    def test_evacuate_trie_has_no_orphaned_pins(self, model):
        from easydist_tpu.analyze import check_fleet_drain

        sess = _mk(model)
        sess.submit([3, 14, 15, 9, 2, 7, 8], max_new_tokens=4)
        for _ in range(3):
            sess.step()
        sess.evacuate()
        assert check_fleet_drain(sess) == []


class TestClose:
    def test_close_is_idempotent_and_releases_pools(self, model):
        sess = _mk(model)
        fut = sess.submit([9, 8, 7], max_new_tokens=2)
        sess.close()
        assert fut.result(timeout=5)["finish_reason"] == "length"
        assert sess._pools == {}
        sess.close()  # second close is a no-op
        with pytest.raises(ReplicaDrainingError):
            sess.submit([1], max_new_tokens=1)


class TestReplicaLabels:
    def test_replica_id_threads_through_metrics(self, model):
        sess = _mk(model, replica_id="r7")
        assert sess.replica_id == "r7"
        assert sess.metrics.replica_id == "r7"
        assert sess.stats()["replica_id"] == "r7"
        db = sess.metrics.export(persist=False)
        assert db.get_op_perf("serving", "engine[r7]")
