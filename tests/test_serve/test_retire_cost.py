"""What a retirement costs the session, by counts and not by a clock: one
`easydist.serve.retire` span a retirement, inside its step; the KV001 audit
it runs decided by array passes (`kv_audits{path=vector}`), with the listed
walk of the pool — `PageTable.mapped` a slot, `PagePool.refcount` a page —
left for the pool that fails."""

import jax
import pytest

from easydist_tpu.analyze import audit_page_table
from easydist_tpu.kv import PagePool, PageTable
from easydist_tpu.models import gpt
from easydist_tpu.runtime import spans
from easydist_tpu.serve import GenerationSession, ServeConfig

STEP = "easydist.serve.step"
RETIRE = "easydist.serve.retire"


@pytest.fixture(scope="module")
def snap():
    """The recorder after a session at 4 slots drained three requests."""
    cfg = gpt.GPTConfig.tiny()
    params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig(decode_buckets=(32,), max_decode_slots=4,
                     prefill_chunk=8, prefill_batch=2)
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    spans.clear()
    futs = [sess.submit(p, max_new_tokens=n)
            for p, n in (([1, 2, 3], 4), (list(range(1, 18)), 2),
                         ([5] * 9, 1))]      # the last retires at its finish
    sess.run_until_drained()
    assert [len(f.result(timeout=5)["ids"]) for f in futs] == [4, 2, 1]
    out = spans.snapshot()
    spans.clear()
    sess.close()
    return out


def test_a_retirement_is_one_span_below_a_phase_of_its_step(snap):
    by_id = {r["id"]: r for r in snap["spans"]}
    retires = [r for r in snap["spans"] if r["name"] == RETIRE]
    assert len(retires) == 3
    assert sorted(r["attrs"]["reason"] for r in retires) == ["length"] * 3
    assert len({r["attrs"]["request_id"] for r in retires}) == 3
    for r in retires:
        parent = by_id[r["parent_id"]]
        # never a step's direct child: the phases still tile the step
        assert parent["name"] in ("easydist.serve.decode.harvest",
                                  "easydist.serve.prefill.finish")
        step = by_id[parent["parent_id"]]
        assert step["name"] == STEP
        assert step["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= step["t1_ns"]
    assert any(by_id[r["parent_id"]]["name"].endswith("prefill.finish")
               for r in retires)


def test_every_audit_of_a_sound_session_took_the_vector_path(snap):
    audits = {k: v for k, v in snap["counters"].items()
              if k.startswith("kv_audits")}
    # one a retirement and one a pool (its first decode round); `listed`
    # is the audit that fell through to the walk: none did
    assert audits == {"kv_audits{path=vector,where=retire}": 3,
                      "kv_audits{path=vector,where=first_decode}": 1}


@pytest.fixture
def walked(monkeypatch):
    """Counts the calls the listed walk makes a slot and a page."""
    calls = {"mapped": 0, "refcount": 0}

    def counting(cls, name):
        plain = getattr(cls, name)

        def method(self, *args):
            calls[name] += 1
            return plain(self, *args)
        monkeypatch.setattr(cls, name, method)
    counting(PageTable, "mapped")
    counting(PagePool, "refcount")
    return calls


def _pool_256x16():
    """The LFM2 cell's geometry, 180 rows live."""
    pool = PagePool(1152, 256, page_bytes=64)
    table = PageTable(256, 16, 1152)
    for slot in range(180):
        for idx in range(1 + slot % 6):
            table.map(slot, idx, pool.alloc())
    return pool, table


def test_a_sound_pool_is_not_walked(walked):
    pool, table = _pool_256x16()
    assert audit_page_table(pool, table) == []
    assert walked == {"mapped": 0, "refcount": 0}


def test_a_corrupt_pool_is_walked_to_word_the_failure(walked):
    pool, table = _pool_256x16()
    table.array[200, 0] = table.array[0, 0]     # a holder with no hold
    findings = audit_page_table(pool, table)
    assert len(findings) == 1
    assert "slot0, slot200" in findings[0].message
    assert walked["mapped"] == 256 and walked["refcount"] == pool.in_use
