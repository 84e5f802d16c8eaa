"""What `GenerationSession` records of itself (runtime/spans.py): the host
phases of `step()` as spans that tile it, the cycle of every program it runs
(host gap and in-flight intervals that tile the session's timeline), how
long it was empty between two steps, a timeline for every request, the
`queue_wait` histogram, and names for its jitted programs."""

import statistics
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import session_timeline
from easydist_tpu.models import exaone_moe, gpt, granite_hybrid
from easydist_tpu.runtime import spans
from easydist_tpu.serve import GenerationSession, ServeConfig

CHUNK, ROWS, CHUNKS_PER_STEP, SLOTS = 4, 2, 2, 3
PROMPTS = [[3, 14, 15, 9, 2, 6], [5, 3, 5], [8, 9, 7, 9, 3, 2, 3, 8, 4, 6],
           [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4], [1, 4, 1, 4, 2]]
NEW = [5, 3, 7, 4, 6]
CONFIGS = {
    "paged": dict(),
    "paged_int8": dict(kv_quant_dtype="int8"),
    "paged_speculative": dict(speculate_k=2),
}
# per step: the step and its admit; per chunk call a build, a call with the
# dispatch inside it, and at most one finish per prefill row; a decode
# round's build, call with its dispatch, and harvest; and one
# `easydist.serve.retire` per slot, each of which can retire (at its
# prefill's finish or in the harvest) at most once in a step
SPANS_PER_STEP_BOUND = 2 + CHUNKS_PER_STEP * (3 + ROWS) + 4 + SLOTS


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    return cfg, gpt.gpt_init(cfg, jax.random.PRNGKey(0))


def _one_device():
    """The mesh every cell's runner serves on."""
    return Mesh(np.array(jax.devices()[:1]), ("d",))


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def run(request, model):
    """One drained session per config: (its key, results, recorder snapshot,
    session).  More requests than slots, so some wait in the queue."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=SLOTS,
                     prefill_chunk=CHUNK, prefill_batch=ROWS,
                     prefill_chunks_per_step=CHUNKS_PER_STEP,
                     **CONFIGS[request.param])
    sess = GenerationSession.for_gpt(params, cfg, config=sc,
                                     mesh=_one_device())
    spans.clear()
    futs = [sess.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, NEW)]
    sess.run_until_drained()
    results = [f.result(timeout=5) for f in futs]
    snap = spans.snapshot()
    spans.clear()
    return request.param, results, snap, sess


def _children(snap):
    by_parent = {}
    for r in snap["spans"]:
        by_parent.setdefault(r["parent_id"], []).append(r)
    return by_parent


def _descendants(rec, by_parent):
    out = []
    for child in by_parent.get(rec["id"], []):
        out += [child] + _descendants(child, by_parent)
    return out


def _steps(snap):
    return [r for r in snap["spans"] if r["name"] == "easydist.serve.step"]


def test_one_step_span_per_step_with_its_attrs(run):
    _, _, snap, sess = run
    steps = _steps(snap)
    assert [s["attrs"]["step"] for s in steps] == \
        list(range(1, sess._step_index + 1))
    assert all(s["parent_id"] == 0 for s in steps)
    assert steps[0]["attrs"]["queued"] == len(PROMPTS)
    assert steps[0]["attrs"]["live"] == 0
    assert max(s["attrs"]["live"] for s in steps) == SLOTS
    assert steps[-1]["attrs"]["queued"] == 0


def test_phases_tile_the_step(run):
    """Every step has exactly one admit; its direct children do not
    overlap and lie inside it; what they leave uncovered — the step's own
    code between the phases — is under the slack: a median of 5 ms and no
    step over 250 ms on a shared CPU (the phases themselves take
    milliseconds to seconds here: they compile)."""
    layout, _, snap, _ = run
    by_parent = _children(snap)
    own_ms = []
    for step in _steps(snap):
        kids = sorted(by_parent[step["id"]], key=lambda r: r["t0_ns"])
        names = [k["name"] for k in kids]
        assert names[0] == "easydist.serve.admit"
        assert names.count("easydist.serve.admit") == 1
        assert set(names) <= {
            "easydist.serve.admit", "easydist.serve.prefill.build",
            "easydist.serve.prefill.call", "easydist.serve.prefill.finish",
            "easydist.serve.decode.build", "easydist.serve.decode.call",
            "easydist.serve.decode.harvest",
            "easydist.step.call"}, names     # the draft model's dispatch
        if "speculative" not in layout:
            # a build, then its call, then (decode) its harvest
            for a, b in zip(names, names[1:]):
                if a.endswith(".build"):
                    assert b == a[:-len("build")] + "call"
                if a == "easydist.serve.decode.call":
                    assert b == "easydist.serve.decode.harvest"
        assert kids[0]["t0_ns"] >= step["t0_ns"]
        assert kids[-1]["t1_ns"] <= step["t1_ns"]
        for a, b in zip(kids, kids[1:]):
            assert a["t1_ns"] <= b["t0_ns"], (a["name"], b["name"])
        own_ms.append(spans.self_ns(step, snap["spans"]) / 1e6)
    assert statistics.median(own_ms) < 5.0 and max(own_ms) < 250.0, own_ms


def test_admit_span_counts_what_left_the_queue(run):
    _, _, snap, _ = run
    admits = [r for r in snap["spans"] if r["name"] == "easydist.serve.admit"]
    assert sum(a["attrs"]["admitted"] for a in admits) == len(PROMPTS)
    assert admits[0]["attrs"]["deferred"] == len(PROMPTS) \
        - admits[0]["attrs"]["admitted"] > 0
    assert admits[-1]["attrs"] == {"admitted": 0, "deferred": 0}


def test_every_program_call_is_a_call_span_with_its_dispatch_inside(run):
    layout, _, snap, sess = run
    by_parent = _children(snap)
    calls = [r for r in snap["spans"]
             if r["name"] in ("easydist.serve.prefill.call",
                              "easydist.serve.decode.call")]
    m = sess.metrics
    assert sum(c["name"].endswith("prefill.call") for c in calls) \
        == m.counter("prefill_chunks")
    assert sum(c["name"].endswith("decode.call") for c in calls) \
        == m.counter("decode_steps") + m.counter("verify_steps")
    for call in calls:
        dispatch = by_parent[call["id"]][0]
        assert dispatch["name"] == "easydist.step.call"
        assert dispatch["attrs"]["fn"] == call["attrs"]["fn"]
        assert call["attrs"]["rows"] >= 1
    want = {"_prefill_chunk_paged", "_decode_paged"}
    if "speculative" in layout:
        want.add("_verify_paged")
    assert {c["attrs"]["fn"] for c in calls} == want
    _compiled_in_its_first_call_or_not_at_all(snap, calls, want)


def _compiled_in_its_first_call_or_not_at_all(snap, calls, fns):
    """XLA compiled each program of `fns` in its FIRST call and never after
    (or not at all: sessions over one model share compiled programs): the
    pool goes into a program's first call as every call hands it back
    (`GenerationSession._born`, `_finish_compile`'s `out_pins`)."""
    by_parent = _children(snap)
    for fn in fns:
        mine = [c for c in calls if c["attrs"]["fn"] == fn]
        assert mine, fn
        compiled_in = [i for i, c in enumerate(mine) if any(
            r["name"] == "easydist.step.compile"
            for r in _descendants(c, by_parent))]
        assert compiled_in in ([], [0]), (fn, compiled_in)
        assert snap["counters"].get(f"xla_compiles{{fn={fn}}}", 0) \
            == len(compiled_in)


STATE_KEEPING = {    # beside the pages: a recurrent state; rings
    "granite_hybrid": (granite_hybrid, granite_hybrid.GraniteHybridConfig,
                       granite_hybrid.granite_init),
    "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoeConfig,
                   exaone_moe.exaone_init),
}


@pytest.mark.parametrize("family", sorted(STATE_KEEPING))
def test_a_state_keeping_family_compiles_its_two_programs_once(family):
    """The pool of such a session is the pages AND the leaves kept a
    sequence, born in one place: `_prefill_chunk_paged_state` and
    `_decode_paged_state` compile in their first call and never after."""
    module, config, init = STATE_KEEPING[family]
    cfg = config.tiny()
    sess = GenerationSession(init(cfg, jax.random.PRNGKey(3)),
                             model=module.decoder(cfg), mesh=_one_device(),
                             config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=4,
        prefill_chunk=8, prefill_batch=2, enable_prefix_cache=False,
        speculate_k=0))
    rng = np.random.default_rng(4)
    spans.clear()
    futs = [sess.submit(rng.integers(1, cfg.vocab, size=n).tolist(),
                        max_new_tokens=m)
            for n, m in ((5, 4), (19, 6), (8, 3), (30, 5), (3, 7))]
    sess.run_until_drained()
    assert all(len(f.result(timeout=5)["ids"]) > 0 for f in futs)
    snap = spans.snapshot()
    spans.clear()
    sess.close()
    calls = [r for r in snap["spans"]
             if r["name"] in ("easydist.serve.prefill.call",
                              "easydist.serve.decode.call")]
    want = {"_prefill_chunk_paged_state", "_decode_paged_state"}
    assert {c["attrs"]["fn"] for c in calls} == want
    assert min(sum(c["attrs"]["fn"] == fn for c in calls)
               for fn in want) >= 3
    _compiled_in_its_first_call_or_not_at_all(snap, calls, want)


@pytest.mark.parametrize("run", ["paged"], indirect=True)
def test_recorder_calls_per_step_are_bounded(run):
    """A count, not a timing: a step opens at most a constant number of
    spans, and stamps each live slot once per decode round.  (Stated
    without speculation, which adds a second round per step.)"""
    _, results, snap, _ = run
    by_parent = _children(snap)
    steady = 0
    for step in _steps(snap):
        inside = _descendants(step, by_parent)
        if any(".compile" in r["name"] for r in inside):
            continue    # a program's first calls: its compile phases too
        steady += 1
        assert 1 + len(inside) <= SPANS_PER_STEP_BOUND
    assert steady >= len(_steps(snap)) // 2
    # one stamp per live slot and round: every later token's stamp is the
    # end of a decode call, shared by the round's slots
    decode_ends = {r["t1_ns"] for r in snap["spans"]
                   if r["name"] == "easydist.serve.decode.call"}
    later = [t for res in results for t in res["timing"]["token_ns"][1:]]
    assert set(later) <= decode_ends
    assert len(set(later)) < len(later)      # shared, not one read each


def _programs(snap):
    """The `.call` records in order, each with `enqueued_ns`: the end of
    the `easydist.step.call` inside it."""
    enqueued = {r["parent_id"]: r["t1_ns"] for r in snap["spans"]
                if r["name"] == "easydist.step.call"}
    return sorted(({**r, "enqueued_ns": enqueued[r["id"]]}
                   for r in snap["spans"]
                   if r["name"] in ("easydist.serve.prefill.call",
                                    "easydist.serve.decode.call")),
                  key=lambda r: r["t0_ns"])


def test_every_program_carries_its_cycle(run):
    """Enqueued, then ready, then read back, inside its `.call`, under a
    step."""
    _, _, snap, _ = run
    by_id = {r["id"]: r for r in snap["spans"]}
    calls = _programs(snap)
    assert len(calls) >= 8
    for call in calls:
        assert call["t0_ns"] <= call["enqueued_ns"] \
            <= call["attrs"]["ready_ns"] <= call["t1_ns"]
        assert by_id[call["parent_id"]]["name"] == "easydist.serve.step"


def test_host_gaps_and_flights_tile_the_session(run):
    """One program in flight at a time: from the first readback to the
    last, every instant is in exactly one host gap (the previous `.call`'s
    end to the program's being enqueued: nothing in flight) or one flight
    (from there to its `.call`'s end) — to the nanosecond, as
    `chipbench/session_timeline.py` derives the two."""
    _, _, snap, _ = run
    calls = session_timeline.calls(snap["spans"])
    assert [(c["t0_ns"], c["dispatched_ns"]) for c in calls] \
        == [(c["t0_ns"], c["enqueued_ns"]) for c in _programs(snap)]
    assert calls[0]["host_gap_ns"] is None
    for prev, call in zip(calls, calls[1:]):
        # a gap starts where the previous flight ended and ends where its
        # own flight starts: no instant in neither, none in both
        assert call["host_gap_ns"] > 0
        assert call["dispatched_ns"] - call["host_gap_ns"] == prev["t1_ns"]
    gaps = sum(c["host_gap_ns"] for c in calls[1:])
    flights = sum(c["t1_ns"] - c["dispatched_ns"] for c in calls[1:])
    assert gaps + flights == calls[-1]["t1_ns"] - calls[0]["t1_ns"]
    assert flights > 0
    # work all the way: only the session's first program follows emptiness
    assert [c["after_idle"] for c in calls] \
        == [True] + [False] * (len(calls) - 1)


def test_steps_say_how_long_the_session_was_empty(run):
    _, _, snap, _ = run
    steps = _steps(snap)
    for step in steps[1:]:
        assert step["attrs"]["empty_ns"] == 0     # work all the way
    assert steps[0]["attrs"]["empty_ns"] > 0      # since the session was made


@pytest.fixture(scope="module")
def two_bursts(model):
    """A session that drains, sits empty, and is given work again; its
    other entry points called between the steps."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=SLOTS,
                     prefill_chunk=CHUNK, prefill_batch=ROWS)
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    spans.clear()
    sess.submit(PROMPTS[1], max_new_tokens=3)
    sess.run_until_drained()
    assert sess.snapshot_inflight() == []
    time.sleep(0.02)
    sess.step()                           # stepped while empty
    time.sleep(0.02)
    sess.submit(PROMPTS[4], max_new_tokens=3)
    assert len(sess.snapshot_inflight()) == 1
    sess.run_until_drained()
    snap = spans.snapshot()
    spans.clear()
    return snap


def test_a_program_after_an_empty_session_is_told_apart(two_bursts):
    """The gap before it is the wait for traffic, not the host's cost: the
    step says how long the session was empty (the sleeps, and the whole of
    a step on an empty session), and the readers leave out the first
    program of a step that says so."""
    steps = _steps(two_bursts)
    (empty,) = [s for s in steps[1:] if not s["attrs"]["live"]
                and not s["attrs"]["queued"]]
    before = steps[steps.index(empty) - 1]
    assert 20e6 <= empty["attrs"]["empty_ns"] \
        == empty["t0_ns"] - before["t1_ns"]
    after = steps[steps.index(empty) + 1]
    assert 20e6 <= after["attrs"]["empty_ns"] \
        < after["t0_ns"] - empty["t1_ns"]      # until the submit, not after
    for step in steps[1:]:
        if step not in (empty, after):
            assert step["attrs"]["empty_ns"] == 0
    calls = session_timeline.calls(two_bursts["spans"])
    idle = [c for c in calls if c["after_idle"]]
    assert len(idle) == 2 and idle[0] is calls[0]
    assert after["t0_ns"] < idle[1]["t0_ns"] < after["t1_ns"]
    assert idle[1]["host_gap_ns"] >= 40e6       # both sleeps
    for name in (session_timeline.PREFILL_CALL, session_timeline.DECODE_CALL):
        assert not any(c["after_idle"] for c in
                       session_timeline.steady_calls(two_bursts["spans"],
                                                     name))


def test_empty_intervals_lie_between_steps_and_sum_to_empty_ns(two_bursts):
    """`easydist.serve.empty`: one record an emptiness that a `submit()`
    ended or a `step()` found — between steps, never over a step that ran a
    program, and together exactly the steps' `empty_ns`."""
    records = two_bursts["spans"]
    empties = [r for r in records if r["name"] == "easydist.serve.empty"]
    steps = _steps(two_bursts)
    # since the session was made; the drained session up to the empty step;
    # from that step's end to the second submit
    assert len(empties) == 3
    assert all(r["parent_id"] == 0 and r["attrs"] == {} for r in empties)
    assert sum(r["t1_ns"] - r["t0_ns"] for r in empties) \
        == sum(s["attrs"]["empty_ns"] for s in steps)
    for r in empties:
        assert r["t0_ns"] < r["t1_ns"]
        assert not any(s["t0_ns"] < r["t1_ns"] and r["t0_ns"] < s["t1_ns"]
                       for s in steps)
    for a, b in zip(empties, empties[1:]):
        assert a["t1_ns"] <= b["t0_ns"]
    # the two that a submit ended end at that request's `submit_ns`
    submitted = sorted(t["submit_ns"] for t in two_bursts["requests"])
    assert [empties[0]["t1_ns"], empties[2]["t1_ns"]] == submitted
    (found,) = [s for s in steps[1:] if not s["attrs"]["live"]
                and not s["attrs"]["queued"]]
    assert empties[1]["t1_ns"] == found["t0_ns"]
    assert empties[2]["t0_ns"] == found["t1_ns"]


def test_a_session_with_work_all_the_way_records_one_emptiness(run):
    _, _, snap, _ = run
    (only,) = [r for r in snap["spans"] if r["name"] == "easydist.serve.empty"]
    assert only["t1_ns"] - only["t0_ns"] \
        == _steps(snap)[0]["attrs"]["empty_ns"]


def test_a_submit_during_a_step_on_an_empty_session_records_no_emptiness(
        model):
    """Another thread's `submit()` while a `step()` that found the session
    empty is running: the emptiness up to that step is recorded already, so
    the submit writes no second record from the same start, over the step."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=SLOTS,
                     prefill_chunk=CHUNK, prefill_batch=ROWS)
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    admit_one, late = sess._admit_one, []

    def admit_after_a_late_submit():
        if not late:
            late.append(sess.submit(PROMPTS[1], max_new_tokens=2))
        return admit_one()

    sess._admit_one = admit_after_a_late_submit
    spans.clear()
    sess.step()                           # empty when it began
    sess.run_until_drained()
    snap = spans.snapshot()
    spans.clear()
    assert late[0].done()
    steps = _steps(snap)
    (only,) = [r for r in snap["spans"] if r["name"] == "easydist.serve.empty"]
    assert only["t1_ns"] == steps[0]["t0_ns"]
    assert only["t1_ns"] - only["t0_ns"] \
        == sum(s["attrs"]["empty_ns"] for s in steps)


def test_submit_and_snapshot_inflight_are_spans_between_the_steps(two_bursts):
    records = two_bursts["spans"]
    submits = [r for r in records if r["name"] == "easydist.serve.submit"]
    looks = [r for r in records
             if r["name"] == "easydist.serve.snapshot_inflight"]
    assert [r["attrs"] for r in submits] == [
        {"prompt_len": len(PROMPTS[1])}, {"prompt_len": len(PROMPTS[4])}]
    assert [r["attrs"] for r in looks] == [{"n": 0}, {"n": 1}]
    steps = _steps(two_bursts)
    for r in submits + looks:
        assert r["parent_id"] == 0
        assert not any(s["t0_ns"] < r["t1_ns"] and r["t0_ns"] < s["t1_ns"]
                       for s in steps)


def test_timelines(run):
    _, results, snap, _ = run
    ring = {r["request_id"]: r for r in snap["requests"]}
    assert len(ring) == len(PROMPTS)
    steps = _steps(snap)
    for prompt, n_new, res in zip(PROMPTS, NEW, results):
        t = res["timing"]
        assert t == ring[t["request_id"]]
        assert len(res["ids"]) == n_new == len(t["token_ns"])
        assert (t["prompt_len"], t["prefix_len"]) == (len(prompt), 0)
        assert t["finish_reason"] == res["finish_reason"] == "length"
        assert t["first_token_ns"] == t["token_ns"][0]
        stamps = [t["submit_ns"], t["admit_ns"]] + t["token_ns"] \
            + [t["finish_ns"]]
        assert stamps == sorted(stamps)
        # the first token is made inside a step, before that step's end;
        # the decode round of the SAME step makes the second: a clock
        # outside the session sees both at once
        (made_in,) = [s for s in steps
                      if s["t0_ns"] <= t["first_token_ns"] <= s["t1_ns"]]
        assert t["first_token_ns"] < made_in["t1_ns"]
        if n_new > 1:
            assert t["first_token_ns"] < t["token_ns"][1] <= made_in["t1_ns"]


def test_finish_spans_carry_their_request(run):
    _, results, snap, _ = run
    finishes = [r for r in snap["spans"]
                if r["name"] == "easydist.serve.prefill.finish"]
    assert sorted(f["attrs"]["request_id"] for f in finishes) \
        == sorted(res["timing"]["request_id"] for res in results)
    for f in finishes:
        (t,) = [res["timing"] for res in results
                if res["timing"]["request_id"] == f["attrs"]["request_id"]]
        assert f["t0_ns"] <= t["first_token_ns"] <= f["t1_ns"]


def test_queue_wait_and_ttft_are_fed(run):
    _, results, _, sess = run
    latency = sess.metrics.snapshot()["latency"]
    assert latency["queue_wait"]["count"] == len(PROMPTS)
    assert latency["ttft"]["count"] == len(PROMPTS)
    waits = [(r["timing"]["admit_ns"] - r["timing"]["submit_ns"]) / 1e9
             for r in results]
    assert latency["queue_wait"]["mean_s"] == pytest.approx(
        sum(waits) / len(waits))
    # the last two waited for a slot, the first did not
    assert waits[0] < waits[-1]


def test_session_programs_have_distinct_stable_names(model):
    """The `XLA Modules` line of a device trace names a program by its
    jit: every program a session builds gets its function's name."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     prefill_chunk=CHUNK, speculate_k=2)
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    names = [sess._paged_defs[k].__name__ for k in sorted(sess._paged_defs)]
    assert names == ["_prefill_chunk_paged", "_decode_paged",
                     "_page_export", "_page_import", "_verify_paged"]
    assert len(set(names)) == len(names), names
    # and the name reaches the jit (api.py names the module after it:
    # tests/test_runtime/test_spans.py)
    fut = sess.submit(PROMPTS[0], max_new_tokens=3)
    sess.run_until_drained()
    assert len(fut.result(timeout=5)["ids"]) == 3
    assert {"chunk", "decode"} <= set(sess._paged_cs)
    for key, compiled in sess._paged_cs.items():
        for result in compiled._cache.values():
            assert result.name == result.tree_jitted.__name__ \
                == sess._paged_defs[key].__name__
    spans.clear()
