"""The seven per-layer readers of PR 36 — `session_empty_pct`,
`decode_gap_host_ms`, `prefill_gap_host_ms`, `step_caller_ms`,
`decode_launch_readback_ms`, `serve_compile_s`, `serve_xla_compiles`
(`chipbench/session_timeline.py`): on a hand-made snapshot of the program's
recorder, with the session's stamps (`ready_ns`, `empty_ns`) and, as the
parent of PR 36 records it (the driver runs it under these readers),
without; and in the three serving cells' traced rehearsals."""

import math

import pytest

from chipbench import contract, session_timeline
from easydist_tpu.runtime import spans

from ._rehearse import BENCH, CELLS, last_line, run_cell
from .test_program_readers import NEW, reader

MS = 1_000_000
SERVING = ["serve-mistral7b-chat-1chip", "serve-granite4hs-chat-1chip",
           "serve-kexaone-mixedlen-1chip"]
CHUNK, DECODE = "_prefill_chunk_paged", "_decode_paged"
SEVEN = {
    # the steady steps span 3010-3593 ms; empty: 297 of the 300 before the
    # last step with work, the 10 before the last step, and that step, 1
    "session_empty_pct": 100 * 308 / 583,
    # rounds after gaps of 64, 3, 9 seven times, 3; none after idleness
    "decode_gap_host_ms": 9.0,
    # step 3's chunk call after 9; the last one follows an empty session
    "prefill_gap_host_ms": 9.0,
    # steps after 10, 3, 4 seven times; one after emptiness, one no round
    "step_caller_ms": 4.0,
    # every steady round is in the trace, 0.3 ms after it was enqueued and
    # done 0.7 ms before its `.call` ends
    "decode_launch_readback_ms": 1.0,
    # trace 1.0 + emit 0.1 + the two programs' compiles 0.3 + 0.4
    "serve_compile_s": 1.8,
    "serve_xla_compiles": 4,
}
# a program before `empty_ns` has the whole interval before a step that
# found nothing live counted as empty: 300, not 297
BEFORE_THE_STAMPS = {**SEVEN, "session_empty_pct": 100 * 311 / 583}


@pytest.fixture(autouse=True)
def _clean_recorder():
    spans.clear()
    yield
    spans.clear()


def hand_made_run(stamps: bool) -> dict:
    """A compiling step, a bare round, a chunk call and a round, seven bare
    rounds, an empty stretch ended by a submit, a chunk call and a round, a
    step on an empty session — as `GenerationSession` records them (ms),
    with or without `ready_ns` and `empty_ns`; and a device trace of the
    steady rounds, on a clock of its own."""
    modules = []

    def call(step, name, fn, t0, at, t1, compiles=False):
        attrs = {"fn": fn}
        if stamps:
            attrs["ready_ns"] = int((t1 - .5) * MS)
        span_id = spans.record_span(name, t0 * MS, t1 * MS, parent_id=step,
                                    **attrs)
        inside = spans.record_span("easydist.step.call", t0 * MS, at * MS,
                                   parent_id=span_id, fn=fn)
        if compiles:
            spans.record_span("easydist.step.compile", t0 * MS, at * MS,
                              parent_id=inside, fn=fn)
        elif name == session_timeline.DECODE_CALL:
            modules.append(["jit__decode_paged(1)",
                            at * MS + 3 * MS // 10 - 2900 * MS,
                            (t1 - at - 1) * MS])

    def step(t0, t1, live, queued, empty):
        attrs = {"live": live, "queued": queued}
        if stamps:
            attrs["empty_ns"] = empty * MS
        return spans.record_span("easydist.serve.step", t0 * MS, t1 * MS,
                                 **attrs)

    chunk, round_ = session_timeline.PREFILL_CALL, session_timeline.DECODE_CALL
    spans.record_span("easydist.compile.trace", 0, 1000 * MS, fn=CHUNK)
    spans.record_span("easydist.compile.emit", 1000 * MS, 1100 * MS, fn=CHUNK)
    s1 = step(2000, 3000, 0, 2, 900)
    call(s1, chunk, CHUNK, 2000, 2300, 2400, compiles=True)
    call(s1, round_, DECODE, 2500, 2900, 2950, compiles=True)
    s2 = step(3010, 3032, 2, 0, 0)
    call(s2, round_, DECODE, 3013, 3014, 3030)
    s3 = step(3035, 3080, 2, 1, 0)
    call(s3, chunk, CHUNK, 3038, 3039, 3060)
    call(s3, round_, DECODE, 3062, 3063, 3078)
    for t in range(3084, 3084 + 7 * 22, 22):    # until 3234, 4 apart
        bare = step(t, t + 18, 3, 0, 0)
        call(bare, round_, DECODE, t + 2, t + 3, t + 16)
    spans.record_span("easydist.serve.submit", 3531 * MS, 3532 * MS,
                      prompt_len=7)
    s5 = step(3534, 3582, 0, 1, 297)
    call(s5, chunk, CHUNK, 3536, 3537, 3557)
    call(s5, round_, DECODE, 3559, 3560, 3577)
    step(3592, 3593, 0, 0, 10)
    spans.count("xla_compiles", 2, fn=CHUNK)
    spans.count("xla_compiles", fn=DECODE)
    spans.count("xla_compiles", fn="_page_export")
    spans.count("pallas_calls", 16, kernel="paged_decode", row_shards=1)
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules}]}]}
    return {"serve": {"arena_pages": 576}, "rehearse": False,
            "trace": {"trace": trace, "window_s": 0.5}}


@pytest.mark.parametrize("stamps", [True, False],
                         ids=["stamped", "before_the_stamps"])
@pytest.mark.parametrize("name", sorted(SEVEN))
def test_a_reader_on_a_hand_made_snapshot(name, stamps):
    r = reader(name)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} \
        == r.META
    assert entry["workloads"] == SERVING
    assert r.read({"chips": 1}) is None      # an empty recorder, no run
    run = hand_made_run(stamps)
    assert r.read({"chips": 1}) is None      # spans, but no serving run
    assert r.read({"train": {}, "trace": run["trace"]}) is None
    assert r.read(run) == pytest.approx(
        (SEVEN if stamps else BEFORE_THE_STAMPS)[name], rel=1e-12)


def test_the_five_entries_before_them_are_as_they_were():
    """What `test_program_readers.py`'s pinned test says of PR 24's five
    beside their place (tests/conftest.py marks it xfail for the place
    alone): one cell each, that reader's, and better lower."""
    five = {name: cell for cell, names in NEW.items() for name in names}
    assert len(five) == 5
    for m in BENCH["per_layer"]:
        if m["name"] in five:
            assert m["workloads"] == [five.pop(m["name"])]
            assert m["better"] == "lower"
    assert not five


def test_the_seven_entries_follow_the_five_and_add_no_layer():
    names = [m["name"] for m in BENCH["per_layer"]]
    five = names.index("step_dispatch_ms")
    assert set(names[five + 1:five + 8]) == set(SEVEN)
    assert {m["layer"] for m in BENCH["per_layer"] if m["name"] in SEVEN} \
        == {"session", "emitted program", "compile"}


def test_a_gap_after_an_empty_session_is_left_out():
    """It is the wait for traffic, not the host's cost: the first program
    of a step whose `empty_ns` is above 0, stamped or (the first program of
    all apart, which has no gap) as a program before the stamp shows it."""
    for stamps in (True, False):
        spans.clear()
        hand_made_run(stamps)
        steady = session_timeline.steady_calls(
            spans.snapshot()["spans"], session_timeline.PREFILL_CALL)
        assert [c["t0_ns"] for c in steady] == [3038 * MS]
        every = [c for c in session_timeline.calls(spans.snapshot()["spans"])
                 if c["name"] == session_timeline.PREFILL_CALL]
        assert [c["after_idle"] for c in every[1:]] == [False, True]
        assert [c["steady"] for c in every] == [False, True, True]
        assert every[0]["host_gap_ns"] is None


def test_launch_and_readback_pairs_an_execution_with_its_program():
    """A round's device time follows its live sequences, so the flight and
    the device time must be of the SAME rounds: thirty rounds whose device
    time grows through the run, twelve of them traced on a clock of its own;
    each costs 0.3 ms to launch and 0.7 to read back.  The difference of
    the run's median flight and the trace's median would read the drift."""
    step = spans.record_span("easydist.serve.step", 0, 10_000 * MS,
                             live=3, queued=0, empty_ns=0)
    at, device, traced = 100 * MS, [], []
    for i in range(30):
        at += (9 + 7 * (i % 3 == 0) + i % 2) * MS    # bare and chunk steps
        dur = (10 + i // 3) * MS
        end = at + dur + 1 * MS
        call = spans.record_span(
            session_timeline.DECODE_CALL, at - MS, end, parent_id=step,
            fn=DECODE, ready_ns=end - MS // 2)
        spans.record_span("easydist.step.call", at - MS, at, parent_id=call,
                          fn=DECODE)
        if 14 <= i < 26:
            jitter = (i % 4) * MS // 20              # up to 0.15 ms
            traced.append(["jit__decode_paged(1)",
                           at + 3 * MS // 10 + jitter - 77_000 * MS, dur])
        at = end
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": traced}]}]}
    run = {"serve": {"arena_pages": 576}, "rehearse": False,
           "trace": {"trace": trace, "window_s": 0.5}}
    r = reader("decode_launch_readback_ms")
    assert r.read(run) == pytest.approx(1.0)
    # a recording (under --rehearse: the Mistral cell's, rounds of ~129 ms)
    # cannot be paired: the difference of the medians, whatever it reads,
    # for the rehearsal's line alone
    assert r.read({**run, "rehearse": True}) < -100.0
    # nor can a trace of other rounds than the recorder saw: on the chip
    # that is no reading (the medians' difference would be -0.5 here)
    for e in traced:
        e[1] = e[1] * 3
    with pytest.raises(RuntimeError, match="cannot be read"):
        r.read(run)


@pytest.mark.parametrize("cell", SERVING)
def test_a_traced_rehearsal_carries_the_seven(cell):
    rc, out, err = run_cell("--workload", cell, "--seed", str(2 ** 31 + 36),
                            "--seconds", "2", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[cell], True, BENCH)
    for name in SEVEN:
        assert math.isfinite(obj["metrics"][name]["value"]), name
    m = {name: obj["metrics"][name]["value"] for name in SEVEN}
    assert 0 <= m["session_empty_pct"] <= 100
    assert m["decode_gap_host_ms"] > 0 and m["prefill_gap_host_ms"] > 0
    assert m["step_caller_ms"] > 0 and m["serve_compile_s"] > 0
    assert m["serve_xla_compiles"] >= 2       # a chunk and a decode program
    assert "launch and readback" in err and "compile seconds by" in err
