"""The six `idle_*_pct` readers of PR 50 (`chipbench/idle_timeline.py`):
the recorder's ring joined to a device trace, every idle nanosecond of the
chip put down to one class — on a hand-built ring and trace of three steps
with known gaps, on the pair recorded on the v5e
(`chipbench/recorded/serve-1chip-joined.json.gz`), and in every serving
cell's traced rehearsal."""

import copy
import importlib.util
import math
import os

import pytest

from chipbench import contract, idle_timeline, session_timeline, trace_reduce

from ._rehearse import BENCH, CELLS, last_line, run_cell

US = 1_000
MS = 1_000_000
OFFSET = 7_000_000_123          # the trace's clock less the recorder's
SIX = ["idle_in_program_pct", "idle_call_pct", "idle_session_pct",
       "idle_caller_pct", "idle_empty_pct", "idle_unattributed_pct"]
SERVING = [w["name"] for w in BENCH["workloads"]
           if w["name"].startswith("serve-")]
PERIOD, WINDOW_MS = 40, 120


def hand_built(empty_records=True, shift_us=0):
    """(records, trace, window_s): three steps of 30 ms, 40 ms apart, each a
    bare round (ms from the step's start, the recorder's clock):

        0 - 3     the step's own code, `admit` (0.1 - 0.2), `decode.build`
                  (1 - 3)                                     session 3
        3 - 25    `decode.call`; the dispatch 3.5 - 5         call 2 (dispatch)
                  the execution 7 - 20                        call 2 (launch)
                  its ops 7 - 10, 11 - 15, 16 - 20            in_program 2
                  `ready_ns` 23.5            call 3.5 + 1.5 (readback)
        25 - 30   `decode.harvest` 25 - 28 with a `retire` 26 - 27, the
                  step's own code                             session 5
        30 - 40   `snapshot_inflight` 31 - 32                 session 1
                  the caller's loop                           caller 9

    but between the second step and the third the session is empty from 33
    to 38, where a `submit` (37.9 - 38.2) ends it: empty 5, session 0.2
    more, caller 3.8.  The window is 120 ms round the 110 the steps span:
    10 at its edges.  The device's events lie `shift_us` EARLY in the
    trace."""
    records, events, ops, modules = [], [], [], []
    ids = iter(range(1, 1000))

    def rec(name, t0, t1, parent=0, **attrs):
        records.append({"name": name, "id": next(ids), "parent_id": parent,
                        "t0_ns": round(t0 * MS), "t1_ns": round(t1 * MS),
                        "attrs": attrs})
        return records[-1]["id"]

    for k in range(3):
        t = 1000 + k * PERIOD
        empty_ns = 5 * MS if k == 2 else 0
        step = rec("easydist.serve.step", t, t + 30, step=k + 1, live=1,
                   queued=0, empty_ns=empty_ns)
        rec("easydist.serve.admit", t + .1, t + .2, step, admitted=0,
            deferred=0)
        rec("easydist.serve.decode.build", t + 1, t + 3, step)
        call = rec("easydist.serve.decode.call", t + 3, t + 25, step,
                   fn="_decode_paged", rows=1, h2d=1,
                   ready_ns=round((t + 23.5) * MS))
        rec("easydist.step.call", t + 3.5, t + 5, call, fn="_decode_paged")
        harvest = rec("easydist.serve.decode.harvest", t + 25, t + 28, step)
        rec("easydist.serve.retire", t + 26, t + 27, harvest, reason="length",
            request_id=k)
        rec("easydist.serve.snapshot_inflight", t + 31, t + 32, n=1)
        if k == 1:
            if empty_records:
                records.append({
                    "name": "easydist.serve.empty", "id": next(ids),
                    "parent_id": 0, "t0_ns": (t + 33) * MS,
                    "t1_ns": (t + 38) * MS, "attrs": {}})
            rec("easydist.serve.submit", t + 37.9, t + 38.2, prompt_len=5)
        events.append(["chipbench.session_step",
                       t * MS - 12 * US + OFFSET, 30 * MS + 24 * US])
        dev = t * MS + OFFSET - shift_us * US
        modules.append(["jit__decode_paged(1)", dev + 7 * MS, 13 * MS])
        ops += [["%fusion.1 fusion", dev + 7 * MS, 3 * MS],
                ["%fusion.2 fusion", dev + 11 * MS, 4 * MS],
                ["%fusion.3 fusion", dev + 16 * MS, 4 * MS]]
    trace = {"device_kind": "TPU v5 lite", "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": events}]}]}
    return records, trace, WINDOW_MS / 1e3


EXPECTED_MS = {"in_program": 6, "call": 27, "session": 26.2, "caller": 12.8,
               "empty": 5, "unattributed": 10}


def _attribute(records, trace, window_s):
    said = []
    res = idle_timeline.attribute(records, trace, window_s, say=said.append)
    return res, idle_timeline.by_class(res["ns"]), said


def test_every_gap_of_three_hand_built_steps_goes_to_its_one_class():
    res, classes, said = _attribute(*hand_built())
    assert {k: v / MS for k, v in classes.items()} \
        == pytest.approx(EXPECTED_MS, abs=1e-9)
    # no piece twice, none lost: the six are the window less the ops
    assert sum(classes.values()) == res["idle_ns"] \
        == WINDOW_MS * MS - 3 * 11 * MS
    by_label = {k: v / MS for k, v in res["ns"].items()}
    assert by_label == pytest.approx({
        "in_program": 6, "call.launch": 6, "call.dispatch": 6,
        "call.readback.wait": 10.5, "call.readback.copy": 4.5, "empty": 5,
        "caller": 12.8,
        "session.serve.step": 3 * (.1 + .8 + 2), "session.serve.admit": .3,
        "session.serve.decode.build": 6, "session.serve.decode.harvest": 6,
        "session.serve.retire": 3, "session.serve.snapshot_inflight": 2,
        "session.serve.submit": .2, "unattributed.edges": 10}, abs=1e-9)
    assert res["joined"]["offset_ns"] == OFFSET    # the middle of 12 | 12
    assert res["joined"]["spread_ns"] == 0
    # 3.5 ms either side of an execution; a `.call`'s parts at the middle |
    # the low end | the high end of that, and their sum the same at each
    assert res["shift_range_ns"] == (-3.5 * MS, 3.5 * MS)
    assert any("launch 5.000 | 0.000 | 13.750" in line
               and "together 22.500 | 22.500 | 22.500" in line
               for line in said), said
    # the slowest twentieth of three steps is one: its 30 ms less the ops'
    assert res["p95_ns"] == 30 * MS
    assert sum(res["p95"].values()) == 30 * MS - 11 * MS
    assert "caller" not in res["p95"] and "empty" not in res["p95"]


def test_the_join_finds_the_traced_steps_in_a_ring_that_holds_more():
    plain, _, _ = _attribute(*hand_built())
    records, trace, window_s = hand_built()
    # steps before and after the traced part: 41 ms apart, not the events' 40
    more = copy.deepcopy(records)
    for r in more:
        by = 700 * MS + (r["t0_ns"] // MS - 1000) // PERIOD * MS
        r["id"] += 5000
        r["parent_id"] += 5000 if r["parent_id"] else 0
        r["t0_ns"] += by
        r["t1_ns"] += by
        if "ready_ns" in r["attrs"]:
            r["attrs"]["ready_ns"] += by
    early = copy.deepcopy(more)
    for r in early:
        r["id"] += 5000
        r["parent_id"] += 5000 if r["parent_id"] else 0
        r["t0_ns"] -= 1400 * MS
        r["t1_ns"] -= 1400 * MS
        if "ready_ns" in r["attrs"]:
            r["attrs"]["ready_ns"] -= 1400 * MS
    res, classes, _ = _attribute(early + records + more, trace, window_s)
    assert [s["t0_ns"] for s in res["joined"]["steps"]] \
        == [s["t0_ns"] for s in plain["joined"]["steps"]] \
        == [(1000 + k * PERIOD) * MS for k in range(3)]
    assert res["ns"] == plain["ns"]


def test_a_program_before_the_empty_record_has_its_emptiness_under_caller():
    """The parent of PR 50: the third step carries `empty_ns` 5 ms and no
    record says where, so the chip's idle time of those 5 ms is the
    caller's (and the `submit`'s, for the tenth that ended it) — said on
    stderr, never silently."""
    res, classes, said = _attribute(*hand_built(empty_records=False))
    want = {**EXPECTED_MS, "empty": 0, "session": 26.2 + .1,
            "caller": 12.8 + 4.9}
    assert {k: v / MS for k, v in classes.items()} \
        == pytest.approx(want, abs=1e-9)
    assert sum(classes.values()) == res["idle_ns"]
    assert any("no `easydist.serve.empty` record" in line for line in said)
    assert not any("no `easydist.serve.empty` record" in line
                   for line in _attribute(*hand_built())[2])


def test_a_ring_that_cannot_be_paired_gives_everything_to_unattributed():
    records, trace, window_s = hand_built()
    for r in records:            # steps the events keep no distance to
        if r["name"] == "easydist.serve.step":
            r["t0_ns"] += r["attrs"]["step"] ** 2 * MS
    res, classes, said = _attribute(records, trace, window_s)
    assert res["joined"] is None
    assert classes == {**dict.fromkeys(idle_timeline.CLASSES, 0),
                       "unattributed": res["idle_ns"]}
    assert any("no join" in line for line in said)
    # and so do no records at all, and a trace without the runner's events
    assert _attribute([], trace, window_s)[1] == classes
    bare = {**trace, "planes": trace["planes"][:1]}
    assert _attribute(hand_built()[0], bare, window_s)[1] == classes


def test_device_events_that_lie_early_are_moved_into_the_range_causality_allows():
    """An execution cannot start before the dispatch that enqueued it opened
    (3.5) nor end after `ready_ns` (23.5): 4 ms early, its 7 - 20 reads
    3 - 16, so the device's events belong 0.5 to 7.5 ms later.  Wherever in
    that range they are put the six classes read the same; only what lies
    before an execution against what lies after it moves, on stderr."""
    plain, _, _ = _attribute(*hand_built())
    res, classes, said = _attribute(*hand_built(shift_us=4000))
    assert res["shift_range_ns"] == (0.5 * MS, 7.5 * MS)
    assert any("500.0 to 7500.0 us later" in line for line in said)
    assert res["ns"] == plain["ns"]        # the middle is the truth here
    assert sum(classes.values()) == res["idle_ns"]
    assert any("together 22.500 | 22.500 | 22.500" in line for line in said)


def test_a_call_without_an_execution_is_unattributed():
    records, trace, window_s = hand_built()
    lines = trace["planes"][0]["lines"]
    lines[0]["events"] = [e for e in lines[0]["events"][:2]]
    lines[1]["events"] = [e for e in lines[1]["events"][:6]]
    res, classes, said = _attribute(records, trace, window_s)
    assert any("1 of 3 `.call`s found no execution" in line for line in said)
    # the third `.call`: 2 of dispatch, then 20 with nothing on the device
    assert res["ns"]["unattributed.no_execution"] == 20 * MS
    assert res["ns"]["call.dispatch"] == 6 * MS
    assert sum(classes.values()) == res["idle_ns"]


def test_a_call_without_its_dispatch_record_is_unattributed():
    records, trace, window_s = hand_built()
    lost = [r for r in records if r["name"] == "easydist.step.call"][1]
    records.remove(lost)
    res, classes, said = _attribute(records, trace, window_s)
    assert any("1 of 3 `.call`s found no `easydist.step.call` inside them"
               in line for line in said)
    # the second `.call`: 4 before its execution and 5 after, of no class
    assert res["ns"]["unattributed.no_dispatch"] == 9 * MS
    assert classes["call"] == (27 - 9) * MS
    assert sum(classes.values()) == res["idle_ns"]


def test_the_readers_share_one_computation_and_state_what_the_entry_states():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == SIX
    run = {"serve": {"arena_pages": 1}, "rehearse": True,
           "trace": {"trace": None, "window_s": 0.0}}
    values = {}
    for name in SIX:
        path = os.path.join(contract.ROOT, "chipbench", "metrics",
                            name + ".py")
        spec = importlib.util.spec_from_file_location("m_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        entry = entries[name]
        assert mod.META == {k: entry[k]
                            for k in ("layer", "unit", "moves", "source")}
        assert entry["better"] == "lower" and entry["workloads"] == SERVING
        assert mod.read({"chips": 4}) is None       # not a serving run
        assert mod.read({"serve": {"arena_pages": 1}}) is None   # untraced
        values[name] = mod.read(run)
    assert all(math.isfinite(v) and v >= 0 for v in values.values())
    assert values == {f"idle_{k}_pct": v
                      for k, v in run["_idle_timeline"].items()}


def test_the_join_on_a_real_capture_of_a_tiny_session(tmp_path):
    """The recorder and the profiler for real (the CPU backend's host
    plane; the join needs no device): steps before, inside and after a capture,
    each wrapped as the runner wraps them; the join finds the steps of the
    capture among them, and the two clocks differ by one constant to well
    under 100 us."""
    import jax

    from easydist_tpu.models import gpt
    from easydist_tpu.runtime import spans
    from easydist_tpu.serve import GenerationSession, ServeConfig

    cfg = gpt.GPTConfig.tiny()
    sess = GenerationSession.for_gpt(
        gpt.gpt_init(cfg, jax.random.PRNGKey(0)), cfg,
        config=ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                           prefill_chunk=4))
    sess.submit([3, 1, 4, 1, 5], max_new_tokens=2)
    sess.run_until_drained()              # compiled
    spans.clear()

    def turn():
        with jax.profiler.TraceAnnotation(idle_timeline.STEP_EVENT):
            sess.step()

    sess.submit([2, 7, 1, 8, 2, 8], max_new_tokens=24)
    for _ in range(4):
        turn()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(9):
            turn()
    sess.run_until_drained()
    records = spans.snapshot()["spans"]
    spans.clear()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(str(tmp_path)))
    events = idle_timeline.step_events(trace)
    assert len(events) == 9
    said = []
    steps = session_timeline.steps(records)
    assert len(steps) > 14
    joined = idle_timeline.join(steps, events, said.append)
    assert joined["steps"] == steps[4:13], said
    assert joined["spread_ns"] < idle_timeline.JOIN_SLACK_NS
    for s, (start, dur) in zip(joined["steps"], events):   # enclosed
        assert start <= s["t0_ns"] + joined["offset_ns"] + 50 * US
        assert s["t1_ns"] + joined["offset_ns"] <= start + dur + 50 * US


# ------------------------------------------------- the pair recorded on v5e

@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load_recorded(idle_timeline.RECORDED_JOINED)


def test_the_recorded_pair_is_small_and_cut_to_the_same_whole_steps(recorded):
    assert os.path.getsize(idle_timeline.RECORDED_JOINED) < 500_000
    records, trace = recorded["spans"], recorded["trace"]
    steps = session_timeline.steps(records)
    events = idle_timeline.step_events(trace)
    inside = idle_timeline.join(steps, events, lambda line: None)["steps"]
    assert len(inside) == len(events) >= 20
    # the ring reaches a second before the capture and a second after it
    assert steps[0]["t0_ns"] < inside[0]["t0_ns"] - 0.9e9
    assert steps[-1]["t1_ns"] > inside[-1]["t1_ns"] + 0.9e9
    assert any(r["name"] == "easydist.serve.empty" for r in records)
    # every op and every execution lies inside the traced steps
    first, last = events[0][0], events[-1][0] + events[-1][1]
    (plane,) = trace_reduce.device_planes(trace)
    for line in plane["lines"]:
        assert line["name"] in ("XLA Ops", "XLA Modules")
        for _, start, dur in line["events"]:
            assert first - MS < start and start + dur < last + MS


def test_the_six_of_the_recorded_pair_sum_to_its_idle_share(recorded):
    res, classes, said = _attribute(recorded["spans"], recorded["trace"],
                                    recorded["window_s"])
    busy = trace_reduce.busy(recorded["trace"], 1)["busy_s"]
    idle_pct = 100.0 * (1.0 - busy / recorded["window_s"])   # `.chat`'s
    six = {k: 100.0 * v / res["window_ns"] for k, v in classes.items()}
    assert sum(six.values()) == pytest.approx(idle_pct, abs=1e-4)
    assert abs(sum(classes.values()) - res["idle_ns"]) \
        <= 1e-6 * res["window_ns"]
    assert res["joined"]["spread_ns"] < idle_timeline.JOIN_SLACK_NS // 4
    assert six["unattributed"] < 2.0
    assert all(v >= 0 for v in classes.values())
    # a decode round's cycle is there: every class of the host's is above 0
    for k in ("in_program", "call", "session", "caller", "empty"):
        assert six[k] > 0, k
    for part in ("dispatch", "launch", "readback.wait", "readback.copy"):
        assert res["ns"]["call." + part] > 0


def test_the_recorded_pairs_traced_steps_lie_inside_their_events(recorded):
    """The join is right, not just steady: every traced step on the trace's
    clock is enclosed by the wrapper the runner opened round it, and a
    `.call`'s idle time reads the same at both ends of the range the device
    plane's events may lie in."""
    res, _, said = _attribute(recorded["spans"], recorded["trace"],
                              recorded["window_s"])
    joined = res["joined"]
    events = idle_timeline.step_events(recorded["trace"])
    for s, (start, dur) in zip(joined["steps"], events):
        assert start - 20 * US <= s["t0_ns"] + joined["offset_ns"]
        assert s["t1_ns"] + joined["offset_ns"] <= start + dur + 20 * US
    low, high = res["shift_range_ns"]
    assert 0.3 * MS < high - low < 2 * MS
    (line,) = [line for line in said if "inside the `.call`s" in line]
    middle, at_low, at_high = line.rsplit("together ", 1)[1].split(" | ")
    assert middle == at_low == at_high


def test_capture_overhead_is_read_off_the_ring_alone(recorded):
    flagged, _, _ = _attribute(recorded["spans"], recorded["trace"],
                               recorded["window_s"])
    over = flagged["capture_overhead_ms"]
    assert over is not None and -1.0 < over < 5.0
    steps = session_timeline.steps(recorded["spans"])
    assert over == idle_timeline.capture_overhead_ms(
        steps, flagged["joined"]["steps"])
    assert idle_timeline.capture_overhead_ms(steps, []) is None


# ------------------------------------------------- the traced rehearsals

@pytest.mark.parametrize("cell", SERVING)
def test_a_traced_rehearsal_carries_the_six(cell):
    rc, out, err = run_cell("--workload", cell, "--seed", "5", "--seconds",
                            "2", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[cell], True, BENCH)
    for name in SIX:
        assert obj["metrics"][name]["unit"] == "%"
        assert math.isfinite(obj["metrics"][name]["value"])
    assert "idle timeline: % of the traced" in err
    assert "not reported: capture_host_overhead_ms_per_step" in err
