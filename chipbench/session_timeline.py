"""The time between two programs, as the session records it (PR 36): what
the seven readers `session_empty_pct`, `decode_gap_host_ms`,
`prefill_gap_host_ms`, `step_caller_ms`, `decode_launch_readback_ms`,
`serve_compile_s` and `serve_xla_compiles` take from the program's span
recorder (`easydist_tpu/runtime/spans.py`), over the whole run.

Nearly all of it is in the records as they are.  The `easydist.step.call`
inside a `.call` ends when the program is enqueued (`dispatched_ns` here);
from the previous `.call`'s end to there nothing is in flight (the host's
gap), from there to the `.call`'s end one program is; from a step's end to
the next one's start the caller's loop runs.  The session stamps the two
things no record shows: `ready_ns` on a `.call` (`block_until_ready`
returned, before the copy out) and `empty_ns` on a step (how much of the
time since the previous step the session had nothing live and nothing
queued).  The first program of a step whose `empty_ns` is above 0 follows
an empty session: its gap is a wait for traffic, and is left out.  A
program built before `empty_ns` (the parent of PR 36, which the driver runs
under these readers) has the interval before a step that found nothing live
counted as empty, whole — it cannot see a session that emptied and was
given work again between two steps.  Nothing here reads the benchmark's own
spans, and only `paired_overhead_ms` is handed a device trace's executions.

"Steady" leaves out a program whose `.call` opens before the last
`easydist.step.compile` of its OWN `fn` does (not the last compile of the
run: `_page_export` compiles after the window), and a step that opens before
the last compile of any program the session's rounds run."""

import statistics

import numpy as np

from chipbench import programs

STEP = "easydist.serve.step"
DECODE_CALL = "easydist.serve.decode.call"
PREFILL_CALL = "easydist.serve.prefill.call"
DISPATCH = "easydist.step.call"
COMPILE = "easydist.step.compile"
COMPILE_SPANS = ("easydist.compile.trace", "easydist.compile.discovery",
                 "easydist.compile.solve", "easydist.compile.emit", COMPILE)
# executions of a device trace are paired with the programs that ran them
# where, over the middle nine tenths of the pairs, the time from a program's
# being enqueued to its start on the device varies by less than this
PAIRING_SLACK_NS = 3_000_000


def snapshot(run):
    """The recorder's snapshot of a serving run; None for any other run."""
    return programs.recorder_snapshot() if run.get("serve") else None


def _last_compile_ns(records) -> dict:
    """{fn: start of its last `easydist.step.compile`}."""
    last = {}
    for r in records:
        if r["name"] == COMPILE:
            fn = r["attrs"].get("fn")
            last[fn] = max(last.get(fn, 0), r["t0_ns"])
    return last


def ran_a_round(step: dict) -> bool:
    return any(c["name"] == DECODE_CALL for c in step["calls"])


def steps(records) -> list:
    """Every `step()` in order: {t0_ns, t1_ns, live, queued, since_prev_ns
    (None for the first), empty_ns, calls: [its `.call` records], steady}."""
    by_id = {r["id"]: r for r in records}
    of_step = {r["id"]: {
        "t0_ns": r["t0_ns"], "t1_ns": r["t1_ns"],
        "live": r["attrs"].get("live"), "queued": r["attrs"].get("queued"),
        "empty_ns": r["attrs"].get("empty_ns"), "calls": []}
        for r in records if r["name"] == STEP}
    for r in records:
        if r["name"] not in (DECODE_CALL, PREFILL_CALL):
            continue
        up = by_id.get(r["parent_id"])
        while up is not None and up["name"] != STEP:
            up = by_id.get(up["parent_id"])
        if up is not None:
            of_step[up["id"]]["calls"].append(r)
    out = sorted(of_step.values(), key=lambda s: s["t0_ns"])
    for prev, s in zip([None] + out, out):
        s["calls"].sort(key=lambda r: r["t0_ns"])
        s["since_prev_ns"] = s["t0_ns"] - prev["t1_ns"] if prev else None
        if s["empty_ns"] is None:
            # before the stamp: a step that finds nothing live follows an
            # empty session, unless the step before it ran chunk calls and
            # no round (a prompt of several steps, nothing decoding yet)
            mid_prefill = prev and prev["calls"] and not ran_a_round(prev)
            s["empty_ns"] = 0 if s["live"] or mid_prefill \
                else s["since_prev_ns"] or 0
    last = _last_compile_ns(records)
    ran = {c["attrs"].get("fn") for s in out for c in s["calls"]}
    warm_from = max((last[fn] for fn in ran if fn in last), default=0)
    for s in out:
        s["steady"] = s["t0_ns"] > warm_from
    return out


def calls(records) -> list:
    """Every program the session ran, in order of dispatch: {name, t0_ns,
    t1_ns, dispatched_ns, ready_ns (None before the stamp), host_gap_ns
    (None for the first), after_idle, steady}."""
    dispatched = {}     # `.call` id -> end of the dispatch inside it
    for r in records:
        if r["name"] == DISPATCH:
            dispatched.setdefault(r["parent_id"], r["t1_ns"])
    last = _last_compile_ns(records)
    out, prev_end = [], None
    for s in steps(records):
        idle = s["empty_ns"] > 0
        for r in s["calls"]:
            at = dispatched.get(r["id"])
            if at is None:
                continue
            out.append({
                "name": r["name"], "t0_ns": r["t0_ns"], "t1_ns": r["t1_ns"],
                "dispatched_ns": at, "ready_ns": r["attrs"].get("ready_ns"),
                "host_gap_ns": None if prev_end is None else at - prev_end,
                "after_idle": idle,
                "steady": r["t0_ns"] > last.get(r["attrs"].get("fn"), 0)})
            prev_end, idle = r["t1_ns"], False
    return out


def steady_calls(records, name: str) -> list:
    """The steady programs under `.call` spans called `name` whose gap is
    the host's cost and not an empty session's wait."""
    return [c for c in calls(records)
            if c["name"] == name and c["steady"] and not c["after_idle"]
            and c["host_gap_ns"] is not None]


def median_gap_ms(run, name: str):
    snap = snapshot(run)
    gaps = [c["host_gap_ns"]
            for c in steady_calls(snap["spans"], name)] if snap else []
    return statistics.median(gaps) / 1e6 if gaps else None


def in_flight_ms(records, name: str):
    """Medians over the steady programs called `name`: (dispatch to the
    `.call`'s end, dispatch to ready, ready to the end) in ms — the last
    two None before the stamp; None where no such program ran."""
    mine = steady_calls(records, name)
    if not mine:
        return None
    stamped = [c for c in mine if c["ready_ns"] is not None]

    def med(values):
        return statistics.median(values) / 1e6 if values else None

    return (med([c["t1_ns"] - c["dispatched_ns"] for c in mine]),
            med([c["ready_ns"] - c["dispatched_ns"] for c in stamped]),
            med([c["t1_ns"] - c["ready_ns"] for c in stamped]))


def paired_overhead_ms(records, name: str, executions):
    """Launch plus readback of the programs a device trace saw: the median,
    over its `executions` ([(start_ns, duration_ns)] of one program, on the
    profiler's clock), of the flight of the program that ran each less its
    duration on the device.  The two clocks differ by a constant nobody
    recorded, so an execution finds its program by order: the run of
    consecutive programs called `name` whose `dispatched_ns` keep the
    steadiest distance to the executions' starts (the right run keeps it
    within the launch latency's jitter; a run one program off moves it by a
    whole round, and by another amount at every step that ran a chunk).
    None where the trace holds fewer than eight executions, more than the
    recorder has programs, or no run fits (a recorded trace under
    `--rehearse`)."""
    mine = [c for c in calls(records) if c["name"] == name]
    runs = sorted(executions)
    n = len(runs)
    if n < 8 or len(mine) < n:
        return None
    enqueued = np.array([c["dispatched_ns"] for c in mine], np.int64)
    lag = np.array([s for s, _ in runs], np.int64)[None, :] \
        - np.lib.stride_tricks.sliding_window_view(enqueued, n)
    low, high = np.percentile(lag, (5, 95), axis=1)
    first = int(np.argmin(high - low))
    if high[first] - low[first] > PAIRING_SLACK_NS:
        return None
    return statistics.median(
        c["t1_ns"] - c["dispatched_ns"] - dur
        for c, (_, dur) in zip(mine[first:first + n], runs)) / 1e6
