"""Share of the session's wall time in which it had nothing live and nothing
queued: over the steps from the first steady one to the last of the run
(`chipbench/session_timeline.py`), the `empty_ns` of every later step (the
part of the interval before it in which the session was empty, as `step()`
stamps it) plus the whole of every step that found `live=0, queued=0` and
ran no program, over the wall time from the first's start to the last's end.
A device that idles under an empty session waits for traffic, not for the
host: `device_idle_pct.chat` cannot tell the two apart, this can.

A program built before the stamp: the interval before a step that starts
with nothing live counts as empty, whole."""

from chipbench import session_timeline

META = {"layer": "session", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    snap = session_timeline.snapshot(run)
    steps = [s for s in session_timeline.steps(snap["spans"])
             if s["steady"]] if snap else []
    if len(steps) < 2:
        return None
    empty = sum(s["empty_ns"] for s in steps[1:]) + sum(
        s["t1_ns"] - s["t0_ns"] for s in steps
        if not s["live"] and not s["queued"] and not s["calls"])
    return 100.0 * empty / (steps[-1]["t1_ns"] - steps[0]["t0_ns"])
