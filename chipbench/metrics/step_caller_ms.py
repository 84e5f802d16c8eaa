"""What the loop that drives the session costs a round: the median
time from the previous step's end to this one's start over the steady
steps that ran a decode round and follow one that did, the session never
empty in between (`chipbench/session_timeline.py`).  In it: the caller's
`submit()` and `snapshot_inflight()` — the session's own
`easydist.serve.submit` / `.snapshot_inflight` spans say how much — and the
caller's own code."""

import statistics

from chipbench import session_timeline

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    snap = session_timeline.snapshot(run)
    steps = session_timeline.steps(snap["spans"]) if snap else []
    between = [s["since_prev_ns"] for prev, s in zip(steps, steps[1:])
               if s["steady"] and not s["empty_ns"]
               and session_timeline.ran_a_round(prev)
               and session_timeline.ran_a_round(s)]
    return statistics.median(between) / 1e6 if between else None
