"""Share of the traced window in which chip 0 sat idle inside a record of the
program that is no `.call`: a step's admission, builds, finishes, harvest
and retirements, `submit`, `snapshot_inflight` (by the innermost record's
name on stderr).  One of the six `idle_*_pct` that add up to
`device_idle_pct.chat` of the same run (`chipbench/idle_timeline.py`: the
recorder's ring joined to the device trace)."""

from chipbench import idle_timeline

META = {"layer": "session", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return idle_timeline.share(run, "session")
