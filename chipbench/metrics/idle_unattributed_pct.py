"""Share of the traced window in which chip 0 sat idle under no class: the
window's edges before the first and after the last traced step, a `.call`
whose execution was not found — and ALL of `device_idle_pct.chat`'s idle
time, with the reason on stderr, where the ring and the trace cannot be
joined.  One of the six `idle_*_pct` that add up to `device_idle_pct.chat`
of the same run (`chipbench/idle_timeline.py`: the recorder's ring joined to
the device trace)."""

from chipbench import idle_timeline

META = {"layer": "device", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return idle_timeline.share(run, "unattributed")
