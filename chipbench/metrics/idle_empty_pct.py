"""Share of the traced window in which chip 0 sat idle inside an
`easydist.serve.empty`: the session had nothing live and nothing queued, so
the chip waits for traffic, not for the host (0, with a line on stderr, for
a program that writes no such record).  One of the six `idle_*_pct`
that add up to `device_idle_pct.chat` of the same run
(`chipbench/idle_timeline.py`: the recorder's ring joined to the device
trace)."""

from chipbench import idle_timeline

META = {"layer": "session", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return idle_timeline.share(run, "empty")
