"""Share of the traced window in which chip 0 sat idle inside an `XLA Modules`
execution, between its ops: the program's own bubbles, not the host's.  One
of the six `idle_*_pct` that add up to `device_idle_pct.chat` of the same
run (`chipbench/idle_timeline.py`: the recorder's ring joined to the device
trace)."""

from chipbench import idle_timeline

META = {"layer": "device", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return idle_timeline.share(run, "in_program")
