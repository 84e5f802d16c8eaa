"""Share of the traced window in which chip 0 sat idle inside a `.call` of
the session (`easydist.serve.decode.call`, `.prefill.call`): the jit's
dispatch and its one upload, the launch, the wait for the result and its
copy out — what a session that enqueues the next program before the previous
one is read back takes away.  ONE number, because a trace's device plane is
on its host plane's clock only to within the millisecond these four share;
stderr has the four at both ends of what causality allows.  One of the six
`idle_*_pct` that add up to `device_idle_pct.chat` of the same run
(`chipbench/idle_timeline.py`: the recorder's ring joined to the device
trace)."""

from chipbench import idle_timeline

META = {"layer": "emitted program", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return idle_timeline.share(run, "call")
