"""What the host does between two programs, before a decode round: the
median over the steady decode programs (`easydist.serve.decode.call`;
`chipbench/session_timeline.py`) of the time from the end of the previous
`.call` (its readback returned) to this program's being enqueued (the end
of the `easydist.step.call` inside its `.call`), with nothing in flight: the
previous round's harvest, the caller's loop, admission, this round's build
and uploads, and the jit's own dispatch.  The first program of a step whose
`empty_ns` is above 0 is left out: the session was empty in that gap."""

from chipbench import session_timeline

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return session_timeline.median_gap_ms(run, session_timeline.DECODE_CALL)
