"""What the host does between two programs, before a chunk call: the
reading of `decode_gap_host_ms`, of the steady chunk programs
(`easydist.serve.prefill.call`): the first program of its step, so the
previous step's harvest, the caller's loop, admission and the chunk's build
are in it."""

from chipbench import session_timeline

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return session_timeline.median_gap_ms(run, session_timeline.PREFILL_CALL)
