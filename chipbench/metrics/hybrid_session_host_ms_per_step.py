"""The host's share of a serving step of a model with state layers: the
reading of `session_host_ms_per_step` (the median `easydist.serve.step`
span with a decode round, less its `.call` descendants), which the
contract's test keeps to one cell (PERF.md section 7)."""

from chipbench.metrics import session_host_ms_per_step as _twin

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def read(run):
    return _twin.read(run)
