"""Time to first token, p90 over the requests due in the window, each timed
from when it was DUE to the end of the `step()` that produced its first
token.  No end-to-end metric: at 50 requests a window it swings by 12-16%
between runs and by more between seeds (PERF.md section 2)."""

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "host_clock"}


def read(run):
    return (run.get("serve") or {}).get("ttft_p90_ms")
