"""The delta-rule layers' decode update's share of the device's busy time
in the traced part: chip 0's seconds in the update kernel inside the decode
program's executions (found by its result, the float32 state leaf:
`delta_trace.py`) over its busy seconds.  The chunked scan of a prefill
call is XLA matmuls with no name of their own in the reduced trace, and is
not in this number (PERF.md section 7)."""

from chipbench import delta_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = delta_trace.kernel_seconds(run)
    if secs is None or not run.get("busy"):
        return None
    return 100.0 * secs / run["busy"]["per_chip_s"][0]
