"""The two selective-state kernels' share of the device's busy time in the
traced part: chip 0's seconds in the decode update inside the decode
program's executions plus those in the chunk scan inside the chunk-prefill
program's (each found by its result, a 3-D float32 array:
`selective_trace.py`) over its busy seconds."""

from chipbench import programs, selective_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = [selective_trace.kernel_seconds(run, which)
            for which in (programs.DECODE, programs.PREFILL_CHUNK)]
    if all(s is None for s in secs) or not run.get("busy"):
        return None
    return 100.0 * sum(s or 0.0 for s in secs) / run["busy"]["per_chip_s"][0]
