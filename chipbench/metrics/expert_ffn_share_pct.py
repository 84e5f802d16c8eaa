"""The routed expert FFN's share of the device's busy time in the traced
part: chip 0's seconds in the grouped-matmul kernel (both products, decode
rounds and chunk calls alike; found by its result, `hybrid_trace.py`) over
its busy seconds."""

from chipbench import hybrid_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = hybrid_trace.seconds(run, hybrid_trace.EXPERT_MATMUL)
    if secs is None or not run.get("busy"):
        return None
    return 100.0 * secs / run["busy"]["per_chip_s"][0]
