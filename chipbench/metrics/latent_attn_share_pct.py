"""The latent attention kernels' share of the device's busy time in the
traced part: chip 0's seconds in `latent_decode` inside the decode
program's executions and in `latent_chunk` inside the chunk-prefill
program's (`latent_trace`), over its busy seconds."""

from chipbench import latent_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = [latent_trace.kernel_seconds(run, which)
            for which in ("decode", "chunk")]
    if all(s is None for s in secs) or not run.get("busy"):
        return None
    return 100.0 * sum(s or 0.0 for s in secs) / run["busy"]["per_chip_s"][0]
