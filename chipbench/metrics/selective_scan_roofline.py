"""The selective-state chunk scan's share of its BYTES' roofline in the
traced part: what its calls need to move — float32 x, dt and y and the B
and C rows of every REAL position of every selective layer (the session's
`selective_scan_positions` over the traced calls: real positions x layers),
and each call's rows' states read once and written once a layer — at the
HBM peak, over the kernel's time INSIDE the chunk-prefill program's
executions (`selective_trace`).  The kernel is bound by `exp` and vector
work, not by bytes (9 operations a byte moved, none of them a matrix
product, so the table's matrix peak says nothing of it), and reads low by
construction: the state elements it updates a second are logged beside it
(`runners/serve_selective.py`, PERF.md section 5)."""

from chipbench import (kernel_costs, kernel_costs_selective, programs,
                       selective_trace)

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def scan_seconds(run):
    return selective_trace.kernel_seconds(run, programs.PREFILL_CHUNK)


def read(run):
    if not run.get("trace") or not run["trace"].get("counted"):
        return None
    counted = run["trace"]["counted"]
    positions = counted.get("selective_scan_positions")
    calls = counted.get("prefill_chunks")
    secs = scan_seconds(run)
    if not positions or not calls or secs is None:
        return None
    rows = run["cell"]["serve_config"]["prefill_batch"]
    least = kernel_costs_selective.scan_bytes(
        positions, calls, rows, run["sizes"]) \
        / kernel_costs.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / secs
