"""The selective-state decode update's share of its roofline in the traced
part: what its calls need — the [inner, state] float32 matrix of every LIVE
row read once and written once in every selective layer (the session's
`selective_rows_updated` over the traced rounds: live rows x layers), x, dt,
B, C and y beside it, seven operations a state element
(`kernel_costs_selective`: 0.8 FLOP a byte, so bytes at the HBM peak bind) —
over the kernel's time INSIDE the decode program's executions
(`selective_trace`).  The bytes are the logical ones: a padded layout would
read lower.  A dead slot costs the kernel a grid step and no state
traffic."""

from chipbench import kernel_costs, kernel_costs_selective, selective_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    if not run.get("trace") or not run["trace"].get("counted"):
        return None
    rows = run["trace"]["counted"].get("selective_rows_updated")
    secs = selective_trace.kernel_seconds(run)
    if not rows or secs is None:
        return None
    sizes = run["sizes"]
    least = kernel_costs.roofline_seconds(
        kernel_costs_selective.update_flops(rows, sizes),
        kernel_costs_selective.update_bytes(rows, sizes),
        kernel_costs.peaks(run["device_kind"]))[0]
    return 100.0 * least / secs
