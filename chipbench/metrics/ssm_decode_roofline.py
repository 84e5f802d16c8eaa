"""The state-update kernel's share of its roofline in the traced part:
the state bytes read and written for the LIVE rows of the traced decode
rounds (the session's `tokens_generated`: one a live row a round) in every
state layer, at the HBM peak — five FLOPs a state element, it is bound by
bytes — over the kernel's time in the trace.  A dead slot costs the kernel a
grid step and no state traffic."""

from chipbench import hybrid_trace, kernel_costs, kernel_costs_hybrid

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = hybrid_trace.seconds(run, hybrid_trace.STATE_UPDATE)
    if secs is None:
        return None
    sizes = run["sizes"]
    rows = run["trace"]["counted"]["tokens_generated"]
    layers = hybrid_trace.state_layers(sizes)
    least = kernel_costs.roofline_seconds(
        kernel_costs_hybrid.ssm_update_flops(rows, layers, sizes),
        kernel_costs_hybrid.ssm_update_bytes(rows, layers, sizes),
        kernel_costs.peaks(run["device_kind"]))[0]
    return 100.0 * least / secs if least > 0 else None
