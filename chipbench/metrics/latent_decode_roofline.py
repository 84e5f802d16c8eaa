"""The latent decode kernel's share of its roofline in the traced part: what
its calls need — every live cached row (576 values) read once a layer for
all 64 heads, q' and the heads' sums beside it, and 139 kFLOP a live token
(`kernel_costs_latent`), the larger of the two at the chip's peaks — over
the kernel's time INSIDE the decode program's executions (`latent_trace`;
the chunk program runs the other latent kernel, whose result has the same
rank).  The live tokens of each traced round are counted by the runner from
the tokens it stamped."""

from chipbench import kernel_costs, kernel_costs_latent, latent_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = latent_trace.kernel_seconds(run, "decode")
    if secs is None:
        return None
    sizes = run["sizes"]
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = sizes["num_hidden_layers"] * sum(kernel_costs.roofline_seconds(
        kernel_costs_latent.decode_flops(live, sizes),
        kernel_costs_latent.decode_bytes(live, slots, sizes),
        peak)[0] for live in run["trace"]["decode_calls"])
    return 100.0 * least / secs
