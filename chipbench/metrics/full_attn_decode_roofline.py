"""The paged decode kernel's share of its roofline on the FULL attention
layers of a model that also has sliding ones, in the traced part: the bytes
its calls need (`kernel_costs_window.full_decode_bytes`: every live K/V
token once per KV head a full layer, q and o) at the HBM peak — it is bound
by bytes — over its time in the trace.  The sliding layers' rings are read
by a fused dot, not by this kernel, so every Mosaic call with a 4-D
bfloat16 result ([rows, kv_heads, group, head_dim]) is a full layer's; the
live tokens of each traced round are counted by the runner from the tokens
it stamped."""

from chipbench import kernel_costs, kernel_costs_window, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}
KERNEL = r"custom-call tpu_custom_call bf16\[\d+,\d+,\d+,\d+\]"


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = trace_reduce.op_seconds(run["trace"]["trace"], KERNEL)
    if not secs or secs[0] <= 0:
        return None
    sizes = run["sizes"]
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = sum(kernel_costs.roofline_seconds(
        kernel_costs_window.full_decode_flops(live, sizes),
        kernel_costs_window.full_decode_bytes(live, slots, sizes),
        peak)[0] for live in run["trace"]["decode_calls"])
    return 100.0 * least / secs[0]
