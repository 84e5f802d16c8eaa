"""The paged decode kernel's share of its roofline at heads of SIXTY-FOUR
on a lane-dense arena: what the traced rounds need — every live K and V row
once a KV head (8 heads of 64: 2,048 B a token a layer), q and o
(`kernel_costs.paged_decode_bytes`, and the FLOPs beside them: bytes bind),
on the SIX attention layers — at the HBM peak, over the seconds of Mosaic
calls with a 4-D bfloat16 result INSIDE the decode program's executions
(`shortconv_trace`): the path this configuration changed, not the chunk
kernel's time and not the expert layer's."""

from chipbench import kernel_costs, kernel_costs_shortconv, shortconv_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = shortconv_trace.attention_seconds(run)
    if secs is None:
        return None
    sizes = run["sizes"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = kernel_costs_shortconv.head_dim(sizes)
    attention = kernel_costs_shortconv.layers(sizes, "full_attention")
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = sum(kernel_costs.roofline_seconds(
        kernel_costs.paged_decode_flops(live, heads, hd),
        kernel_costs.paged_decode_bytes(live, slots, heads, kv, hd, 2),
        peak)[0] for live in run["trace"]["decode_calls"])
    return 100.0 * attention * least / secs
