"""The paged decode kernel's share of its roofline where every KV head
serves ONE query head (30 of 30 here), unlisted: the accepted
`paged_decode_roofline` takes every Mosaic call of the trace for the
kernel's and every layer for a full one, and this cell runs the delta-rule
update beside it on three layers in four.  The same needed bytes
(`kernel_costs.paged_decode_bytes`: every live K/V token once a KV head, q
and o) of the traced rounds, on the FULL layers alone, over the seconds of
Mosaic calls with a 4-D bfloat16 result inside the decode program's
executions (`delta_trace`)."""

from chipbench import delta_trace, kernel_costs

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = delta_trace.kernel_seconds(run, delta_trace.ATTENTION)
    if secs is None:
        return None
    sizes = run["sizes"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim = sizes["hidden_size"] // heads
    full = sum(t == "full_attention" for t in
               sizes["layer_types"][:sizes["num_hidden_layers"]])
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = sum(kernel_costs.roofline_seconds(
        kernel_costs.paged_decode_flops(live, heads, head_dim),
        kernel_costs.paged_decode_bytes(live, slots, heads, kv, head_dim, 2),
        peak)[0] for live in run["trace"]["decode_calls"])
    return 100.0 * full * least / secs
