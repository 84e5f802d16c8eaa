"""The paged decode kernel's share of its roofline where ONE KV head serves
every query head (20 on 1 here), unlisted: the accepted
`paged_decode_roofline` takes every Mosaic call of the trace for the
kernel's and every layer for an attention layer, and this cell runs the
selective update beside it on thirteen layers in fourteen.  The same needed
bytes (`kernel_costs.paged_decode_bytes`: every live K/V token once a KV
head, q and o) of the traced rounds, on the ATTENTION layers alone, over the
seconds of Mosaic calls with a 4-D bfloat16 result inside the decode
program's executions (`selective_trace`)."""

from chipbench import kernel_costs, kernel_costs_selective, selective_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = selective_trace.kernel_seconds(
        run, kernel=selective_trace.ATTENTION)
    if secs is None:
        return None
    sizes = run["sizes"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim = sizes["hidden_size"] // heads
    attention = sizes["num_hidden_layers"] \
        - kernel_costs_selective.state_layers(sizes)
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = sum(kernel_costs.roofline_seconds(
        kernel_costs.paged_decode_flops(live, heads, head_dim),
        kernel_costs.paged_decode_bytes(live, slots, heads, kv, head_dim, 2),
        peak)[0] for live in run["trace"]["decode_calls"])
    return 100.0 * attention * least / secs
