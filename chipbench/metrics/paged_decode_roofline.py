"""The paged decode kernel's share of its roofline in the traced part of a
serving run: the bytes its calls need (`kernel_costs.paged_decode_bytes`:
every live K/V token once per KV head, q and o) at the HBM peak — it is
bound by bytes, one multiply-add per byte read — over its time in the
trace.  The live tokens of each traced decode round are counted by the
runner from the tokens it stamped."""

from chipbench import kernel_costs, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}
# the decode program runs one Pallas kernel and the chunk-prefill program
# none: every Mosaic custom call of a serving trace is the paged decode
# kernel (ops/flash_attention.py::flash_paged_decode_attention)
KERNEL = trace_reduce.PALLAS_KERNEL


def read(run):
    if not run.get("trace") or not run["trace"].get("decode_calls"):
        return None
    secs = trace_reduce.op_seconds(run["trace"]["trace"], KERNEL)[0]
    if secs <= 0:
        return None
    sizes = run["sizes"]
    slots = run["cell"]["serve_config"]["max_decode_slots"]
    peak = kernel_costs.peaks(run["device_kind"])
    least = 0.0
    for live_tokens in run["trace"]["decode_calls"]:
        nbytes = kernel_costs.paged_decode_bytes(
            live_tokens, slots, sizes["num_attention_heads"],
            sizes["num_key_value_heads"], sizes["head_dim"], 2)
        flops = kernel_costs.paged_decode_flops(
            live_tokens, sizes["num_attention_heads"], sizes["head_dim"])
        least += sizes["num_hidden_layers"] * kernel_costs.roofline_seconds(
            flops, nbytes, peak)[0]
    return 100.0 * least / secs
