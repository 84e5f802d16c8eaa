"""The latent chunk kernel's share of the chip's peak in the traced part, in
the MODEL's own operations: the attention a chunk of queries asks for — 64
heads x (192 + 128) multiply-adds a (real query, visible key) pair a layer,
the pairs counted by the session on the host (`prefill_attn_pairs`) — at
the bf16 peak, over the kernel's time INSIDE the chunk-prefill program's
executions (`latent_trace`).  The same work whichever form computes it: the
absorbed kernel multiplies 576 + 512 wide where the model says 192 + 128,
so it reads at most 29 %; it is bound by operations (about a thousand a
byte of page read)."""

from chipbench import kernel_costs, kernel_costs_latent, latent_trace

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    counted = (run.get("trace") or {}).get("counted") or {}
    pairs = counted.get("prefill_attn_pairs")
    secs = latent_trace.kernel_seconds(run, "chunk")
    if not pairs or secs is None:
        return None
    sizes = run["sizes"]
    peak = kernel_costs.peaks(run["device_kind"])
    flops = sizes["num_hidden_layers"] \
        * kernel_costs_latent.chunk_model_flops(pairs, sizes)
    return 100.0 * flops / peak["bf16_flops_per_s"] / secs
