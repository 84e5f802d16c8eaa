"""The grouped-matmul kernel's share of its roofline in the traced part, at
LFM2-MoE's sizes (experts of 1,792, 16 held of 32, top 4): what the traced
programs NEEDED — the weights of the held experts HIT (22.0 MB each) and the
FLOPs of the (token, expert) pairs ROUTED (22.0 MFLOP each), as the program
counted them on the device and read back with each program's tokens, summed
over the 22 expert layers — at the chip's peaks, over the kernel's time in
the trace (`hybrid_trace.EXPERT_MATMUL`: both products, decode rounds and
chunk calls alike).  Decode rounds (bound by bytes) and chunk calls (bound
by FLOPs) are taken at their own bound; a sum's bound is at most the sum of
the calls' bounds, so the share errs low.  Granite's yardstick
(`expert_ffn_roofline`) at this configuration's keys."""

from chipbench import hybrid_trace, kernel_costs, kernel_costs_shortconv

META = {"layer": "kernels", "unit": "%", "moves": "token_gap_p95_ms",
        "source": "device_trace"}


def read(run):
    secs = hybrid_trace.seconds(run, hybrid_trace.EXPERT_MATMUL)
    if secs is None:
        return None
    sizes, n = run["sizes"], run["trace"]["counted"]
    if "moe_pairs_routed" not in n:
        return None
    peak = kernel_costs.peaks(run["device_kind"])
    least = 0.0
    for pre in ("moe_", "moe_prefill_"):
        pairs, hit = n[pre + "pairs_routed"], n[pre + "experts_hit"]
        least += kernel_costs.roofline_seconds(
            kernel_costs_shortconv.expert_ffn_flops(pairs, sizes),
            kernel_costs_shortconv.expert_ffn_bytes(pairs, hit, sizes),
            peak)[0]
    return 100.0 * least / secs if least > 0 else None
