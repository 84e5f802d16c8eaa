"""The flash-attention forward and backward kernels' share of their
roofline in the traced training steps, per chip, mean over the chips: the
FLOPs the calls that chip executed need (`kernel_costs.causal_attention_flops`:
the causal half once, no recomputation) at the bf16 peak, over that chip's
kernel time in the trace.  What a call computed is read from its result
shape in the trace ([rows x heads, positions, head_dim]: the plan decides
what share of the batch and the heads reaches each chip — today all of it,
emission binds the kernel whole on every chip); a layer's forward and
backward are three calls (forward, dq, dk/dv) that together need the
forward's and the backward's FLOPs once."""

import math

from chipbench import kernel_costs, trace_reduce

META = {"layer": "kernels", "unit": "%",
        "moves": "train_tokens_per_s_per_chip", "source": "device_trace"}
# the traced training steps run three Pallas kernels and no other: the flash
# forward, and the backward's dq and dk/dv kernels (ops/flash_attention.py)
KERNEL = trace_reduce.PALLAS_KERNEL
CALLS_PER_LAYER = 3


def read(run):
    if not run.get("trace") or not run.get("train"):
        return None
    trace = run["trace"]["trace"]
    peak = kernel_costs.peaks(run["device_kind"])["bf16_flops_per_s"]
    shares = []
    for secs, shapes in zip(trace_reduce.op_seconds(trace, KERNEL),
                            trace_reduce.op_shapes(trace, KERNEL)):
        shapes = [s for s in shapes if len(s) >= 3]
        if secs <= 0 or not shapes:
            continue
        flops = sum(
            kernel_costs.causal_attention_flops(1, math.prod(lead), t, d)
            + kernel_costs.causal_attention_flops(1, math.prod(lead), t, d,
                                                  backward=True)
            for *lead, t, d in shapes) / CALLS_PER_LAYER
        shares.append(flops / peak / secs)
    return 100.0 * sum(shares) / len(shares) if shares else None
