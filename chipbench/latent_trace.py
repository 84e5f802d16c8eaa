"""Where the latent attention kernels are in a reduced device trace.

`trace_reduce.short_name` keeps a custom call's target and the first array
of its result, and a Pallas kernel is told from the others by that result
(`hybrid_trace.py`): the latent kernels give a 4-D bfloat16 array ([rows,
blocks of heads, heads x queries, values]), the grouped matmul of the
expert FFN a 2-D one.  Both latent kernels give such an array, so the two
are told apart by the PROGRAM they run in: `latent_decode` in an execution
of the decode program, `latent_chunk` in one of the chunk-prefill program
(`programs.executions`, chip 0's `XLA Modules` line).  The pattern is by
rank and type, not by size, so that a rehearsal's recorded trace (real
sizes) is read by a tiny configuration."""

import bisect
import re

from chipbench import programs, trace_reduce

KERNEL = r"custom-call tpu_custom_call bf16\[\d+,\d+,\d+,\d+\]"
WHICH = {"decode": programs.DECODE, "chunk": programs.PREFILL_CHUNK}


def kernel_seconds(run, which: str):
    """Chip 0's seconds in the latent kernel inside the executions of the
    `which` ("decode" | "chunk") program in the traced part; None where
    there is no trace, no such program or no such kernel in it."""
    if not run.get("trace") or not run["trace"].get("trace"):
        return None
    trace = run["trace"]["trace"]
    runs = sorted(programs.executions(trace, WHICH[which]))
    planes = trace_reduce.device_planes(trace)
    if not runs or not planes:
        return None
    starts = [s for s, _ in runs]
    pattern = re.compile(KERNEL)
    inside = []
    for name, start, dur in trace_reduce.op_events(planes[0]):
        if not pattern.search(name):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][0] + runs[i][1]:
            inside.append((start, dur))
    secs = trace_reduce.union_ns(inside)[0] / 1e9
    return secs if secs > 0 else None
