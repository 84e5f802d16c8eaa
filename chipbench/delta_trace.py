"""Where the delta-rule decode update is in a reduced device trace.

`trace_reduce.short_name` keeps a custom call's target and the first array
of its result, and a Pallas kernel is told from the others by that result
(`hybrid_trace.py`): the update gives a 4-D float32 array (the state leaf,
[slots, packs of heads, key, values]), the paged attention kernels 4-D
bfloat16 ones.  Only the kernel's time INSIDE an execution of the decode
program is taken (`programs.executions`, chip 0's `XLA Modules` line), as
`latent_trace.py` does: nothing the chunk-prefill program runs can be read
as the update.  The pattern is by rank and type, not by size, so that a
rehearsal's recorded trace (real sizes) is read by a tiny configuration."""

import bisect
import re

from chipbench import programs, trace_reduce

KERNEL = re.compile(r"custom-call tpu_custom_call f32\[\d+,\d+,\d+,\d+\]")
ATTENTION = re.compile(r"custom-call tpu_custom_call bf16\[\d+,\d+,\d+,\d+\]")


def kernel_seconds(run, kernel=KERNEL):
    """Chip 0's seconds in the update kernel (or another, told by its
    result) inside the decode program's executions in the traced part; None
    where there is no trace, no such program or no such kernel in it."""
    if not run.get("trace") or not run["trace"].get("trace"):
        return None
    trace = run["trace"]["trace"]
    runs = sorted(programs.executions(trace, programs.DECODE))
    planes = trace_reduce.device_planes(trace)
    if not runs or not planes:
        return None
    starts = [s for s, _ in runs]
    inside = []
    for name, start, dur in trace_reduce.op_events(planes[0]):
        if not kernel.search(name):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][0] + runs[i][1]:
            inside.append((start, dur))
    secs = trace_reduce.union_ns(inside)[0] / 1e9
    return secs if secs > 0 else None
