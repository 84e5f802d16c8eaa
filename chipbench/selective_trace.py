"""Where the two selective-state kernels are in a reduced device trace.

`trace_reduce.short_name` keeps a custom call's target and the first array
of its result, and a Pallas kernel is told from the others by that result
(`hybrid_trace.py`, `delta_trace.py`): both selective kernels give a 3-D
float32 array first (the decode update the state leaf, [slots, state,
inner]; the chunk scan y, [rows, positions, inner]), the paged attention
kernels 4-D bfloat16 ones.  The two are told from each other by the PROGRAM
whose execution they run inside (`programs.executions`, chip 0's `XLA
Modules` line): the update inside `jit__decode_paged_state`, the scan inside
`jit__prefill_chunk_paged_state`.  The pattern is by rank and type, not by
size, so that a rehearsal's recorded trace (real sizes) is read by a tiny
configuration."""

import bisect
import re

from chipbench import programs, trace_reduce
from chipbench.delta_trace import ATTENTION  # noqa: F401  (the same kernels)

KERNEL = re.compile(r"custom-call tpu_custom_call f32\[\d+,\d+,\d+\]")


def kernel_seconds(run, which=programs.DECODE, kernel=KERNEL):
    """Chip 0's seconds in the selective kernel (or another, told by its
    result) inside the executions of the program `which` in the traced
    part; None where there is no trace, no such program or no such kernel
    in it."""
    if not run.get("trace") or not run["trace"].get("trace"):
        return None
    trace = run["trace"]["trace"]
    runs = sorted(programs.executions(trace, which))
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
