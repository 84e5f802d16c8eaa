"""Where the LFM2-MoE cell's kernels are in a reduced device trace.

`trace_reduce.short_name` keeps a custom call's target and the first array
of its result, and a Pallas kernel is told from the others by that result
(`hybrid_trace.py`, `delta_trace.py`): the grouped products of the expert
FFN give a 2-D array (`hybrid_trace.EXPERT_MATMUL`), the paged attention
kernels a 4-D bfloat16 one ([rows, kv_heads, group (x chunk), head_dim]:
`delta_trace.ATTENTION`).  The decode kernel is told from the chunk kernel
by the PROGRAM whose execution it runs inside (`programs.executions`, chip
0's `XLA Modules` line).  The patterns are by rank and type, not by size, so
that a rehearsal's recorded trace (real sizes) is read by a tiny
configuration."""

from chipbench import programs, selective_trace
from chipbench.delta_trace import ATTENTION


def attention_seconds(run, which=programs.DECODE):
    """Chip 0's seconds in the paged attention kernel inside the executions
    of the program `which` in the traced part, or None."""
    return selective_trace.kernel_seconds(run, which, ATTENTION)
