"""Where the Granite 4.0-H kernels are in a reduced device trace.

`trace_reduce.short_name` keeps a custom call's target and the first array
of its result; emission names the call's variable after the PROGRAM, not
the kernel, so a Pallas kernel is told from the others by that result: the
grouped matmul of the expert FFN gives a 2-D bfloat16 array (rows x width),
the state update a 4-D float32 one (the state leaf, [slots, heads, d_head,
d_state]), the paged decode kernel a 4-D bfloat16 one ([batch, kv_heads,
group, head_dim]).  The patterns are by rank and type, not by size, so that
a rehearsal's recorded trace (real sizes) is read by a tiny configuration."""

from chipbench import trace_reduce

EXPERT_MATMUL = r"custom-call tpu_custom_call bf16\[\d+,\d+\]"
STATE_UPDATE = r"custom-call tpu_custom_call f32\[\d+,\d+,\d+,\d+\]"


def seconds(run, pattern):
    """Chip 0's seconds in ops matching `pattern` in the traced part; None
    where there is no trace or no such op."""
    if not run.get("trace") or not run["trace"].get("counted"):
        return None
    secs = trace_reduce.op_seconds(run["trace"]["trace"], pattern)
    return secs[0] if secs and secs[0] > 0 else None


def state_layers(sizes) -> int:
    return sum(t == "mamba" for t in
               sizes["layer_types"][:sizes["num_hidden_layers"]])
