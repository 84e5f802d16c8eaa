"""The routed expert FFN's share of the device's busy time in the traced
part of the LFM2-MoE cell: chip 0's seconds in the grouped-matmul kernel
(both products, decode rounds and chunk calls alike; found by its result,
`hybrid_trace.py`) over its busy seconds — the Granite cell's reading
(`expert_ffn_share_pct`) under this cell's name."""

from chipbench.metrics.expert_ffn_share_pct import META, read  # noqa: F401
