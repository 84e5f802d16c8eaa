"""Device microseconds of the chunk-prefill program for each REAL prompt
position it carried through the delta-rule layers: the median duration of
`jit__prefill_chunk_paged_state` in the traced part over the mean real
positions of a traced call — the session's `delta_chunk_positions` (real
positions x delta-rule layers, counted on the host from the jobs' lengths)
over the layers and over the traced calls (`prefill_chunks`).  A call costs
the same whatever its row holds, so a row's padding reads here, and so does
whatever the chunked scans cost: their own seconds cannot be told from the
weights' products in a reduced trace (`trace_reduce.short_name` keeps no
scope), so this is the whole program's time, of which they are a part
(PERF.md section 5)."""

from chipbench import kernel_costs_delta
from chipbench.metrics.hybrid_prefill_chunk_device_ms import read as chunk_ms

META = {"layer": "emitted program", "unit": "us",
        "moves": "token_gap_p95_ms", "source": "device_trace"}


def read(run):
    counted = (run.get("trace") or {}).get("counted") or {}
    positions = counted.get("delta_chunk_positions")
    calls = counted.get("prefill_chunks")
    ms = chunk_ms(run)
    if not positions or not calls or ms is None:
        return None
    per_call = positions / kernel_costs_delta.state_layers(run["sizes"]) / calls
    return 1e3 * ms / per_call
