"""Device milliseconds of one chunk-prefill call of LFM2-MoE: the median
duration of chip 0's `XLA Modules` events of
`jit__prefill_chunk_paged_state` in the traced part, on this cell's own
trace (a rehearsal reads the cell's recording) — the Granite cell's reading
(`hybrid_prefill_chunk_device_ms`) under this cell's name; a test keeps
`prefill_chunk_device_ms` to the Mistral cell."""

from chipbench.metrics.hybrid_prefill_chunk_device_ms import META, read  # noqa: F401
