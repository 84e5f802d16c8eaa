"""Device milliseconds of one decode round of LFM2-MoE: the median duration
of chip 0's `XLA Modules` events of `jit__decode_paged_state` in the traced
part, on this cell's own trace (a rehearsal reads the cell's recording) —
the Granite cell's reading (`hybrid_decode_step_device_ms`: by the program's
name, which holds `decode`) under this cell's name; a test keeps
`decode_step_device_ms` to the Mistral cell."""

from chipbench.metrics.hybrid_decode_step_device_ms import META, read  # noqa: F401
