"""Device milliseconds of one decode round of a model with window layers: the
median duration of chip 0's `XLA Modules` events of `jit__decode_paged_state`
in the traced part, on this cell's own trace (a rehearsal reads the cell's
recording) — the Granite cell's reading (`hybrid_decode_step_device_ms`: the
session's two programs of a model that keeps slots carry the same names)
under this cell's name."""

from chipbench.metrics.hybrid_decode_step_device_ms import META, read  # noqa: F401
