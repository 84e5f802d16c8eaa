"""Device milliseconds of one chunk call of a model with state layers:
the median duration of chip 0's `XLA Modules` events of the program whose
jit name holds `prefill_chunk` (`jit__prefill_chunk_paged_state`;
`chipbench/programs.py`) in the traced part — the reading of
`prefill_chunk_device_ms`, which the contract's test keeps to one cell
(PERF.md section 7), on this cell's own trace (a rehearsal reads the
cell's recording)."""

import statistics

from chipbench import programs

META = {"layer": "emitted program", "unit": "ms",
        "moves": "token_gap_p95_ms", "source": "device_trace"}


def read(run):
    if not run.get("serve") or not run.get("trace"):
        return None
    durations = [d for _, d in programs.executions(
        run["trace"]["trace"], programs.PREFILL_CHUNK)]
    return statistics.median(durations) / 1e6 if durations else None
