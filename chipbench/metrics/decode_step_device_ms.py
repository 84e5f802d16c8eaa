"""Device milliseconds of one decode round: the median duration of chip
0's `XLA Modules` events of the decode program (`jit__decode_paged`, found by
its name: `chipbench/programs.py`) in the traced part of the run.  With 32
live slots this is the floor of every token gap.

Under `--rehearse` there is no device trace of the run's own, and the cell's
old recording (`recorded/serve-1chip.json.gz`) predates the names: the
reader reads `recorded/serve-1chip-named.json.gz`, the same cell's traced
part recorded on the v5e in PR 24."""

from chipbench import programs

META = {"layer": "emitted program", "unit": "ms",
        "moves": "token_gap_p95_ms", "source": "device_trace"}


def read(run):
    return programs.median_ms(run, programs.DECODE)
