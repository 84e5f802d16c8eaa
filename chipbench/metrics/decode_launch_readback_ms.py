"""Launch plus readback latency of a decode round: the time from the
program's being enqueued (the end of the `easydist.step.call` inside its
`.call`) to the end of that `.call` (the int32 result is on the host), less
the time the program ran on the device.  Each execution of the decode
program in the traced part (chip 0's `jit__decode_paged*` events,
`chipbench/programs.py`) is paired with the `.call` that ran it
(`session_timeline.paired_overhead_ms`) and the median difference taken: a
round's device time follows the live sequences, so the two must be of the
SAME rounds.  A chip run whose executions cannot be paired raises: the
difference of two medians read -0.4 and -1.1 ms there (PERF.md section 6),
and a number under this name is the paired one.

Under `--rehearse` the trace is a recording of another run and nothing can
be paired: the flight over the steady decode programs of the whole run less
`programs.median_ms(run, programs.DECODE)`, whatever that reads, so that
the line of a rehearsal has its names.

Logs both programs' readings on stderr (the chunk program's reads `-`
where a trace holds too few of its executions to pair), with the flight's
split at `ready_ns`: to `block_until_ready`'s return, and the copy out after
it."""

import sys

from chipbench import programs, session_timeline

META = {"layer": "emitted program", "unit": "ms",
        "moves": "token_gap_p95_ms", "source": "program_span"}

PROGRAMS = (("decode", session_timeline.DECODE_CALL, programs.DECODE),
            ("chunk", session_timeline.PREFILL_CALL, programs.PREFILL_CHUNK))


def _ms(value) -> str:
    return "-" if value is None else f"{value:.3f}"


def _read(run, records, name, which, label):
    """(launch and readback in ms, whether there was anything to read):
    None with something to read is a chip run that could not be paired."""
    flight = session_timeline.in_flight_ms(records, name)
    device_ms = programs.median_ms(run, which)
    if flight is None or device_ms is None:
        return None, False
    if run.get("rehearse"):
        value, how = flight[0] - device_ms, "a rehearsal: of two medians"
    else:
        value, how = session_timeline.paired_overhead_ms(
            records, name, programs.executions(run["trace"]["trace"], which)
        ), "paired with the traced executions"
    print(f"[chipbench] {label} program: launch and readback {_ms(value)} ms "
          f"({how}); in flight {flight[0]:.3f} ms over the run (to ready "
          f"{_ms(flight[1])}, ready to end {_ms(flight[2])}), on the device "
          f"{device_ms:.3f} in the traced part", file=sys.stderr, flush=True)
    return value, True


def read(run):
    snap = session_timeline.snapshot(run)
    if not snap:
        return None
    (decode, ran), _chunk = [_read(run, snap["spans"], name, which, label)
                             for label, name, which in PROGRAMS]
    if ran and decode is None:
        raise RuntimeError(
            "the traced executions of the decode program find no run of "
            f"`{session_timeline.DECODE_CALL}` records that enqueued them at "
            "a steady distance: launch and readback cannot be read")
    return decode
