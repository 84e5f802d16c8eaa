"""How often XLA compiled a program (or loaded it from the persistent
cache) in a serving run: the sum of the program's counters
`xla_compiles{fn=*}` at the end of the run (`CompileResult.dispatch` counts
one whenever the jit's executable cache grew).  One a program is the floor;
a `fn` that reads 2 compiled again for the shardings its donated state came
back with.  Logs the count by `fn` on stderr."""

import sys

from chipbench import session_timeline

META = {"layer": "compile", "unit": "count", "moves": "setup_s",
        "source": "program_counter"}

COUNTER = "xla_compiles{fn="


def read(run):
    snap = session_timeline.snapshot(run)
    counts = {k: n for k, n in (snap["counters"] if snap else {}).items()
              if k.startswith(COUNTER)}
    if not counts:
        return None
    print(f"[chipbench] {counts}", file=sys.stderr, flush=True)
    return sum(counts.values())
