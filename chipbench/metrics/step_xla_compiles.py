"""How often XLA compiled the train step (or loaded it from the persistent
cache) in this run: the program's counter `xla_compiles{fn=train_step}`
(`easydist_tpu/runtime/spans.py`, counted in `CompileResult.dispatch` when
the jit's executable cache grows).  2 while the donated state comes back
under other shardings than it was born with; 1 once it keeps them.

A program without the counter (before PR 24) is read from outside: the
first steps that took at least a second longer than the median step."""

from chipbench import programs

META = {"layer": "compile", "unit": "count", "moves": "setup_s",
        "source": "program_counter"}

COUNTER = "xla_compiles{fn=train_step}"


def read(run):
    t = run.get("train")
    if not t:
        return None
    snap = programs.recorder_snapshot()
    n = snap["counters"].get(COUNTER) if snap else None
    if n is None:
        n = sum(s - t["median_step_s"] >= 1.0 for s in t["first_steps_s"])
    return n
