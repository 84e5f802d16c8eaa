"""Host milliseconds to dispatch one train step: the median duration of the
`easydist.step.call` spans of `train_step` that began after its last
`easydist.step.compile` (`easydist_tpu/runtime/spans.py`; opened in
`CompileResult.dispatch` round the jit's call, with no fence).  While the
device waits for the next step this is part of the gap between steps.

A program without the recorder (before PR 24) is measured from outside, in
the traced steps: from the start of the benchmark's `chipbench.train_step`
span to the start of the program it dispatched on chip 0."""

import statistics

from chipbench import programs

META = {"layer": "emitted program", "unit": "ms",
        "moves": "train_tokens_per_s_per_chip", "source": "program_span"}


def _from_spans(records):
    mine = [r for r in records if r["attrs"].get("fn") == "train_step"]
    warm_from = max((r["t1_ns"] for r in mine
                     if r["name"] == "easydist.step.compile"), default=0)
    calls = [(r["t1_ns"] - r["t0_ns"]) / 1e6 for r in mine
             if r["name"] == "easydist.step.call" and r["t0_ns"] >= warm_from]
    return statistics.median(calls) if calls else None


def _from_trace(trace):
    starts = sorted(s for _, s, _ in programs.module_events(trace))
    waits = []
    for start, dur in programs.host_spans(trace, "chipbench.train_step"):
        inside = [s for s in starts if start <= s < start + dur]
        if inside:
            waits.append((inside[0] - start) / 1e6)
    return statistics.median(waits) if waits else None


def read(run):
    if not run.get("train"):
        return None
    snap = programs.recorder_snapshot()
    value = _from_spans(snap["spans"]) if snap else None
    if value is None and run.get("trace"):
        value = _from_trace(run["trace"]["trace"])
    return value
