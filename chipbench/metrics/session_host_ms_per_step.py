"""The host's share of a serving step, with the device idle: the median,
over every `easydist.serve.step` span that ran a decode round, of the span's
duration less what its `.call` descendants cover (the intervals in which a
compiled program is dispatched and its readback awaited).  What is left is
admission, table build and uploads, the slot walk, retirement and its
audits, the pool's occupancy count.  Read from the program's span recorder
(`easydist_tpu/runtime/spans.py`) over the whole run.

A program without the recorder (before PR 24) is measured from outside, in
the traced part only: the benchmark's `chipbench.session_step` span less the
time chip 0 spent in programs inside it, which also counts each dispatch's
and readback's latency as the host's."""

import statistics

from chipbench import programs, trace_reduce

META = {"layer": "session", "unit": "ms", "moves": "token_gap_p95_ms",
        "source": "program_span"}


def _from_spans(records):
    children = {}
    for r in records:
        children.setdefault(r["parent_id"], []).append(r)
    host_ms = []
    for step in records:
        if step["name"] != "easydist.serve.step":
            continue
        inside, todo = [], list(children.get(step["id"], []))
        while todo:
            r = todo.pop()
            inside.append(r)
            todo += children.get(r["id"], [])
        if not any(r["name"] == "easydist.serve.decode.call"
                   for r in inside):
            continue
        covered, _ = trace_reduce.union_ns(
            (r["t0_ns"], r["t1_ns"] - r["t0_ns"]) for r in inside
            if r["name"].endswith(".call"))
        host_ms.append((step["t1_ns"] - step["t0_ns"] - covered) / 1e6)
    return statistics.median(host_ms) if host_ms else None


def _from_trace(trace):
    decode = programs.executions(trace, programs.DECODE)
    every = [(s, d) for _, s, d in programs.module_events(trace)]
    host_ms = []
    for start, dur in programs.host_spans(trace, "chipbench.session_step"):
        if not any(start <= s < start + dur for s, _ in decode):
            continue
        busy, _ = trace_reduce.union_ns(
            (s, min(d, start + dur - s)) for s, d in every
            if start <= s < start + dur)
        host_ms.append((dur - busy) / 1e6)
    return statistics.median(host_ms) if host_ms else None


def read(run):
    if not run.get("serve"):
        return None
    snap = programs.recorder_snapshot()
    value = _from_spans(snap["spans"]) if snap else None
    if value is None and run.get("trace"):
        value = _from_trace(run["trace"]["trace"])
    return value
