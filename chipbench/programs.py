"""What the per-layer readers take from inside the program (PR 24): its
jitted programs in a device trace, and its span recorder.

Chip 0's `XLA Modules` line has one event per execution of a program, named
by its jit — `jit__decode_paged(<hash>)`, `jit__prefill_chunk_paged(<hash>)`,
since the program names its jits after the functions they wrap.

A program built before that names every one `jit_tree_fn(<hash>)`.  There
the two programs a serving cell runs are told apart by what the names were
read from by hand until then (PERF.md section 3): the decode program runs
the Pallas decode kernel, the chunk-prefill program runs none."""

import bisect
import os
import re
import statistics

from chipbench import trace_reduce

MODULE_LINE = "XLA Modules"
DECODE = re.compile(r"decode")
PREFILL_CHUNK = re.compile(r"prefill_chunk")
UNNAMED = re.compile(r"^jit_tree_fn\(")
# the chat cell's traced part, recorded on the v5e after the names landed:
# what the rehearsals (no chip, no device trace of their own) read
RECORDED_NAMED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "recorded", "serve-1chip-named.json.gz")


def module_events(trace: dict) -> list:
    """[(name, start_ns, duration_ns)] of chip 0's program executions."""
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return []
    return [tuple(e) for line in planes[0]["lines"]
            if line["name"] == MODULE_LINE for e in line["events"]]


def _runs_a_kernel(trace: dict, events: list) -> dict:
    """{module name: whether a Pallas kernel started inside one of its
    executions} — for programs without names of their own."""
    starts = sorted(s for n, s, _ in trace_reduce.op_events(
        trace_reduce.device_planes(trace)[0])
        if re.search(trace_reduce.PALLAS_KERNEL, n))
    out = {}
    for name, start, dur in events:
        i = bisect.bisect_left(starts, start)
        inside = i < len(starts) and starts[i] < start + dur
        out[name] = out.get(name, False) or inside
    return out


def executions(trace: dict, which) -> list:
    """[(start_ns, duration_ns)] of the program `which` (DECODE or
    PREFILL_CHUNK) on chip 0, by its name; among unnamed programs, by the
    kernel."""
    events = module_events(trace)
    named = [(s, d) for n, s, d in events if which.search(n)]
    if named or not any(UNNAMED.match(n) for n, _, _ in events):
        return named
    has_kernel = _runs_a_kernel(trace, events)
    return [(s, d) for n, s, d in events
            if UNNAMED.match(n) and has_kernel[n] == (which is DECODE)]


def host_spans(trace: dict, name: str) -> list:
    """[(start_ns, duration_ns)] of the host plane's spans called `name`."""
    return [(s, d) for plane in trace["planes"]
            if trace_reduce.HOST_PLANE.match(plane["name"])
            for line in plane["lines"] for n, s, d in line["events"]
            if n == name]


def recorder_snapshot():
    """`easydist_tpu.runtime.spans.snapshot()`, or None for a program that
    has no recorder (before PR 24): the readers then measure from outside."""
    try:
        from easydist_tpu.runtime import spans
    except ImportError:
        return None
    return spans.snapshot()


def median_ms(run: dict, which):
    """Median device milliseconds of one execution of `which` in the traced
    part of a serving run; None where the program did not run there."""
    if not run.get("serve") or not run.get("trace"):
        return None
    trace = trace_reduce.load_recorded(RECORDED_NAMED) \
        if run.get("rehearse") else run["trace"]["trace"]
    durations = [d for _, d in executions(trace, which)]
    return statistics.median(durations) / 1e6 if durations else None
