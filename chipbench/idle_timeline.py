"""Every idle nanosecond of chip 0 in the traced part of a serving run, put
down to the phase of the host that owned it (PR 50): what the six readers
`idle_in_program_pct`, `idle_call_pct`, `idle_session_pct`,
`idle_caller_pct`, `idle_empty_pct` and `idle_unattributed_pct` take from
the program's span recorder (`easydist_tpu/runtime/spans.py`) laid over the
device trace.

*The join.*  The ring is on `time.perf_counter_ns()`, the trace on the
profiler's clock.  The runner wraps every `sess.step()` in exactly one
`chipbench.session_step` host event, so the traced steps are the run of as
many consecutive `easydist.serve.step` records whose starts keep the
steadiest distance to the events' (as `session_timeline.paired_overhead_ms`
finds its programs, but at the slack a wrapper allows: the middle nine
tenths of the pairs within 100 us, where a wrong run is off by whole steps
of milliseconds).  The event encloses the record: the offset of the two
clocks lies between (event start - record start) and (event end - record
end), and the median over the pairs of the middle of the two is taken; how
far the pairs spread is logged (5-8 us on the v5e), and no run within 100 us
is no join.  Nothing of the program's is needed for it, so the parent of
PR 50, which the driver runs under these readers, joins as the change does.

*The device's own clock.*  The host plane and the device plane of one trace
are not on one clock to the microsecond: on the v5e the device's events lie
1-2 ms EARLY in the first capture on a machine (an execution starts before
its `.call` opens) and within a millisecond either way later.  Every
execution is paired with its `.call` (its middle lies inside it, its name is
the `.call`'s `fn`, in order), and causality bounds the shift: an execution
starts no earlier than the `easydist.step.call` that dispatched it opened
and ends no later than `ready_ns`.  The range that every pair allows is
0.7-1.3 ms wide, as wide as what lies inside a `.call` before its execution
(the jit's dispatch, the launch) and after it (the wait to `ready_ns`, the
copy out) — so those are NOT told apart in a metric: a `.call`'s idle time
is one class, the same wherever in the range the device's events are put,
and its split is logged at both ends of the range and at the middle.

*The attribution, by duration.*  Chip 0's idle intervals are the complement
of the union of its `XLA Ops` events (`device_idle_pct.chat`'s definition).
Between the first traced step's start and the last one's end they are cut at
every boundary of a record and of an `XLA Modules` execution, and each piece
goes to exactly one class, the first that holds of it:

    in_program    inside an execution, between its ops
    call          inside a `.call`: `.dispatch` before its program is
                  enqueued (the end of the `easydist.step.call` inside it),
                  `.launch` from there to its execution's start,
                  `.readback.wait` from the execution's end to `ready_ns`,
                  `.readback.copy` after it — the four known only as far as
                  the range above
    empty         inside an `easydist.serve.empty` (a program before that
                  record has none: its emptiness reads as `caller`, and
                  stderr says so)
    session       inside any other record of the program: a step outside its
                  `.call`s, `submit`, `snapshot_inflight` (by the innermost
                  record's name on stderr)
    caller        outside every record: the loop that drives the session
    unattributed  a `.call` whose execution or whose `easydist.step.call`
                  was not found; and what is left of the window's idle time:
                  its edges before the first and after the last traced step.
                  All of it where the join fails, with the reason on stderr:
                  never a silent 0, never a raise

The six add up to `device_idle_pct.chat` of the same run by construction
(integer nanoseconds over one window).

Under `--rehearse` there is no trace of the run's own: the six read the
ONE recorded pair, `recorded/serve-1chip-joined.json.gz`, whatever the cell
(as `programs.median_ms` reads `serve-1chip-named.json.gz`).  It holds both
sides of one traced run of the Mistral cell on the v5e — `{"trace": chip 0's
`XLA Ops` and `XLA Modules` and the host plane's `chipbench.*` events,
"window_s", "spans": the recorder's ring}` — written by a scratch wrapper
round `chipbench.run.execute` (it keeps what `read_per_layer` is handed and
`spans.snapshot()`; `run.py` is not edited) and cut by TIME, not by a count
of events a line: the trace to the whole steps of one stretch of the capture
(about a second), `window_s` to that stretch, the ring to the records from a
second before it to a second after it.  (`README.md` does not list this
module, the six readers or the recording: a PR that is no `benchmark` PR
edits no file the benchmark has.)

A serving runner needs only what `runners/serve.py::_Loop.turn` does: one
`ctx.span("chipbench.session_step")` round each `sess.step()`."""

import bisect
import os
import statistics
import sys
import traceback

import numpy as np

from chipbench import programs, session_timeline, trace_reduce

STEP = session_timeline.STEP
DISPATCH = session_timeline.DISPATCH
EMPTY = "easydist.serve.empty"
STEP_EVENT = "chipbench.session_step"
JOIN_SLACK_NS = 100_000
MIN_PAIRS = 3
CLASSES = ("in_program", "call", "session", "caller", "empty", "unattributed")
RECORDED_JOINED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "recorded", "serve-1chip-joined.json.gz")


def log(msg: str) -> None:
    print(f"[chipbench] idle timeline: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the join

def step_events(trace: dict) -> list:
    """[(start_ns, duration_ns)] of the runner's wrapper round every
    `sess.step()`, in order."""
    return sorted(programs.host_spans(trace, STEP_EVENT))


def steadiest(steps: list, events: list):
    """The index of the first of the len(events) consecutive `steps` whose
    starts keep the steadiest distance to the events' (the middle nine
    tenths of the pairs within JOIN_SLACK_NS); None where no run does."""
    n = len(events)
    if n < MIN_PAIRS or len(steps) < n:
        return None
    t0 = np.array([s["t0_ns"] for s in steps], np.int64)
    lag = np.array([s for s, _ in events], np.int64)[None, :] \
        - np.lib.stride_tricks.sliding_window_view(t0, n)
    low, high = np.percentile(lag, (5, 95), axis=1)
    first = int(np.argmin(high - low))
    return first if high[first] - low[first] <= JOIN_SLACK_NS else None


def join(steps: list, events: list, say=log):
    """`steps`: `session_timeline.steps` of the whole ring; `events`:
    `step_events` of the trace.  {"steps": the traced steps (one an event,
    in order), "offset_ns": add to the recorder's clock to get the trace's,
    "spread_ns"}; None, with the reason said, where the traced steps cannot
    be found."""
    first = steadiest(steps, events)
    if first is None:
        say(f"no join: no run of {len(events)} steps among the ring's "
            f"{len(steps)} keeps within {JOIN_SLACK_NS / 1e3:.0f} us of the "
            f"`{STEP_EVENT}` events (fewer than {MIN_PAIRS} are none)")
        return None
    found = steps[first:first + len(events)]
    # the event encloses the record: the offset lies between the two
    mid = np.array([(es - s["t0_ns"] + es + ed - s["t1_ns"]) // 2
                    for s, (es, ed) in zip(found, events)], np.int64)
    low, high = np.percentile(mid, (5, 95))
    say(f"joined {len(found)} steps: offset {int(np.median(mid))} ns, spread "
        f"{(high - low) / 1e3:.1f} us over the middle nine tenths (all: "
        f"{(mid.max() - mid.min()) / 1e3:.1f} us)")
    return {"steps": found, "offset_ns": int(np.median(mid)),
            "spread_ns": int(high - low)}


# --------------------------------------------------- the records, by name

class _Lanes:
    """Records grouped by name, each name's sorted by start, for `at(t)`:
    the records that hold the instant t (at most one a name: the records of
    one name do not overlap on the session's thread)."""

    def __init__(self, records):
        by = {}
        for r in sorted(records, key=lambda r: r["t0_ns"]):
            by.setdefault(r["name"], []).append(r)
        self.lanes = {n: ([r["t0_ns"] for r in rs], rs)
                      for n, rs in by.items()}

    def at(self, t) -> list:
        out = []
        for starts, rs in self.lanes.values():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < rs[i]["t1_ns"]:
                out.append(rs[i])
        return out


def _is_call(name: str) -> bool:
    return name in (session_timeline.DECODE_CALL,
                    session_timeline.PREFILL_CALL)


def _module_fn(name: str) -> str:
    """`jit__decode_paged(8636...)` -> `_decode_paged`."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def pair_executions(calls: list, modules: list) -> None:
    """Give every `.call` its execution: `exec_ns` = (start, end) of the
    first `XLA Modules` event not yet taken whose MIDDLE lies inside the
    `.call` and whose name is the `.call`'s `fn` (any name among unnamed
    programs) — an execution lasts milliseconds and the two planes differ by
    under one, so the middle is inside whatever the shift."""
    mids = [s + d // 2 for _, s, d in modules]
    taken = set()
    for c in calls:
        c["exec_ns"] = None
        fn = c["attrs"].get("fn")
        i = bisect.bisect_left(mids, c["t0_ns"])
        while i < len(mids) and mids[i] < c["t1_ns"]:
            name, s, d = modules[i]
            if i not in taken and (programs.UNNAMED.match(name)
                                   or _module_fn(name) == fn):
                c["exec_ns"] = (s, s + d)
                taken.add(i)
                break
            i += 1


def shift_range(calls: list, say=log) -> tuple:
    """(low, high): the nanoseconds that may be ADDED to the device plane's
    events without breaking causality in any paired `.call` — no execution
    starts before the `easydist.step.call` that enqueued it opened (low) nor
    ends after `ready_ns` (high)."""
    paired = [c for c in calls if c["exec_ns"] and c["dispatch_ns"]]
    if not paired:
        return 0, 0
    low = max(c["dispatch_ns"][0] - c["exec_ns"][0] for c in paired)
    high = min(c["attrs"]["ready_ns"] - c["exec_ns"][1] for c in paired)
    say(f"causality over {len(paired)} paired executions puts the device "
        f"plane's events {low / 1e3:.1f} to {high / 1e3:.1f} us later"
        + (" (NO shift satisfies every pair: the classes are read at the "
           "middle all the same)" if low > high else ""))
    return int(low), int(high)


# --------------------------------------------------------- the attribution

def _label(t, in_a_program, lanes: _Lanes) -> str:
    if in_a_program(t):
        return "in_program"
    held = lanes.at(t)
    call = next((r for r in held if _is_call(r["name"])), None)
    if call is not None:
        if call["dispatch_ns"] is None:
            return "unattributed.no_dispatch"
        if t < call["dispatch_ns"][1]:
            return "call.dispatch"
        if call["exec_ns"] is None:
            return "unattributed.no_execution"
        if t < call["exec_ns"][0]:
            return "call.launch"
        return "call.readback.wait" if t < call["attrs"]["ready_ns"] \
            else "call.readback.copy"
    if any(r["name"] == EMPTY for r in held):
        return "empty"
    if held:
        inner = min(held, key=lambda r: r["t1_ns"] - r["t0_ns"])
        return "session." + inner["name"].replace("easydist.", "", 1)
    return "caller"


class _Busy:
    """The union of chip 0's op events, moved by `shift`; `before(x)`: busy
    ns before x."""

    def __init__(self, trace):
        planes = trace_reduce.device_planes(trace)
        self.total, merged = trace_reduce.union_ns(
            (s, d) for _, s, d in trace_reduce.op_events(planes[0]))
        m = np.array(merged, np.int64).reshape(-1, 2)
        self.starts, self.ends = m[:, 0], m[:, 1]
        self.cum = np.concatenate([[0], np.cumsum(self.ends - self.starts)])
        self.shift = 0

    def before(self, x):
        x = np.asarray(x, np.int64) - self.shift
        if not len(self.starts):
            return np.zeros_like(x)
        i = np.searchsorted(self.starts, x, side="right") - 1
        j = np.maximum(i, 0)
        part = np.clip(x - self.starts[j], 0, self.ends[j] - self.starts[j])
        return np.where(i >= 0, self.cum[j] + part, 0)


def _on_the_traces_clock(r: dict, offset: int) -> dict:
    out = {**r, "t0_ns": r["t0_ns"] + offset, "t1_ns": r["t1_ns"] + offset}
    if r["attrs"].get("ready_ns") is not None:
        out["attrs"] = {**r["attrs"],
                        "ready_ns": r["attrs"]["ready_ns"] + offset}
    return out


def _sum(labels, idle, i=0, j=None) -> dict:
    out = {}
    for label, ns in zip(labels[i:j], idle[i:j]):
        if ns:
            out[label] = out.get(label, 0) + int(ns)
    return out


def _split(shift, a, b, near, calls, modules, busy):
    """(cuts, idle, labels) of [a, b) with the device plane's events moved
    by `shift`: the instants where an owner may change, the idle ns of each
    piece between two of them, and its label (None where it has none)."""
    busy.shift = shift
    _, programs_run = trace_reduce.union_ns(
        (s + shift, d) for _, s, d in modules)
    program_starts = [s for s, _ in programs_run]
    moved = [{**c, "exec_ns": c["exec_ns"] and (c["exec_ns"][0] + shift,
                                                c["exec_ns"][1] + shift)}
             for c in calls]

    def in_a_program(t):
        i = bisect.bisect_right(program_starts, t) - 1
        return i >= 0 and t < programs_run[i][1]

    cuts = {a, b}
    for r in near:
        cuts.update((r["t0_ns"], r["t1_ns"]))
    for c in moved:
        cuts.update(c["exec_ns"] or ())
        cuts.update(c["dispatch_ns"] or ())
        cuts.add(c["attrs"]["ready_ns"])
    for s, e in programs_run:
        cuts.update((s, e))
    cuts = np.array(sorted(t for t in cuts if a <= t <= b), np.int64)
    idle = np.diff(cuts) - np.diff(busy.before(cuts))
    lanes = _Lanes([r for r in near if not _is_call(r["name"])] + moved)
    labels = [_label((int(lo) + int(hi)) // 2, in_a_program, lanes)
              if ns else None
              for lo, hi, ns in zip(cuts[:-1], cuts[1:], idle)]
    return cuts, idle, labels


def attribute(records: list, trace: dict, window_s: float, say=log) -> dict:
    """{"ns": {label: idle ns}, "window_ns", "idle_ns", "joined": `join`'s
    result or None, "shift_range_ns", "p95": {label: ns} inside the slowest
    twentieth of the traced steps, "p95_ns": their durations' sum,
    "capture_overhead_ms"}."""
    busy = _Busy(trace)
    window_ns = int(round(window_s * 1e9))
    out = {"ns": {}, "window_ns": window_ns,
           "idle_ns": window_ns - busy.total, "joined": None,
           "shift_range_ns": (0, 0), "p95": {}, "p95_ns": 0,
           "capture_overhead_ms": None}
    steps = session_timeline.steps(records)
    joined = out["joined"] = join(steps, step_events(trace), say)
    if joined is None:
        say("nothing can be attributed: all of the idle time is "
            "`idle_unattributed_pct`")
        out["ns"]["unattributed.no_join"] = out["idle_ns"]
        return out
    out["capture_overhead_ms"] = capture_overhead_ms(steps, joined["steps"])
    if not any(r["name"] == EMPTY for r in records):
        say(f"no `{EMPTY}` record in the ring (a program before PR 50): the "
            f"chip's idle time while the session was empty reads as "
            f"`idle_caller_pct`, `idle_empty_pct` as 0")
    # everything onto the trace's host clock
    offset = joined["offset_ns"]
    a = joined["steps"][0]["t0_ns"] + offset
    b = joined["steps"][-1]["t1_ns"] + offset
    near = [_on_the_traces_clock(r, offset) for r in records]
    near = [r for r in near if r["t1_ns"] > a and r["t0_ns"] < b]
    dispatch = {}
    for r in near:
        if r["name"] == DISPATCH:
            dispatch.setdefault(r["parent_id"], (r["t0_ns"], r["t1_ns"]))
    calls = sorted((r for r in near if _is_call(r["name"])),
                   key=lambda r: r["t0_ns"])
    for c in calls:
        c["dispatch_ns"] = dispatch.get(c["id"])
    modules = sorted(programs.module_events(trace), key=lambda e: e[1])
    pair_executions(calls, modules)
    for what, key in (("execution of their `fn` on the device", "exec_ns"),
                      ("`easydist.step.call` inside them", "dispatch_ns")):
        lost = sum(c[key] is None for c in calls)
        if lost:
            say(f"{lost} of {len(calls)} `.call`s found no {what}: their "
                f"idle time is unattributed")
    low, high = out["shift_range_ns"] = shift_range(calls, say)

    def split(shift):
        return _split(shift, a, b, near, calls, modules, busy)

    cuts, idle, labels = split((low + high) // 2)
    out["ns"] = _sum(labels, idle)
    out["ns"]["unattributed.edges"] = out["idle_ns"] - int(idle.sum())
    outside = busy.total - int(np.diff(busy.before(cuts[[0, -1]]))[0])
    say(f"the {len(joined['steps'])} traced steps span {(b - a) / 1e9:.4f} s "
        f"of the window's {window_s:.4f}; the chip is busy "
        f"{outside / 1e6:.3f} ms outside them")
    # a `.call`'s idle time is one class: its parts depend on the shift
    ends = [out["ns"]]
    if low < high:
        for shift in (low, high):
            _, idle_there, labels_there = split(shift)
            ends.append(_sum(labels_there, idle_there))
    parts = sorted({k for e in ends for k in e if k.startswith("call.")})
    say("inside the `.call`s, % of the window with the device's events at "
        "the middle" + (" | the low end | the high end" if low < high else "")
        + " of that range: " + ", ".join(
            f"{k[5:]} " + " | ".join(
                f"{100.0 * e.get(k, 0) / window_ns:.3f}" for e in ends)
            for k in parts)
        + "; together " + " | ".join(
            f"{100.0 * by_class(e)['call'] / window_ns:.3f}" for e in ends))

    # the same split inside the slowest twentieth of the traced steps
    slow = sorted(joined["steps"], key=lambda s: s["t0_ns"] - s["t1_ns"])
    for s in slow[:max(1, len(slow) // 20)]:
        i, j = np.searchsorted(
            cuts, (s["t0_ns"] + offset, s["t1_ns"] + offset))
        for label, ns in _sum(labels, idle, i, j).items():
            out["p95"][label] = out["p95"].get(label, 0) + ns
        out["p95_ns"] += s["t1_ns"] - s["t0_ns"]
    return out


def by_class(ns: dict) -> dict:
    """{class: ns} of {label: ns}: a label's class is what precedes its
    first dot."""
    out = dict.fromkeys(CLASSES, 0)
    for label, value in ns.items():
        out[label.split(".", 1)[0]] += value
    return out


def capture_overhead_ms(steps: list, traced: list):
    """What a capturing profiler costs the host a step, from the ring alone:
    the median host share (duration less its `.call`s:
    `session_host_ms_per_step`'s definition) of the `traced` steps that ran
    a round, less the same over the steady ones of `steps` outside the
    capture; None where either set is empty."""
    inside = {s["t0_ns"] for s in traced}

    def host_ms(s):
        return (s["t1_ns"] - s["t0_ns"] - sum(
            c["t1_ns"] - c["t0_ns"] for c in s["calls"])) / 1e6

    on, off = [], []
    for s in steps:
        if session_timeline.ran_a_round(s):
            if s["t0_ns"] in inside:
                on.append(host_ms(s))
            elif s["steady"]:
                off.append(host_ms(s))
    if not on or not off:
        return None
    return statistics.median(on) - statistics.median(off)


# ----------------------------------------------------------- the readers

def _inputs(run):
    """(records, reduced trace, window_s) of a traced serving run; of the
    recorded pair under `--rehearse`; None for any other run."""
    if not run.get("serve") or not run.get("trace"):
        return None
    if run.get("rehearse"):
        rec = trace_reduce.load_recorded(RECORDED_JOINED)
        return rec["spans"], rec["trace"], rec["window_s"]
    snap = programs.recorder_snapshot()
    if not snap:
        return None
    return snap["spans"], run["trace"]["trace"], run["trace"]["window_s"]


def _pct_line(ns: dict, whole: int) -> str:
    return ", ".join(f"{k} {100.0 * v / whole:.3f}"
                     for k, v in sorted(ns.items(), key=lambda kv: -kv[1])
                     if v)


def shares(run):
    """{class: % of the traced window} of this run, computed once a run and
    logged; None where there is nothing to read."""
    if "_idle_timeline" in run:
        return run["_idle_timeline"]
    found = _inputs(run)
    if found is None:
        run["_idle_timeline"] = None
        return None
    records, trace, window_s = found
    try:
        res = attribute(records, trace, window_s)
    except Exception:   # the parent's run must not fail on a new reader
        log("the attribution raised; all of the idle time is "
            "`idle_unattributed_pct`:\n" + traceback.format_exc())
        window_ns = int(round(window_s * 1e9))
        res = {"ns": {"unattributed.raised":
                      window_ns - _Busy(trace).total},
               "window_ns": window_ns, "joined": None}
    whole = res["window_ns"]
    out = {k: 100.0 * v / whole for k, v in by_class(res["ns"]).items()}
    log(f"% of the traced {whole / 1e9:.3f} s by label: "
        f"{_pct_line(res['ns'], whole)}; sum {sum(out.values()):.4f}")
    if res["joined"]:
        if res["p95_ns"]:
            log(f"the slowest twentieth of the traced steps "
                f"({res['p95_ns'] / 1e6:.1f} ms), % of their own time: "
                f"{_pct_line(res['p95'], res['p95_ns'])}")
        over = res["capture_overhead_ms"]
        log("capture_host_overhead_ms_per_step: no steady step outside the "
            "capture to compare with" if over is None else
            f"not reported: capture_host_overhead_ms_per_step = {over:.4f}")
    run["_idle_timeline"] = out
    return out


def share(run, which: str):
    out = shares(run)
    return None if out is None else out[which]
