"""From a profiler trace to numbers.  The trace is reduced to a small plain
form first — {"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]} — which is also what the recorded traces
beside the tests hold, so that the arithmetic below is tested on what the
chip wrote.

Device planes are found by pattern (`/device:TPU:<n>`), never by index; a
chip's busy time is the union of the intervals of its op line(s), so that
nested or overlapping events are counted once; `busy_s` is the MEAN over
the chips, never a sum over planes."""

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line(s) of a device plane that hold one event per executed op; the
# other lines ("Steps", "XLA Modules", "XLA TraceMe", ...) span many ops
OP_LINES = ("XLA Ops",)
HOST_PLANE = re.compile(r"^/host:CPU$")
# a Pallas (Mosaic) kernel: the program's `pallas_call`s carry no name of
# their own yet, so a kernel is known by its target and by the program it
# runs in (PERF.md, Open questions: names for the tracing issue)
PALLAS_KERNEL = r"custom-call tpu_custom_call( |$)"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(full: str) -> str:
    """An op event is named by its whole HLO line.  Keep what identifies
    it: `%all-gather.3 all-gather`, `%fusion.716 fusion`, `%shard_map.5
    custom-call tpu_custom_call bf16[2,25,1024,64]` — the variable, the
    opcode, and for a custom call its target (a Pallas kernel's is
    `tpu_custom_call`) and the first array of its result, which says what
    share of the batch and the heads this chip's call computed."""
    if not full.startswith("%") or " = " not in full:
        return full[:120]
    var, rest = full.split(" = ", 1)
    op = _OPCODE.search(" " + rest)
    target = _TARGET.search(rest)
    shape = _SHAPE.search(rest) if target else None
    return " ".join(x for x in (var, op and op.group(1),
                                target and target.group(1),
                                shape and shape.group(0)) if x)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}: the "
                                f"profiler wrote no trace")
    return found[-1]


def load_xplane(path: str, keep_host=re.compile(r"^chipbench\.")) -> dict:
    """Device planes whole; of the host plane only this benchmark's own
    spans (`chipbench.*`), which is what keeps a four-chip trace small."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and not HOST_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name) if is_dev else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if is_dev or keep_host.match(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(trace: dict) -> list:
    out = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(out, key=lambda p: int(DEVICE_PLANE.match(p["name"])[1]))


def op_events(plane: dict) -> list:
    return [e for line in plane["lines"] if line["name"] in OP_LINES
            for e in line["events"]]


def union_ns(intervals) -> tuple:
    """(total ns covered, merged [start, end] list) of (start, dur) pairs."""
    merged = []
    for start, dur in sorted(intervals):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def self_times(events: list) -> list:
    """[(name, self ns)]: each event's duration minus what the events
    nested inside it cover (a scanned model's ops sit inside a `while`, a
    fusion's children inside the fusion), so that summing by name counts
    every nanosecond once and names the op that ran, not its container."""
    out, stack = [], []      # stack of [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out += [(n, s) for n, _, s in stack]
    return out


def op_seconds(trace: dict, pattern) -> list:
    """Per chip: seconds covered by ops whose name matches `pattern`."""
    pattern = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [union_ns((s, d) for n, s, d in op_events(plane)
                     if pattern.search(n))[0] / 1e9
            for plane in device_planes(trace)]


def op_shapes(trace: dict, pattern) -> list:
    """Per chip: the result shape (a tuple of ints) of every op whose name
    matches `pattern` and carries one, in trace order."""
    pattern = re.compile(pattern) if isinstance(pattern, str) else pattern
    out = []
    for plane in device_planes(trace):
        shapes = []
        for name, _, _ in op_events(plane):
            m = _SHAPE.search(name) if pattern.search(name) else None
            if m:
                dims = m.group(0)[m.group(0).index("[") + 1:-1]
                shapes.append(tuple(int(d) for d in dims.split(",") if d))
        out.append(shapes)
    return out


def op_count(trace: dict, pattern) -> list:
    pattern = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [sum(1 for n, _, _ in op_events(p) if pattern.search(n))
            for p in device_planes(trace)]


def busy(trace: dict, n_chips: int) -> dict:
    """{"busy_s": mean over chips of each chip's union-busy seconds,
    "per_chip_s": [...]}.  Raises where the trace lacks a device plane or an
    op line for any of the cell's chips: a silent 0 is the fault PR 22 was
    refused for."""
    planes = device_planes(trace)
    if len(planes) < n_chips:
        raise ValueError(
            f"the trace has {len(planes)} device planes "
            f"({[p['name'] for p in trace['planes']]}), the cell has "
            f"{n_chips} chips")
    per_chip = []
    for plane in planes[:n_chips]:
        evs = op_events(plane)
        if not evs:
            raise ValueError(
                f"plane {plane['name']} has no events on {OP_LINES}; its "
                f"lines are {[l['name'] for l in plane['lines']]}")
        per_chip.append(union_ns((s, d) for _, s, d in evs)[0] / 1e9)
    return {"busy_s": sum(per_chip) / len(per_chip), "per_chip_s": per_chip}


_FAMILY = re.compile(r"(?<=[A-Za-z_\-])\.\d+(?= |$)")


def breakdown(trace: dict, top: int = 10) -> dict:
    """The ten families of device ops that took most time (self times,
    summed by name without its number, mean over chips) and the ten host spans under which the first
    chip sat idle longest."""
    planes = device_planes(trace)
    by_name = {}
    for plane in planes:
        for name, self_ns in self_times(op_events(plane)):
            name = _FAMILY.sub("", name)   # %fusion.716 -> %fusion
            name = _SHAPE.sub("", name).strip()
            by_name[name] = by_name.get(name, 0.0) \
                + self_ns / 1e9 / len(planes)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

    gaps = {}
    if planes:
        _, merged = union_ns((s, d) for _, s, d in op_events(planes[0]))
        spans = sorted((s, s + d, n) for p in trace["planes"]
                       if HOST_PLANE.match(p["name"])
                       for line in p["lines"] for n, s, d in line["events"])
        for (_, a_end), (b_start, _) in zip(merged, merged[1:]):
            mid = (a_end + b_start) / 2
            # the innermost (shortest) host span over the gap's middle
            over = [(e - s, n) for s, e, n in spans if s <= mid <= e]
            name = min(over)[1] if over else "no_span"
            gaps[name] = gaps.get(name, 0.0) + (b_start - a_end) / 1e9
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}


def trim(trace: dict, max_events_per_line: int = 20000) -> dict:
    """A recorded trace small enough to keep beside the tests: the first
    events of every line."""
    return {**trace, "planes": [
        {"name": p["name"], "lines": [
            {"name": l["name"], "events": l["events"][:max_events_per_line]}
            for l in p["lines"]]} for p in trace["planes"]]}


def save_recorded(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))
