"""The program's one span recorder: host spans, counters, request timelines.

`span(name, **attrs)` is a context manager that (a) enters
`jax.profiler.TraceAnnotation(name, **attrs)`, so that whenever a
`jax.profiler` session is capturing the span lands in the trace's host
plane, on the device trace's clock, and (b) appends one record to a
process-wide bounded ring.  Always on: no switch, no exporter, no thread, no
file.  With no profiler session a span costs two clock reads and a deque
append (`TraceMe` is inert when nothing captures).  `attrs` hold only values
already at hand (a count, an index, a name): never a walk over tokens, never
a device read.

Every time is `time.perf_counter_ns()`.  `snapshot()` gives plain lists and
dicts; the rings drop their oldest entries when full, and `clear()` empties
them (tests, or a benchmark between phases).

The ring and a trace are joined through a span seen on both sides.  While
a `jax.profiler` session captures, a span is an event of its name in the
trace's host plane, its attrs at entry among the event's stats, and a
record of the ring: an `easydist.serve.step` event whose `step` is n IS the
record whose `step` is n, and (event start - record `t0_ns`) is the constant
between the profiler's clock and `perf_counter_ns`.  With it every record
of the ring, from before the capture and after it too, lies on the device's
timeline.  `chipbench/idle_timeline.py` does that (through the wrapper its
runner opens round each `step()`, the median over all pairs) and puts each
idle interval of the chip down to the record that owned it: the six
`idle_*_pct` of `BENCHMARK.json`.

The names, each opened in one place (`PERF.md` section 3 says which metric
reads which):

    easydist.compile.trace | .discovery | .solve | .emit
        jaxfront/api.py::compile_step; `CompileResult.phase_seconds` is
        filled from the first three
    easydist.step.call            attrs: fn
        jaxfront/api.py::CompileResult.dispatch, round every call of a
        compiled step (no fence: the device may still be running when it
        ends)
    easydist.step.compile         attrs: fn
        the interval of a dispatch that made XLA compile the step, or load
        it from the persistent cache; counted as `xla_compiles{fn=...}`
    easydist.serve.step           attrs: step, live, queued, empty_ns
    easydist.serve.empty
        one record (`record_span`, no parent) an interval in which the
        session had nothing live and nothing queued: written when a
        `submit()` ends it, and up to the start of a `step()` that finds the
        session still empty.  The records lie between steps and add up to
        the steps' `empty_ns`; `idle_empty_pct` is the chip's idle time
        inside them.
    easydist.serve.admit          attrs: admitted, deferred
    easydist.serve.prefill.build | .call | .finish
    easydist.serve.decode.build | .call | .harvest
        serve/generation.py::GenerationSession.step and what it calls; a
        `.call` runs from the dispatch of one compiled program to the
        return of its readback, so a step's duration less its `.call`
        descendants is the host's share of the step, with the device idle;
        `.finish` carries its request's `request_id`.  `empty_ns` is the
        part of the time since the previous step's end in which the
        session had nothing live and nothing queued (a session that
        emptied and was given work again between two steps: no other
        record shows it), so the first program of a step whose `empty_ns`
        is above 0 follows emptiness, not the host's work.
        Every `.call` (`GenerationSession._run`) also carries `ready_ns`:
        `block_until_ready` returned, before the result is copied out.
        The rest of a program's cycle is in the records as they are: the
        `easydist.step.call` inside a `.call` ends when the program is
        enqueued; from the previous `.call`'s end to there nothing is in
        flight (the host's gap), from there to the `.call`'s end one
        program is (launch, execution, readback).  The session runs one
        program at a time, so the two tile its timeline exactly.
    easydist.serve.retire         attrs: reason, request_id
        GenerationSession._retire, round all a retirement does (slot and
        pages given back, the KV001 audit, the future resolved): below a
        step's `.decode.harvest` or `.prefill.finish`, never the step's
        direct child; outside any step when `evacuate` retires
    easydist.serve.submit         attrs: prompt_len
    easydist.serve.snapshot_inflight    attrs: n
        the session's other entry points on the loop's thread, called
        between steps: what they cover of the time from one step's end to
        the next one's start is the session's, the rest the caller's own
        code

Counters: `xla_compiles{fn=<name>}`; `pallas_calls{kernel=<name>,
row_shards=<k>}`, one per Pallas kernel call of a program emitted for a mesh
(jaxfront/api.py::_pallas_row_axes): `k` is the number of shards its rows
were split into, 1 for a call every device runs whole (the kernels of a
model with state layers among them: `ssd_chunk_scan`, `ssm_decode_update`,
`delta_decode_update`, `selective_chunk_scan`, `selective_decode_update`;
an expert layer's two: `grouped_matmul`, `grouped_matmul_sum`; the latent
kernels: `latent_decode`, `latent_chunk`);
`flash_train_calls{kernel=<name>,kv=resident|streamed,operands=<dtype>}`,
one per traced call of a flash training kernel (ops/flash_attention.py):
whether a grid step holds the side it walks whole, and the dtype the MXU
is handed.
`kv_audits{where=retire|first_decode,path=vector|listed}`, one per KV001
audit of a session (serve/generation.py::_audit_kv): `vector` where array
passes decided the pool consistent, `listed` where they did not and the
pool was walked to word the finding — a sound run reads `listed` 0.
Serving counts stay in `ServeMetrics`: the state pool's gauges, a latent
arena's `latent_cache_bytes`, the delta-rule layers' `delta_state_bytes`,
`delta_rows_updated` and `delta_chunk_positions`, the selective-state
layers' `selective_state_bytes`, `selective_rows_updated` and
`selective_scan_positions`, and the expert FFN's
`moe_*` counters too,
which a program sums over its layers on the device and hands back with
its tokens, under the same `.call` span — of a model stepped with a
`State` or without one.
Requests: the timeline `GenerationSession` gives every finished request
(`docs/SERVING.md`, "Observability").
"""

from __future__ import annotations

import collections
import itertools
import statistics
import threading
import time
from typing import Dict, List, Optional

import jax

SPAN_RING = 65536      # ~120 spans a second of serving: minutes of history
REQUEST_RING = 4096

_spans: collections.deque = collections.deque(maxlen=SPAN_RING)
_requests: collections.deque = collections.deque(maxlen=REQUEST_RING)
_counters: Dict[str, int] = {}
_counters_lock = threading.Lock()
_ids = itertools.count(1)
_open = threading.local()      # .stack: ids of this thread's open spans


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class span:
    """`with span("easydist.layer.what", n=3) as sp:` — afterwards
    `sp.seconds` is the duration, for the caller that also feeds it to a
    histogram.  `sp.set(k=v)` adds to the ring's record what is only known
    inside (the profiler's annotation keeps the attrs given at entry)."""

    __slots__ = ("name", "attrs", "id", "parent_id", "t0_ns", "t1_ns",
                 "_annotation")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        stack = _stack()
        self.parent_id = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        self._annotation.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _stack().pop()
        _spans.append((self.name, self.id, self.parent_id, self.t0_ns,
                       self.t1_ns, self.attrs))
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def record_span(name: str, t0_ns: int, t1_ns: int, parent_id: int = 0,
                **attrs) -> int:
    """A span whose interval is only known afterwards (a dispatch that
    turned out to compile, an emptiness that a `submit()` ended).  Returns
    its id."""
    span_id = next(_ids)
    _spans.append((name, span_id, parent_id, t0_ns, t1_ns, attrs))
    return span_id


def count(name: str, n: int = 1, **key) -> None:
    """Process-wide counter `name{k=v,...}` for events that have no
    `ServeMetrics` to live in."""
    if key:
        name += "{" + ",".join(f"{k}={v}" for k, v in sorted(key.items())) \
            + "}"
    with _counters_lock:
        _counters[name] = _counters.get(name, 0) + n


def request(timeline: dict) -> None:
    """One finished request's timeline into the bounded request ring."""
    _requests.append(timeline)


def snapshot() -> dict:
    """{"spans": [{name, id, parent_id, t0_ns, t1_ns, attrs}], "counters":
    {name: n}, "requests": [timeline]} — copies, oldest first."""
    with _counters_lock:
        counters = dict(_counters)
    return {"spans": [{"name": n, "id": i, "parent_id": p, "t0_ns": t0,
                       "t1_ns": t1, "attrs": dict(a)}
                      for n, i, p, t0, t1, a in tuple(_spans)],
            "counters": counters,
            "requests": [dict(r) for r in tuple(_requests)]}


def clear() -> None:
    _spans.clear()
    _requests.clear()
    with _counters_lock:
        _counters.clear()


def _self_ns(record: dict, children: List[dict]) -> int:
    t0, t1 = record["t0_ns"], record["t1_ns"]
    covered, end = 0, t0
    for c0, c1 in sorted((max(r["t0_ns"], t0), min(r["t1_ns"], t1))
                         for r in children):
        if c1 > end:
            covered += c1 - max(c0, end)
            end = c1
    return (t1 - t0) - covered


def self_ns(record: dict, records: List[dict]) -> int:
    """A span's duration less what its children cover: the nanoseconds the
    layer spent in its own code."""
    return _self_ns(record, [r for r in records
                             if r["parent_id"] == record["id"]])


def self_time_by_name(snapshot: dict,
                      under: Optional[str] = "easydist.serve.step") -> dict:
    """{name: (count, median self ms, total self s)} over every span called
    `under` and every span beneath one (`under=None`: over every span) —
    where a step goes, by phase."""
    records = snapshot["spans"]
    children: Dict[int, List[dict]] = {}
    for r in records:
        children.setdefault(r["parent_id"], []).append(r)
    if under is None:
        picked = records
    else:
        picked, todo = [], [r for r in records if r["name"] == under]
        while todo:
            r = todo.pop()
            picked.append(r)
            todo += children.get(r["id"], [])
    selves: Dict[str, List[int]] = {}
    for r in picked:
        selves.setdefault(r["name"], []).append(
            _self_ns(r, children.get(r["id"], [])))
    return {name: (len(ns), statistics.median(ns) / 1e6, sum(ns) / 1e9)
            for name, ns in selves.items()}
