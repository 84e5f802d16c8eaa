"""The five per-layer readers of PR 24 — `decode_step_device_ms`,
`prefill_chunk_device_ms` (device trace, programs found by name),
`session_host_ms_per_step`, `step_dispatch_ms` (the program's spans) and
`step_xla_compiles` (its counter): on hand-made runs, on the trace recorded
on the v5e after the programs got names, against a program that has no
recorder, and in both cells' traced rehearsals."""

import importlib.util
import math
import os
import sys

import pytest

from chipbench import contract, programs, trace_reduce
from easydist_tpu.runtime import spans

from ._rehearse import BENCH, CELLS, last_line, run_cell

NEW = {"serve-mistral7b-chat-1chip": ["decode_step_device_ms",
                                      "prefill_chunk_device_ms",
                                      "session_host_ms_per_step"],
       "train-gpt2xl-4chip": ["step_xla_compiles", "step_dispatch_ms"]}
MS = 1_000_000
# what the runners hand the readers of their kind (any of it will do)
SERVE = {"arena_pages": 576}
TRAIN = {"median_step_s": 0.2, "first_steps_s": [30.0, 0.3, 0.2]}


def reader(name):
    path = os.path.join(contract.ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_recorder():
    spans.clear()
    yield
    spans.clear()


@pytest.fixture
def no_recorder(monkeypatch):
    """The program as it was before PR 24: `runtime/spans.py` is not
    there to import."""
    import easydist_tpu.runtime as runtime

    monkeypatch.delattr(runtime, "spans")
    monkeypatch.setitem(sys.modules, "easydist_tpu.runtime.spans", None)


def serve_trace(decode="jit__decode_paged(11)",
                chunk="jit__prefill_chunk_paged(22)"):
    """Two steps on chip 0: one of a decode round (150 ms), one of two
    chunk calls (30, 40 ms) and a decode round (152 ms); the decode
    program runs a Pallas kernel, the chunk program a fusion."""
    modules = [[decode, 10 * MS, 150 * MS], [chunk, 170 * MS, 30 * MS],
               [chunk, 205 * MS, 40 * MS], [decode, 250 * MS, 152 * MS]]
    kernel = "%paged_decode.1 custom-call tpu_custom_call bf16[32,32,1,128]"
    ops = [[kernel, 20 * MS, 5 * MS], ["%fusion.2 fusion", 171 * MS, 20 * MS],
           ["%fusion.2 fusion", 206 * MS, 30 * MS], [kernel, 260 * MS, 5 * MS]]
    host = [["chipbench.session_step", 4 * MS, 160 * MS],
            ["chipbench.session_step", 166 * MS, 240 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def serve_run(trace, **over):
    return {"serve": SERVE, "trace": {"trace": trace, "window_s": 0.5},
            "rehearse": False, **over}


# ------------------------------------------------ programs in a device trace

@pytest.mark.parametrize("name, expect", [("decode_step_device_ms", 151.0),
                                          ("prefill_chunk_device_ms", 35.0)])
def test_a_program_is_found_by_its_name(name, expect):
    assert reader(name).read(serve_run(serve_trace())) == expect


@pytest.mark.parametrize("name, expect", [("decode_step_device_ms", 151.0),
                                          ("prefill_chunk_device_ms", 35.0)])
def test_unnamed_programs_are_told_apart_by_the_kernel(name, expect):
    trace = serve_trace(decode="jit_tree_fn(11)", chunk="jit_tree_fn(22)")
    assert reader(name).read(serve_run(trace)) == expect


@pytest.mark.parametrize("name", NEW["serve-mistral7b-chat-1chip"][:2])
def test_a_program_that_did_not_run_reads_nothing(name):
    trace = serve_trace(decode="jit_other(1)", chunk="jit_another(2)")
    assert reader(name).read(serve_run(trace)) is None
    assert reader(name).read({"serve": SERVE, "trace": None}) is None
    assert reader(name).read({"train": TRAIN, "trace": {"trace": trace}}) is None


def test_the_named_recording_holds_both_programs_by_name():
    trace = trace_reduce.load_recorded(programs.RECORDED_NAMED)
    assert trace["device_kind"] == "TPU v5 lite"
    names = {n for n, _, _ in programs.module_events(trace)}
    assert not any(programs.UNNAMED.match(n) for n in names), names
    decode = programs.executions(trace, programs.DECODE)
    chunk = programs.executions(trace, programs.PREFILL_CHUNK)
    assert len(decode) >= 10 and len(chunk) >= 4
    assert not set(decode) & set(chunk)
    # under --rehearse the two readers read this file, whatever the run's
    # own trace is: a decode round of 32 slots, a chunk call of 4 rows
    run = serve_run(serve_trace(), rehearse=True)
    d = reader("decode_step_device_ms").read(run)
    c = reader("prefill_chunk_device_ms").read(run)
    assert 100.0 < d < 200.0 and 20.0 < c < 60.0 and d != 151.0
    # and the old recording, made before the names, reads the same way
    # through the kernel
    old = trace_reduce.load_recorded(os.path.join(
        contract.ROOT, "chipbench", "recorded", "serve-1chip.json.gz"))
    assert len(programs.executions(old, programs.DECODE)) == 20
    assert len(programs.executions(old, programs.PREFILL_CHUNK)) == 33


def test_the_named_recording_names_the_programs_phases_in_its_idle_gaps():
    """It was recorded with the host plane's `easydist.*` spans kept: the
    breakdown then says which phase of `step()` the device waited for."""
    trace = trace_reduce.load_recorded(programs.RECORDED_NAMED)
    gaps = dict(trace_reduce.breakdown(trace, top=40)["idle_gaps"])
    assert any(n.startswith("easydist.serve.") for n in gaps), gaps
    assert trace_reduce.busy(trace, 1)["busy_s"] > 0


# --------------------------------------------------- the program's recorder

def hand_made_steps():
    """Three steps as `GenerationSession.step` records them: (start, end,
    [(phase, start, end[, children])]) in ms."""
    steps = [
        # a decode-only step: 160 ms, 150 of them in the call -> 10 host
        (0, 160, [("easydist.serve.admit", 0, 1),
                  ("easydist.serve.decode.build", 1, 4),
                  ("easydist.serve.decode.call", 4, 154,
                   [("easydist.step.call", 4, 6)]),
                  ("easydist.serve.decode.harvest", 154, 159)]),
        # prefill and decode: 260 ms, 40 + 40 + 150 in calls, and a
        # dispatch of the bucketed layout's migrate (2 ms) -> 28 host
        (200, 460, [("easydist.serve.admit", 200, 202),
                    ("easydist.serve.prefill.build", 202, 206),
                    ("easydist.serve.prefill.call", 206, 246),
                    ("easydist.serve.prefill.call", 250, 290),
                    ("easydist.serve.prefill.finish", 290, 296,
                     [("easydist.step.call", 291, 293)]),
                    ("easydist.serve.decode.build", 296, 300),
                    ("easydist.serve.decode.call", 300, 450),
                    ("easydist.serve.decode.harvest", 450, 458)]),
        # no decode round: not counted
        (500, 600, [("easydist.serve.admit", 500, 501),
                    ("easydist.serve.prefill.build", 501, 503),
                    ("easydist.serve.prefill.call", 503, 543)]),
    ]

    def put(items, parent):
        for name, a, b, *kids in items:
            span_id = spans.record_span(name, a * MS, b * MS,
                                        parent_id=parent)
            if kids:
                put(kids[0], span_id)

    for a, b, phases in steps:
        put(phases, spans.record_span("easydist.serve.step", a * MS, b * MS))


def test_session_host_is_the_step_less_its_calls():
    hand_made_steps()
    value = reader("session_host_ms_per_step").read({"serve": SERVE})
    assert value == (10.0 + 28.0) / 2


def test_session_host_without_a_decode_round_reads_nothing():
    with spans.span("easydist.serve.step"):
        with spans.span("easydist.serve.admit"):
            pass
    assert reader("session_host_ms_per_step").read({"serve": SERVE}) is None


def test_session_host_from_outside_for_a_program_without_the_recorder(
        no_recorder):
    r = reader("session_host_ms_per_step")
    assert r.read({"serve": SERVE}) is None
    # step 1: 160 ms less the decode round's 150; step 2: 240 less
    # 30 + 40 + 152 on the device
    assert r.read(serve_run(serve_trace("jit_tree_fn(1)",
                                        "jit_tree_fn(2)"))) == (10.0 + 18.0) / 2


def test_step_dispatch_is_the_median_call_after_the_last_compile():
    for t0, dur in [(0, 900), (1000, 800)]:      # two compiling dispatches
        spans.record_span("easydist.step.call", t0 * MS, (t0 + dur) * MS,
                          fn="train_step")
        spans.record_span("easydist.step.compile", t0 * MS, (t0 + dur) * MS,
                          fn="train_step")
    for i, dur in enumerate([3, 5, 4]):
        spans.record_span("easydist.step.call", (2000 + 10 * i) * MS,
                          (2000 + 10 * i + dur) * MS, fn="train_step")
    spans.record_span("easydist.step.call", 3000 * MS, 3100 * MS,
                      fn="other")
    assert reader("step_dispatch_ms").read({"train": TRAIN}) == 4.0


def test_step_xla_compiles_is_the_counter():
    r = reader("step_xla_compiles")
    spans.count("xla_compiles", fn="other")
    train = {"first_steps_s": [30.0, 0.3, 0.2], "median_step_s": 0.2}
    assert r.read({"train": train}) == 1      # no counter: from outside
    spans.count("xla_compiles", fn="train_step")
    spans.count("xla_compiles", fn="train_step")
    assert r.read({"train": train}) == 2


def test_train_readers_from_outside_for_a_program_without_the_recorder(
        no_recorder):
    train = {"first_steps_s": [33.0, 30.0, 0.23], "median_step_s": 0.222}
    assert reader("step_xla_compiles").read({"train": train}) == 2
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
            ["jit_tree_fn(1)", 12 * MS, 200 * MS],
            ["jit_tree_fn(1)", 225 * MS, 200 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["chipbench.train_step", 10 * MS, 205 * MS],
            ["chipbench.train_step", 221 * MS, 206 * MS]]}]}]}
    r = reader("step_dispatch_ms")
    assert r.read({"train": train}) is None
    assert r.read({"train": train, "trace": {"trace": trace}}) == 3.0


# ------------------------------------------------------------ the rehearsals

@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_carries_the_new_metrics(cell):
    rc, out, err = run_cell("--workload", cell, "--seed", str(2 ** 31 + 29),
                            "--seconds", "2", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    obj = last_line(out)
    contract.check_last_line(obj, CELLS[cell], True, BENCH)
    for name in NEW[cell]:
        value = obj["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)
    if cell.startswith("train"):
        assert obj["metrics"]["step_xla_compiles"]["value"] in (1.0, 2.0)


def test_the_five_entries_are_the_last_of_benchmark_json():
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == \
        NEW["serve-mistral7b-chat-1chip"] + NEW["train-gpt2xl-4chip"]
    for m in BENCH["per_layer"][-5:]:
        (cell,) = m["workloads"]
        assert m["name"] in NEW[cell] and m["better"] == "lower"
