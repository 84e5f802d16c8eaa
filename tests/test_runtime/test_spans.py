"""runtime/spans.py: the recorder itself, and its call sites in
jaxfront/api.py (compile phases, dispatch, XLA compile events, the names of
the jitted programs)."""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
from easydist_tpu.runtime import spans


@pytest.fixture(autouse=True)
def _clean_recorder():
    spans.clear()
    yield
    spans.clear()


def _named(snap, name):
    return [r for r in snap["spans"] if r["name"] == name]


# ------------------------------------------------------------ the recorder

def test_nesting_gives_parent_ids():
    with spans.span("t.outer", k=1) as outer:
        with spans.span("t.inner") as inner:
            with spans.span("t.leaf"):
                pass
        with spans.span("t.sibling"):
            pass
    by = {r["name"]: r for r in spans.snapshot()["spans"]}
    assert by["t.outer"]["parent_id"] == 0
    assert by["t.inner"]["parent_id"] == outer.id == by["t.outer"]["id"]
    assert by["t.leaf"]["parent_id"] == inner.id
    assert by["t.sibling"]["parent_id"] == outer.id
    assert by["t.outer"]["attrs"] == {"k": 1}
    # a child lies inside its parent, on one clock
    assert by["t.outer"]["t0_ns"] <= by["t.inner"]["t0_ns"] \
        <= by["t.inner"]["t1_ns"] <= by["t.outer"]["t1_ns"]
    assert len({r["id"] for r in by.values()}) == 4


def test_parent_is_the_innermost_open_span_of_the_same_thread():
    seen = {}

    def other():
        with spans.span("t.other_thread") as sp:
            seen["parent"] = sp.parent_id
            with spans.span("t.other_child") as child:
                seen["child_parent"], seen["id"] = child.parent_id, sp.id

    with spans.span("t.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert seen["parent"] == 0          # not t.main: that is another thread's
    assert seen["child_parent"] == seen["id"]


def test_span_records_and_unwinds_when_its_body_raises():
    with pytest.raises(KeyError):
        with spans.span("t.fails"):
            raise KeyError("x")
    with spans.span("t.after") as after:
        pass
    assert after.parent_id == 0
    assert [r["name"] for r in spans.snapshot()["spans"]] == ["t.fails",
                                                              "t.after"]


def test_set_and_seconds():
    with spans.span("t.timed", a=1) as sp:
        time.sleep(0.002)
        sp.set(b=2)
    (rec,) = spans.snapshot()["spans"]
    assert rec["attrs"] == {"a": 1, "b": 2}
    assert sp.seconds == (rec["t1_ns"] - rec["t0_ns"]) / 1e9 >= 0.002


def test_rings_are_bounded():
    for i in range(spans.SPAN_RING + 50):
        spans.record_span("t.flood", i, i + 1)
    for i in range(spans.REQUEST_RING + 5):
        spans.request({"request_id": i})
    snap = spans.snapshot()
    assert len(snap["spans"]) == spans.SPAN_RING
    assert snap["spans"][0]["t0_ns"] == 50          # the oldest went
    assert len(snap["requests"]) == spans.REQUEST_RING
    assert snap["requests"][-1]["request_id"] == spans.REQUEST_RING + 4


def test_counters_by_key_and_clear():
    spans.count("compiles", fn="a")
    spans.count("compiles", fn="a")
    spans.count("compiles", 3, fn="b", kind="x")
    spans.count("plain")
    assert spans.snapshot()["counters"] == {
        "compiles{fn=a}": 2, "compiles{fn=b,kind=x}": 3, "plain": 1}
    spans.clear()
    assert spans.snapshot() == {"spans": [], "counters": {}, "requests": []}


def test_snapshot_is_a_copy():
    with spans.span("t.one", k=1):
        pass
    snap = spans.snapshot()
    snap["spans"][0]["attrs"]["k"] = 99
    snap["spans"].clear()
    assert spans.snapshot()["spans"][0]["attrs"] == {"k": 1}


@pytest.mark.parametrize("children, expect", [
    ([], 100),                                   # no child: all its own
    ([(10, 30), (50, 60)], 70),                  # two apart
    ([(10, 40), (30, 60)], 50),                  # overlapping: counted once
    ([(-20, 10), (90, 150)], 80),                # clipped to the parent
    ([(0, 100)], 0),                             # covered whole
])
def test_self_ns(children, expect):
    parent = {"id": 7, "parent_id": 0, "t0_ns": 0, "t1_ns": 100}
    records = [parent] + [
        {"id": 100 + i, "parent_id": 7, "t0_ns": a, "t1_ns": b}
        for i, (a, b) in enumerate(children)]
    # a grandchild and a stranger change nothing
    records += [{"id": 900, "parent_id": 100, "t0_ns": 0, "t1_ns": 100},
                {"id": 901, "parent_id": 3, "t0_ns": 0, "t1_ns": 100}]
    assert spans.self_ns(parent, records) == expect


@pytest.mark.parametrize("under, expect", [
    # two steps: 10 ms of which 6 in a call (2 of those its dispatch), and
    # 20 ms of which 4 in a build and 12 in a call; a submit between them
    ("t.step", {"t.step": (2, 4.0, 0.008), "t.build": (1, 4.0, 0.004),
                "t.call": (2, 8.0, 0.016), "t.dispatch": (1, 2.0, 0.002)}),
    ("t.call", {"t.call": (2, 8.0, 0.016), "t.dispatch": (1, 2.0, 0.002)}),
    (None, {"t.step": (2, 4.0, 0.008), "t.build": (1, 4.0, 0.004),
            "t.call": (2, 8.0, 0.016), "t.dispatch": (1, 2.0, 0.002),
            "t.submit": (1, 1.0, 0.001)}),
    ("t.absent", {}),
])
def test_self_time_by_name(under, expect):
    """Where a step goes, by phase: count, median self ms, total self s."""
    ms = 1_000_000
    one = spans.record_span("t.step", 0, 10 * ms)
    call = spans.record_span("t.call", 2 * ms, 8 * ms, parent_id=one)
    spans.record_span("t.dispatch", 2 * ms, 4 * ms, parent_id=call)
    spans.record_span("t.submit", 11 * ms, 12 * ms)
    two = spans.record_span("t.step", 20 * ms, 40 * ms)
    spans.record_span("t.build", 21 * ms, 25 * ms, parent_id=two)
    spans.record_span("t.call", 26 * ms, 38 * ms, parent_id=two)
    assert spans.self_time_by_name(spans.snapshot(), under=under) == expect


def test_many_threads_lose_no_count_and_share_no_id():
    """More workers than cores, a short switch interval: every increment
    counted, every span recorded under an id of its own, every thread's
    nesting its own."""
    n_threads, n_each = 32, 200
    wrong_parent = []

    def work():
        for _ in range(n_each):
            with spans.span("t.stress") as outer:
                with spans.span("t.stress.child") as child:
                    if child.parent_id != outer.id:
                        wrong_parent.append(child.id)
                spans.count("stress")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = spans.snapshot()
    assert snap["counters"]["stress"] == n_threads * n_each
    assert len(snap["spans"]) == 2 * n_threads * n_each
    assert len({r["id"] for r in snap["spans"]}) == len(snap["spans"])
    assert not wrong_parent


def test_span_is_in_the_profilers_host_plane(tmp_path):
    """With a `jax.profiler` session capturing, the span lands in the
    trace beside the device (here: the CPU backend's host plane)."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with spans.span("easydist.test.traced", n=3):
        jnp.ones((8,)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert any(n.startswith("easydist.test.traced") for n in names)


def test_a_span_seen_in_a_trace_and_in_the_ring_gives_the_two_clocks_offset(
        tmp_path):
    """The join the docstring promises (the CPU backend's host plane
    suffices): a captured span's event carries its attrs at entry, so the
    event whose `step` is n is the record whose `step` is n, and (event start
    - record start) is one constant for every such pair."""
    import glob

    from jax.profiler import ProfileData

    with spans.span("easydist.test.step", step=1):
        pass                              # before the capture: ring only
    with jax.profiler.trace(str(tmp_path)):
        for n in (2, 3, 4):
            with spans.span("easydist.test.step", step=n) as sp:
                time.sleep(0.003 * n)
                sp.set(later=n)           # not at entry: the ring's alone
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {dict(e.stats)["step"]: e
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == "easydist.test.step"}
    records = {r["attrs"]["step"]: r for r in spans.snapshot()["spans"]}
    assert sorted(events) == [2, 3, 4] and sorted(records) == [1, 2, 3, 4]
    assert all("later" not in dict(e.stats) for e in events.values())
    offsets = [events[n].start_ns - records[n]["t0_ns"] for n in (2, 3, 4)]
    assert max(offsets) - min(offsets) < 100_000
    for n in (2, 3, 4):
        assert abs(events[n].duration_ns - (
            records[n]["t1_ns"] - records[n]["t0_ns"])) < 100_000


# ----------------------------------------------------- jaxfront call sites

def _mlp(w, x):
    return jnp.tanh(x @ w).sum()


@pytest.mark.world_8
def test_phase_seconds_are_the_compile_spans(cpu_devices):
    mesh = make_device_mesh((8,), ("d",))
    w, x = jnp.ones((64, 64)), jnp.ones((16, 64))
    result = easydist_compile(_mlp, mesh=mesh).get_compiled(w, x)
    snap = spans.snapshot()
    assert set(result.phase_seconds) == {"trace", "discovery", "solve"}
    for phase, seconds in result.phase_seconds.items():
        (rec,) = _named(snap, "easydist.compile." + phase)
        assert seconds == (rec["t1_ns"] - rec["t0_ns"]) / 1e9 > 0
        assert rec["attrs"]["fn"] == "_mlp"
    (emit,) = _named(snap, "easydist.compile.emit")
    assert emit["t0_ns"] >= _named(snap, "easydist.compile.solve")[0]["t1_ns"]


@pytest.mark.world_8
def test_called_twice_alike_compiles_once(cpu_devices):
    mesh = make_device_mesh((8,), ("d",))
    compiled = easydist_compile(_mlp, mesh=mesh)
    w, x = jnp.ones((64, 64)), jnp.ones((16, 64))
    for _ in range(3):
        compiled(w, x)
    snap = spans.snapshot()
    assert snap["counters"] == {"xla_compiles{fn=_mlp}": 1}
    calls = _named(snap, "easydist.step.call")
    assert len(calls) == 3 and all(c["attrs"] == {"fn": "_mlp"}
                                   for c in calls)
    (comp,) = _named(snap, "easydist.step.compile")
    # the compile is the first call's, and lies inside it
    assert comp["parent_id"] == calls[0]["id"]
    assert calls[0]["t0_ns"] == comp["t0_ns"] <= comp["t1_ns"] \
        <= calls[0]["t1_ns"]


@pytest.mark.world_8
def test_second_input_sharding_is_a_second_xla_compile(cpu_devices):
    """What `CompiledFunction`'s own cache cannot see (one signature, one
    CompileResult) and the train step does: the same shapes come back
    under other shardings, and the jit compiles again."""
    mesh = make_device_mesh((8,), ("d",))
    compiled = easydist_compile(_mlp, mesh=mesh)
    w = jnp.ones((64, 64))
    x = jnp.ones((16, 64))
    compiled(w, x)
    compiled(w, jax.device_put(x, NamedSharding(mesh, P("d"))))
    compiled(w, jax.device_put(x, NamedSharding(mesh, P("d"))))
    assert compiled.cache_stats()["misses"] == 1
    snap = spans.snapshot()
    assert snap["counters"]["xla_compiles{fn=_mlp}"] == 2
    assert len(_named(snap, "easydist.step.compile")) == 2
    assert len(_named(snap, "easydist.step.call")) == 3


@pytest.mark.world_8
def test_new_shape_is_counted_under_the_same_function(cpu_devices):
    mesh = make_device_mesh((8,), ("d",))
    compiled = easydist_compile(_mlp, mesh=mesh)
    w = jnp.ones((64, 64))
    compiled(w, jnp.ones((16, 64)))
    compiled(w, jnp.ones((32, 64)))     # SignatureMismatch, then a new plan
    snap = spans.snapshot()
    assert snap["counters"]["xla_compiles{fn=_mlp}"] == 2
    assert len(_named(snap, "easydist.compile.trace")) == 2


@pytest.mark.world_8
def test_jitted_programs_carry_the_functions_name(cpu_devices):
    mesh = make_device_mesh((8,), ("d",))

    def train_step(w, x):
        return w - 0.1 * jax.grad(_mlp)(w, x), _mlp(w, x)

    w, x = jnp.ones((64, 64)), jnp.ones((16, 64))
    names = {}
    for fn in (_mlp, train_step):
        result = easydist_compile(fn, mesh=mesh).get_compiled(w, x)
        assert result.name == fn.__name__
        names[fn.__name__] = (
            result.tree_jitted.lower(w, x).as_text().split("\n", 1)[0],
            result.jitted.lower(w, x).as_text().split("\n", 1)[0])
    assert "module @jit__mlp " in names["_mlp"][0]
    assert "module @jit_train_step " in names["train_step"][0]
    assert "module @jit__mlp_flat " in names["_mlp"][1]
    assert "module @jit_train_step_flat " in names["train_step"][1]
