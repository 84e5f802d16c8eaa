"""What `GenerationSession` records of itself (runtime/spans.py): the host
phases of `step()` as spans that tile it, a timeline for every request, the
`queue_wait` histogram, and names for its jitted programs."""

import statistics

import jax
import pytest

from easydist_tpu.models import gpt
from easydist_tpu.runtime import spans
from easydist_tpu.serve import GenerationSession, ServeConfig

CHUNK, ROWS, CHUNKS_PER_STEP, SLOTS = 4, 2, 2, 3
PROMPTS = [[3, 14, 15, 9, 2, 6], [5, 3, 5], [8, 9, 7, 9, 3, 2, 3, 8, 4, 6],
           [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4], [1, 4, 1, 4, 2]]
NEW = [5, 3, 7, 4, 6]
LAYOUTS = {
    "bucketed": dict(),
    "paged": dict(kv_layout="paged"),
    "paged_speculative": dict(kv_layout="paged", speculate_k=2),
}
# per step: the step and its admit; per chunk call a build, a call with the
# dispatch inside it, and at most one finish per staging row; a decode
# round's build, call with its dispatch, and harvest
SPANS_PER_STEP_BOUND = 2 + CHUNKS_PER_STEP * (3 + ROWS) + 4


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny()
    return cfg, gpt.gpt_init(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=sorted(LAYOUTS))
def run(request, model):
    """One drained session per layout: (layout, results, recorder snapshot,
    session).  More requests than slots, so some wait in the queue."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=SLOTS,
                     prefill_chunk=CHUNK, prefill_batch=ROWS,
                     prefill_chunks_per_step=CHUNKS_PER_STEP,
                     **LAYOUTS[request.param])
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    spans.clear()
    futs = [sess.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, NEW)]
    sess.run_until_drained()
    results = [f.result(timeout=5) for f in futs]
    snap = spans.snapshot()
    spans.clear()
    return request.param, results, snap, sess


def _children(snap):
    by_parent = {}
    for r in snap["spans"]:
        by_parent.setdefault(r["parent_id"], []).append(r)
    return by_parent


def _descendants(rec, by_parent):
    out = []
    for child in by_parent.get(rec["id"], []):
        out += [child] + _descendants(child, by_parent)
    return out


def _steps(snap):
    return [r for r in snap["spans"] if r["name"] == "easydist.serve.step"]


def test_one_step_span_per_step_with_its_attrs(run):
    _, _, snap, sess = run
    steps = _steps(snap)
    assert [s["attrs"]["step"] for s in steps] == \
        list(range(1, sess._step_index + 1))
    assert all(s["parent_id"] == 0 for s in steps)
    assert steps[0]["attrs"]["queued"] == len(PROMPTS)
    assert steps[0]["attrs"]["live"] == 0
    assert max(s["attrs"]["live"] for s in steps) == SLOTS
    assert steps[-1]["attrs"]["queued"] == 0


def test_phases_tile_the_step(run):
    """Every step has exactly one admit; its direct children do not
    overlap and lie inside it; what they leave uncovered — the step's own
    code between the phases — is under the slack: a median of 5 ms and no
    step over 250 ms on a shared CPU (the phases themselves take
    milliseconds to seconds here: they compile)."""
    layout, _, snap, _ = run
    by_parent = _children(snap)
    own_ms = []
    for step in _steps(snap):
        kids = sorted(by_parent[step["id"]], key=lambda r: r["t0_ns"])
        names = [k["name"] for k in kids]
        assert names[0] == "easydist.serve.admit"
        assert names.count("easydist.serve.admit") == 1
        assert set(names) <= {
            "easydist.serve.admit", "easydist.serve.prefill.build",
            "easydist.serve.prefill.call", "easydist.serve.prefill.finish",
            "easydist.serve.decode.build", "easydist.serve.decode.call",
            "easydist.serve.decode.harvest",
            "easydist.step.call"}, names     # the draft model's dispatch
        if "speculative" not in layout:
            # a build, then its call, then (decode) its harvest
            for a, b in zip(names, names[1:]):
                if a.endswith(".build"):
                    assert b == a[:-len("build")] + "call"
                if a == "easydist.serve.decode.call":
                    assert b == "easydist.serve.decode.harvest"
        assert kids[0]["t0_ns"] >= step["t0_ns"]
        assert kids[-1]["t1_ns"] <= step["t1_ns"]
        for a, b in zip(kids, kids[1:]):
            assert a["t1_ns"] <= b["t0_ns"], (a["name"], b["name"])
        own_ms.append(spans.self_ns(step, snap["spans"]) / 1e6)
    assert statistics.median(own_ms) < 5.0 and max(own_ms) < 250.0, own_ms


def test_admit_span_counts_what_left_the_queue(run):
    _, _, snap, _ = run
    admits = [r for r in snap["spans"] if r["name"] == "easydist.serve.admit"]
    assert sum(a["attrs"]["admitted"] for a in admits) == len(PROMPTS)
    assert admits[0]["attrs"]["deferred"] == len(PROMPTS) \
        - admits[0]["attrs"]["admitted"] > 0
    assert admits[-1]["attrs"] == {"admitted": 0, "deferred": 0}


def test_every_program_call_is_a_call_span_with_its_dispatch_inside(run):
    layout, _, snap, sess = run
    by_parent = _children(snap)
    calls = [r for r in snap["spans"]
             if r["name"] in ("easydist.serve.prefill.call",
                              "easydist.serve.decode.call")]
    m = sess.metrics
    assert sum(c["name"].endswith("prefill.call") for c in calls) \
        == m.counter("prefill_chunks")
    assert sum(c["name"].endswith("decode.call") for c in calls) \
        == m.counter("decode_steps") + m.counter("verify_steps")
    for call in calls:
        dispatch = by_parent[call["id"]][0]
        assert dispatch["name"] == "easydist.step.call"
        assert dispatch["attrs"]["fn"] == call["attrs"]["fn"]
        assert call["attrs"]["rows"] >= 1
    want = {"bucketed": {"_prefill_chunk", "_decode"},
            "paged": {"_prefill_chunk_paged", "_decode_paged"},
            "paged_speculative": {"_prefill_chunk_paged", "_decode_paged",
                                  "_verify_paged"}}[layout]
    assert {c["attrs"]["fn"] for c in calls} == want
    # XLA compiled each program in its first calls and never after: once
    # for the pool as it was made, and (where the donated pool comes back
    # committed to other shardings) once more for the pool it gave back
    # (or not at all: sessions over one model share compiled programs)
    for fn in want:
        mine = [c for c in calls if c["attrs"]["fn"] == fn]
        compiled_in = [i for i, c in enumerate(mine) if any(
            r["name"] == "easydist.step.compile"
            for r in _descendants(c, by_parent))]
        assert compiled_in in ([], [0], [0, 1]), (fn, compiled_in)
        assert snap["counters"].get(f"xla_compiles{{fn={fn}}}", 0) \
            == len(compiled_in)


@pytest.mark.parametrize("run", ["paged"], indirect=True)
def test_recorder_calls_per_step_are_bounded(run):
    """A count, not a timing: a step opens at most a constant number of
    spans, and stamps each live slot once per decode round.  (Stated for
    the paged layout: the bucketed one adds a dispatch per trie chunk it
    extracts, speculation a second round per step.)"""
    _, results, snap, _ = run
    by_parent = _children(snap)
    steady = 0
    for step in _steps(snap):
        inside = _descendants(step, by_parent)
        if any(".compile" in r["name"] for r in inside):
            continue    # a program's first calls: its compile phases too
        steady += 1
        assert 1 + len(inside) <= SPANS_PER_STEP_BOUND
    assert steady >= len(_steps(snap)) // 2
    # one stamp per live slot and round: every later token's stamp is the
    # end of a decode call, shared by the round's slots
    decode_ends = {r["t1_ns"] for r in snap["spans"]
                   if r["name"] == "easydist.serve.decode.call"}
    later = [t for res in results for t in res["timing"]["token_ns"][1:]]
    assert set(later) <= decode_ends
    assert len(set(later)) < len(later)      # shared, not one read each


def test_timelines(run):
    _, results, snap, _ = run
    ring = {r["request_id"]: r for r in snap["requests"]}
    assert len(ring) == len(PROMPTS)
    steps = _steps(snap)
    for prompt, n_new, res in zip(PROMPTS, NEW, results):
        t = res["timing"]
        assert t == ring[t["request_id"]]
        assert len(res["ids"]) == n_new == len(t["token_ns"])
        assert (t["prompt_len"], t["prefix_len"]) == (len(prompt), 0)
        assert t["finish_reason"] == res["finish_reason"] == "length"
        assert t["first_token_ns"] == t["token_ns"][0]
        stamps = [t["submit_ns"], t["admit_ns"]] + t["token_ns"] \
            + [t["finish_ns"]]
        assert stamps == sorted(stamps)
        # the first token is made inside a step, before that step's end;
        # the decode round of the SAME step makes the second: a clock
        # outside the session sees both at once
        (made_in,) = [s for s in steps
                      if s["t0_ns"] <= t["first_token_ns"] <= s["t1_ns"]]
        assert t["first_token_ns"] < made_in["t1_ns"]
        if n_new > 1:
            assert t["first_token_ns"] < t["token_ns"][1] <= made_in["t1_ns"]


def test_finish_spans_carry_their_request(run):
    _, results, snap, _ = run
    finishes = [r for r in snap["spans"]
                if r["name"] == "easydist.serve.prefill.finish"]
    assert sorted(f["attrs"]["request_id"] for f in finishes) \
        == sorted(res["timing"]["request_id"] for res in results)
    for f in finishes:
        (t,) = [res["timing"] for res in results
                if res["timing"]["request_id"] == f["attrs"]["request_id"]]
        assert f["t0_ns"] <= t["first_token_ns"] <= f["t1_ns"]


def test_queue_wait_and_ttft_are_fed(run):
    _, results, _, sess = run
    latency = sess.metrics.snapshot()["latency"]
    assert latency["queue_wait"]["count"] == len(PROMPTS)
    assert latency["ttft"]["count"] == len(PROMPTS)
    waits = [(r["timing"]["admit_ns"] - r["timing"]["submit_ns"]) / 1e9
             for r in results]
    assert latency["queue_wait"]["mean_s"] == pytest.approx(
        sum(waits) / len(waits))
    # the last two waited for a slot, the first did not
    assert waits[0] < waits[-1]


def test_session_programs_have_distinct_stable_names(model):
    """The `XLA Modules` line of a device trace names a program by its
    jit: every program a session builds gets its function's name."""
    cfg, params = model
    sc = ServeConfig(decode_buckets=(cfg.seq,), max_decode_slots=2,
                     prefill_chunk=CHUNK, kv_layout="paged", speculate_k=2)
    sess = GenerationSession.for_gpt(params, cfg, config=sc)
    names = [sess._paged_defs[k].__name__ for k in sorted(sess._paged_defs)]
    assert names == ["_prefill_chunk_paged", "_decode_paged",
                     "_page_export", "_page_import", "_verify_paged"]
    flat = GenerationSession.for_gpt(
        params, cfg, config=ServeConfig(decode_buckets=(cfg.seq,),
                                        max_decode_slots=2,
                                        prefill_chunk=CHUNK))
    names += [c.func.__name__ for c in (
        flat._prefill_chunk_c, flat._restore_c,
        flat._migrate_c, flat._decode_c, flat._extract_for(4),
        flat._extract_for(8))] + [flat._verify_def.__name__]
    assert len(set(names)) == len(names), names
    # and the name reaches the jit (api.py names the module after it:
    # tests/test_runtime/test_spans.py)
    fut = sess.submit(PROMPTS[0], max_new_tokens=3)
    sess.run_until_drained()
    assert len(fut.result(timeout=5)["ids"]) == 3
    assert {"chunk", "decode"} <= set(sess._paged_cs)
    for key, compiled in sess._paged_cs.items():
        for result in compiled._cache.values():
            assert result.name == result.tree_jitted.__name__ \
                == sess._paged_defs[key].__name__
    spans.clear()
