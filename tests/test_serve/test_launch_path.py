"""How a paged step program is launched (`serve/generation.py`): its small
operands cross as ONE int32 array handed to the dispatch as numpy, the
pool holds the program's `CompileResult` after the first launch, and the
readback's host copy is asked for before `_run` blocks — for the four paged
step programs, each served by a family that runs it, against the float32
references the model tests already use."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import weights
from chipbench.reference import mistral
from easydist_tpu.jaxfront.api import SignatureMismatch
from easydist_tpu.models import llama
from easydist_tpu.runtime import spans
from easydist_tpu.serve import GenerationSession, ServeConfig

from ..test_models import test_axk1 as axk1_case
from ..test_models import test_exaone_moe as exaone_case
from ..test_models import test_granite_hybrid as granite_case

MISTRAL = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
               head_dim=8, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, rope_theta=1e6, rms_norm_eps=1e-5)
PLAIN = ("_prefill_chunk_paged", "_decode_paged")
STATE = ("_prefill_chunk_paged_state", "_decode_paged_state")
CALLS = ("easydist.serve.prefill.call", "easydist.serve.decode.call")
BUILDS = ("easydist.serve.prefill.build", "easydist.serve.decode.build")
# (prompt length, tokens asked for) in two waves: the second finds every
# program held, and the two together run past fifty steps
WAVES = (((5, 12), (19, 30), (8, 9)), ((30, 25), (3, 28), (16, 7)))
SLOTS, ROWS, PAGE, BUCKET = 4, 2, 8, 64


def _llama():
    cfg = llama.LlamaConfig(vocab=96, seq=BUCKET, dim=32, heads=4,
                            kv_heads=2, layers=2, ffn_dim=64, rope_theta=1e6,
                            dtype="float32")
    params = weights.mistral_params(MISTRAL, weights.seed_key(3),
                                    dtype=jnp.float32)
    return llama.decoder(cfg), params, \
        lambda seq: mistral.logits(params, MISTRAL, np.asarray(seq))


def _of(case, decoder, make_params, key):
    """A family whose model test keeps its reference's sizes and config."""
    def build():
        params = make_params(case.SIZES, key, dtype=jnp.float32)
        return decoder(case.CFG), params, lambda seq: case.reference.logits(
            params, case.SIZES, jnp.asarray(seq, jnp.int32))
    return build


FAMILIES = {    # name: (decoder, params, reference logits of a sequence)
    "llama": (_llama, PLAIN),
    "granite": (_of(granite_case, granite_case.gh.decoder,
                    granite_case.weights_granite.granite_params,
                    granite_case.weights_granite.seed_key(3)), STATE),
    "exaone": (_of(exaone_case, exaone_case.em.decoder,
                   exaone_case.weights_exaone.exaone_params,
                   exaone_case.weights_exaone.seed_key(3)), STATE),
    "axk1": (_of(axk1_case, axk1_case.axk1.decoder,
                 axk1_case.weights_axk1.axk1_params,
                 axk1_case.weights_axk1.seed_key(4)), PLAIN),
}


def _one_device():
    return Mesh(np.array(jax.devices()[:1]), ("d",))


def _session(decoder, params, mesh=None, compile_key=None, **kw):
    base = dict(decode_buckets=(BUCKET,),
                max_decode_slots=SLOTS, prefill_chunk=PAGE,
                prefill_batch=ROWS, kv_arena_pages=40,
                enable_prefix_cache=False, speculate_k=0)
    base.update(kw)
    return GenerationSession(params, model=decoder,
                             config=ServeConfig(**base),
                             mesh=mesh or _one_device(),
                             compile_key=compile_key)


def _requests(seed=4):
    rng = np.random.default_rng(seed)
    return [[(rng.integers(1, 96, size=n).tolist(), m) for n, m in wave]
            for wave in WAVES]


def _serve(sess, waves):
    """[(prompt, ids)] of every request, a wave drained before the next."""
    out = []
    for wave in waves:
        futs = [sess.submit(p, max_new_tokens=m) for p, m in wave]
        sess.run_until_drained()
        out += [(p, f.result(timeout=5)["ids"])
                for (p, _), f in zip(wave, futs)]
    return out


class _Reached:
    """Counts `jnp.asarray` and `jax.device_put` by the innermost open span
    of the session: what a `.build` reaches of them is a transfer of the
    session's own, made before the dispatch."""

    def __init__(self, patch):
        self.open, self.by_span = [], collections.Counter()
        enter, leave = spans.span.__enter__, spans.span.__exit__

        def on_enter(sp):
            self.open.append(sp.name)
            return enter(sp)

        def on_exit(sp, *exc):
            self.open.pop()
            return leave(sp, *exc)

        patch.setattr(spans.span, "__enter__", on_enter)
        patch.setattr(spans.span, "__exit__", on_exit)
        for module, name in ((jnp, "asarray"), (jax, "device_put")):
            patch.setattr(module, name,
                          self._counting(getattr(module, name)))

    def _counting(self, fn):
        def counted(*a, **kw):
            if self.open:
                self.by_span[self.open[-1]] += 1
            return fn(*a, **kw)
        return counted


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    """(programs, [(prompt, ids)], recorder snapshot, what the builds
    reached, the reference, steps) of one family's session over both waves."""
    build, programs = FAMILIES[request.param]
    decoder, params, reference = build()
    sess = _session(decoder, params)
    spans.clear()
    with pytest.MonkeyPatch.context() as patch:
        reached = _Reached(patch)
        results = _serve(sess, _requests())
    snap = spans.snapshot()
    spans.clear()
    steps = sess._step_index
    sess.close()
    return programs, results, snap, reached.by_span, reference, steps


def _calls(snap, fn=None):
    return [r for r in snap["spans"] if r["name"] in CALLS
            and fn in (None, r["attrs"]["fn"])]


def test_the_session_runs_the_familys_two_programs_past_fifty_steps(served):
    programs, _, snap, _, _, steps = served
    assert {c["attrs"]["fn"] for c in _calls(snap)} == set(programs)
    assert steps >= 50


def test_every_launch_carries_one_host_array(served):
    _, _, snap, _, _, _ = served
    assert {c["attrs"]["h2d"] for c in _calls(snap)} == {1}


def test_a_build_reaches_no_asarray_and_no_device_put(served):
    _, _, _, reached, _, _ = served
    assert [reached[name] for name in BUILDS] == [0, 0]
    # and the counter does count: tracing the two programs converts
    # constants under the first dispatch
    assert sum(reached.values()) > 0


def test_a_program_is_resolved_once_a_pool_and_held_after(served):
    programs, _, snap, _, _, _ = served
    for fn in programs:
        launches = len(_calls(snap, fn))
        assert launches >= 10
        assert snap["counters"][f"serve_launches{{fn={fn},path=resolved}}"] \
            == 1
        assert snap["counters"][f"serve_launches{{fn={fn},path=held}}"] \
            == launches - 1


def test_xla_compiles_each_program_once(served):
    """A numpy operand meets the same jit entry on every call, the first
    included."""
    programs, _, snap, _, _, _ = served
    assert {k: v for k, v in snap["counters"].items()
            if k.startswith("xla_compiles")} \
        == {f"xla_compiles{{fn={fn}}}": 1 for fn in programs}


def test_the_copy_out_is_behind_the_program_and_the_records_keep_order(
        served):
    """`enqueued <= ready_ns <= end` of every `.call`, as before."""
    _, _, snap, _, _, _ = served
    enqueued = {r["parent_id"]: r["t1_ns"] for r in snap["spans"]
                if r["name"] == "easydist.step.call"}
    for call in _calls(snap):
        assert call["t0_ns"] <= enqueued[call["id"]] \
            <= call["attrs"]["ready_ns"] <= call["t1_ns"]


def test_the_served_ids_are_the_float32_references(served):
    _, results, _, _, reference, _ = served
    assert len(results) == sum(map(len, WAVES))
    for prompt, ids in results:
        want = np.asarray(reference(prompt + ids))
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(ids)]
        assert ids == rows.argmax(-1).tolist()


class _Readback:
    """Stands for the device array `_run` reads back."""

    def __init__(self, log):
        self.log = log

    def copy_to_host_async(self):
        self.log.append("copy_to_host_async")

    def block_until_ready(self):
        self.log.append("block_until_ready")
        return self

    def __array__(self, dtype=None, copy=None):
        self.log.append("asarray")
        return np.arange(3, dtype=np.int32)


class _Result:
    name = "_stub"

    def __init__(self, log, refuses=False):
        self.log, self.refuses = log, refuses

    def dispatch(self, args, kwargs):
        self.log.append("dispatch")
        if self.refuses:
            raise SignatureMismatch
        return "state", _Readback(self.log)


@pytest.fixture
def plain_session():
    decoder, params, _ = _llama()
    sess = _session(decoder, params)
    yield sess
    sess.close()


def test_run_asks_for_the_host_copy_before_it_blocks(plain_session):
    log = []
    spans.clear()
    state, out, sp = plain_session._run(
        "easydist.serve.decode.call", _Result(log),
        ("arena", "params", np.zeros((2, 3), np.int32)), rows=2)
    assert log == ["dispatch", "copy_to_host_async", "block_until_ready",
                   "asarray"]
    assert state == "state" and out.tolist() == [0, 1, 2]
    assert sp.attrs["h2d"] == 1 and sp.attrs["rows"] == 2
    assert sp.t0_ns <= sp.attrs["ready_ns"] <= sp.t1_ns
    # not a paged step program's launch: nothing is counted
    assert not any(k.startswith("serve_launches")
                   for k in spans.snapshot()["counters"])
    spans.clear()


def test_a_refusal_of_a_result_nobody_holds_is_the_callers(plain_session):
    with pytest.raises(SignatureMismatch):
        plain_session._run("easydist.serve.decode.call",
                           _Result([], refuses=True), ())


@pytest.mark.parametrize("family", ["llama", "granite"])
def test_a_held_result_of_other_shapes_falls_back_and_resolves_its_own(
        family):
    """Two sessions over one model share its compiled programs and differ
    in their slots and prefill rows: each pool resolves, and holds, its own
    signature's result; and a pool handed the OTHER's (a held result that
    no longer fits) refuses it while tracing, resolves again and serves the
    same tokens."""
    build, programs = FAMILIES[family]
    decoder, params, _ = build()
    waves = _requests(seed=7)[:1]
    key = ("test_launch_path", family)
    spans.clear()
    wide = _session(decoder, params, compile_key=key)
    want = _serve(wide, waves)
    narrow = _session(decoder, params, compile_key=key, max_decode_slots=2,
                      prefill_batch=1)
    assert narrow._paged_cs is wide._paged_cs
    assert _serve(narrow, waves) == want
    counters = spans.snapshot()["counters"]
    (wide_pool,), (narrow_pool,) = wide._pools.values(), \
        narrow._pools.values()
    for fn in programs:
        assert counters[f"serve_launches{{fn={fn},path=resolved}}"] == 2
    assert set(wide_pool.held) == set(narrow_pool.held)
    for name, result in wide_pool.held.items():
        assert result is not narrow_pool.held[name]
    theirs = dict(narrow_pool.held)
    narrow_pool.held.update(wide_pool.held)
    spans.clear()
    assert _serve(narrow, waves) == want
    counters = spans.snapshot()["counters"]
    for fn in programs:
        assert counters[f"serve_launches{{fn={fn},path=resolved}}"] == 1
        assert counters[f"serve_launches{{fn={fn},path=held}}"] >= 3
        # found again by signature: nothing compiled for it
        assert f"xla_compiles{{fn={fn}}}" not in counters
    assert narrow_pool.held == theirs
    spans.clear()
    wide.close()
    narrow.close()


def test_on_a_mesh_of_four_devices_the_operand_is_a_replicated_input(
        cpu_devices):
    """No cell serves on a mesh: this holds that the one operand goes in
    as the table did, and the tokens are the one-device session's."""
    decoder, params, _ = _llama()
    waves = _requests(seed=9)[:1]
    single = _session(decoder, params)
    want = _serve(single, waves)
    single.close()
    mesh = Mesh(np.array(cpu_devices[:4]), ("tp",))
    sess = _session(decoder, params, mesh=mesh)
    spans.clear()
    assert _serve(sess, waves) == want
    snap = spans.snapshot()
    spans.clear()
    sess.close()
    assert {c["attrs"]["h2d"] for c in _calls(snap)} == {1}
    for fn in PLAIN:
        assert snap["counters"][f"serve_launches{{fn={fn},path=resolved}}"] \
            == 1
