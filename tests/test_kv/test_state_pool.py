"""The recurrent-state pool beside the page pool: the host's book
(`kv/state.py`), the device adapter (`models/decoder.py::State`), and the
session's one manager — admission takes a state slot and pages together,
retirement gives both back, the gauges report both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.kv import StatePool
from easydist_tpu.models import granite_hybrid as gh
from easydist_tpu.models.decoder import State
from easydist_tpu.serve import GenerationSession, ServeConfig

CFG = gh.GraniteHybridConfig.tiny()


def test_state_pool_keeps_the_book():
    pool = StatePool(3)
    assert pool.sentinel == 3 and pool.in_use == 0
    pool.take(2)
    pool.take(0)
    assert pool.in_use == 2
    with pytest.raises(ValueError, match="held"):
        pool.take(2)
    with pytest.raises(ValueError, match="out of range"):
        pool.take(3)
    pool.check_invariants([0, 2])
    with pytest.raises(AssertionError, match="state slots held"):
        pool.check_invariants([0])
    pool.release(2)
    with pytest.raises(ValueError, match="not held"):
        pool.release(2)
    assert pool.in_use == 1
    with pytest.raises(ValueError):
        StatePool(0)


def test_state_adapter_reads_clips_zeroes_and_drops():
    dec = gh.decoder(CFG)
    state = State.init(dec, 4)
    assert sorted(state) == ["conv", "ssm"]
    assert len(state["ssm"]) == dec.state_layers == 2
    assert state["ssm"][0].shape == (4, 4, 8, 16)
    assert state["conv"][0].shape == (4, 3 * (32 + 2 * 16))
    state = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape) + 1,
        state)
    slots = jnp.asarray([2, 4, 0], jnp.int32)      # row 1 is no sequence
    st = State(state, slots < 4, slots, fresh=jnp.asarray([False, False,
                                                           True]))
    assert st.live.tolist() == [True, False, True]
    for layer in range(2):
        carry = st.read()
        np.testing.assert_array_equal(carry["ssm"][0],
                                      state["ssm"][layer][2])
        np.testing.assert_array_equal(carry["ssm"][1],
                                      state["ssm"][layer][3])   # clipped
        assert not np.asarray(carry["ssm"][2]).any()            # fresh
        st.write({k: v + 1000 for k, v in carry.items()})
    new = st.cache()
    for name in state:
        for old, leaf in zip(state[name], new[name]):
            np.testing.assert_array_equal(leaf[2], old[2] + 1000)
            np.testing.assert_array_equal(leaf[0], np.full_like(old[0],
                                                                1000))
            np.testing.assert_array_equal(leaf[1], old[1])   # untouched
            np.testing.assert_array_equal(leaf[3], old[3])   # write dropped
    # rows that ARE the slots: the carry is the leaf itself
    whole = State(state, jnp.ones((4,), bool))
    assert whole.read()["ssm"] is state["ssm"][0]


def test_admission_takes_a_slot_and_pages_together_and_retirement_frees_both():
    params = gh.granite_init(CFG, jax.random.PRNGKey(0))
    sess = GenerationSession(params, model=gh.decoder(CFG), config=ServeConfig(
        decode_buckets=(32,), max_decode_slots=2,
        prefill_chunk=8, prefill_batch=2, kv_arena_pages=8,
        enable_prefix_cache=False, speculate_k=0))
    rng = np.random.default_rng(0)
    futs = [sess.submit(rng.integers(1, 96, size=n).tolist(),
                        max_new_tokens=m)
            for n, m in ((9, 6), (12, 9), (5, 4))]
    seen = []
    for _ in range(200):
        if all(f.done() for f in futs):
            break
        sess.step()
        pool = next(iter(sess._pools.values()))
        held = pool.state.in_use
        sequences = len(pool.slots) + len(pool.jobs)
        assert held == sequences == pool.n_slots - len(pool.free)
        # every sequence holds the pages it can ever touch, and no other
        # page is out
        mapped = sum(len(pool.table.mapped(i)) for i in
                     list(pool.slots) + [j.slot_idx
                                         for j in pool.jobs.values()])
        assert pool.pool.in_use == mapped
        assert (held == 0) == (pool.pool.in_use == 0)
        seen.append(held)
        gauges = sess.metrics.snapshot()["gauges"]
        if "state_slots_in_use" in gauges:
            assert gauges["state_slots"] == 2
            assert 0 <= gauges["state_slots_in_use"] <= 2
    assert all(f.done() for f in futs) and max(seen) == 2 and seen[-1] == 0
    # two slots, three requests: the third waited for a slot, not for pages
    assert [len(f.result()["ids"]) for f in futs] == [6, 9, 4]
    leaves = pool.arena
    assert sorted(leaves) == ["conv", "k", "ssm", "v"]
    assert len(leaves["k"]) == 1 and len(leaves["ssm"]) == 2
    assert pool.page_bytes == sum(int(x.nbytes) // 8
                                  for name in ("k", "v")
                                  for x in leaves[name])
    sess.close()


def test_evacuation_gives_state_slots_back():
    params = gh.granite_init(CFG, jax.random.PRNGKey(0))
    sess = GenerationSession(params, model=gh.decoder(CFG), config=ServeConfig(
        decode_buckets=(32,), max_decode_slots=2,
        prefill_chunk=8, prefill_batch=2, enable_prefix_cache=False,
        speculate_k=0))
    sess.submit(list(range(1, 20)), max_new_tokens=8)
    sess.submit(list(range(1, 7)), max_new_tokens=8)
    sess.step()
    pool = next(iter(sess._pools.values()))
    assert pool.state.in_use == 2
    out = sess.evacuate()
    assert len(out) == 2 and pool.state.in_use == 0
    assert pool.pool.in_use == 0 and len(pool.free) == 2
