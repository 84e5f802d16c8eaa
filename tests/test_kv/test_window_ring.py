"""The ring a window layer keeps a sequence (`models/decoder.py::Ring`,
`ops.window_attention`), the pool that hands its slots out
(`kv/state.py::StatePool` inside the session's `_PagedPool`), and what a
window layer costs: the same bytes at 10 positions and at 10,000, and no
arena page."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.models import exaone_moe as em
from easydist_tpu.models import llama
from easydist_tpu.models.decoder import Contiguous, Paged, Ring, State
from easydist_tpu.ops import window_attention
from easydist_tpu.serve import GenerationSession, ServeConfig

CFG = em.ExaoneMoeConfig.tiny()


def _dense(q, k, v, window):
    """softmax(q k^T / sqrt(d)) v over positions 0..t-1 with i - window < j
    <= i: q [h, t, d], k / v [kvh, t, d]."""
    h, t, d = q.shape
    rep = h // k.shape[0]
    k, v = np.repeat(k, rep, 0), np.repeat(v, rep, 0)
    s = np.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    s = np.where((j <= i) & (j > i - window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("window", [8, 6, 3])
def test_a_ring_in_any_rotation_is_the_window(window):
    """One sequence fed through a `Ring` in chunks of 4 then a row at a
    time, 37 positions (the ring of 8 rows wraps four times): what each
    position attends is the dense window over the whole sequence."""
    dec = em.decoder(em.ExaoneMoeConfig.tiny(
        layer_types=("sliding_attention",), mlp_layer_types=("dense",),
        sliding_window=window))
    h, kvh, d, t, c = dec.heads, dec.kv_heads, dec.head_dim, 37, 4
    rng = np.random.default_rng(window)
    q = rng.standard_normal((h, t, d)).astype(np.float32)
    k = rng.standard_normal((kvh, t, d)).astype(np.float32)
    v = rng.standard_normal((kvh, t, d)).astype(np.float32)
    want = _dense(q, k, v, window)

    rings = Ring.init(dec, 3)
    assert rings["ring_k"][0].shape == (3, kvh, 8, d)
    slot = jnp.asarray([1], jnp.int32)
    got = []
    for start in range(0, 18, c):         # chunks; the last ends at 18 of 20
        n = min(c, 18 - start)
        pos = jnp.asarray([start + np.arange(c)], jnp.int32)
        ring = Ring(rings, slot)
        ring.seek(pos, pos < start + n)
        sl = slice(start, start + c)
        ring.write(jnp.asarray(k[None, :, sl]), jnp.asarray(v[None, :, sl]))
        out = ring.attend(dec, jnp.asarray(q[None, :, sl]), pos)
        got.append(np.asarray(out)[0, :, :n])
        rings = ring.cache()
    for p in range(18, t):                # decode: the rows are the slots
        pos = jnp.asarray([0, p, 0], jnp.int32)
        ring = Ring(rings)
        ring.seek(pos, jnp.asarray([False, True, False]))
        row = lambda a: jnp.zeros((3,) + a.shape[:1] + (d,)).at[1].set(
            a[:, p])
        ring.write(row(k), row(v))
        got.append(np.asarray(ring.attend(dec, row(q), pos))[1][:, None])
        rings = ring.cache()
    np.testing.assert_allclose(np.concatenate(got, 1), want, atol=2e-6)
    # slots 0 and 2 were rows of every round and never live: untouched
    for leaf in rings["ring_k"] + rings["ring_v"]:
        assert not np.asarray(leaf[0]).any() and not np.asarray(leaf[2]).any()


def test_a_key_with_no_position_is_never_seen():
    q = jnp.ones((1, 2, 1, 4))
    k = jnp.ones((1, 1, 3, 4))
    v = jnp.asarray([[[[1.0] * 4, [5.0] * 4, [9.0] * 4]]])
    out = window_attention(q, k, v, jnp.asarray([[7]]),
                           jnp.asarray([[-1, 7, 2]]), 4)
    np.testing.assert_allclose(out, 5.0)   # -1: nothing; 2: outside 4..7


def test_a_window_layers_bytes_do_not_grow_and_pages_are_for_full_layers():
    """The tentpole's claim as shapes: three of the tiny model's four
    layers slide, so the arena has ONE leaf a key, and what a sequence
    holds in the window layers is the same at 10 and at 10,000 positions."""
    dec = em.decoder(CFG)
    assert dec.kv_layers == 1 and dec.ring_windows == (8, 8, 8)
    assert dec.per_sequence and not llama.decoder(
        llama.LlamaConfig.tiny()).per_sequence
    pages = Paged.init(dec, 16, 8)
    assert len(pages["k"]) == 1 == len(pages["v"])
    held = State.init(dec, 4)
    assert sorted(held) == ["ring_k", "ring_v"] and len(held["ring_k"]) == 3
    a_sequence = sum(leaf[0].nbytes for key in Ring.KEYS
                     for leaf in held[key])
    assert a_sequence == 3 * 2 * (2 * 8 * 8) * 4   # layers, k and v, a ring

    token_bytes = 2 * dec.kv_heads * dec.head_dim * 4
    for positions in (10, 10_000):
        n_pages = -(-positions // 8)
        paged = n_pages * sum(leaf[0].nbytes for key in ("k", "v")
                              for leaf in pages[key])
        assert paged == n_pages * 8 * token_bytes * dec.kv_layers
        assert a_sequence == 3 * 8 * token_bytes   # whatever `positions`
    with pytest.raises(ValueError, match="window layers.*contiguous"):
        Contiguous.init(dec, 2, 32)


def test_the_pool_hands_rings_out_with_the_slot_and_counts_pages_for_full():
    params = em.exaone_init(CFG, jax.random.PRNGKey(0))
    sess = GenerationSession(params, model=em.decoder(CFG), config=ServeConfig(
        decode_buckets=(64,), max_decode_slots=3,
        prefill_chunk=8, prefill_batch=2, kv_arena_pages=24,
        enable_prefix_cache=False, speculate_k=0))
    pool = sess._pool_for(64)
    token_bytes = 2 * 2 * 8 * 4
    assert pool.page_bytes == 8 * token_bytes          # ONE full layer
    assert pool.ring_bytes == 3 * 3 * 8 * token_bytes  # slots, layers, ring
    assert sorted(pool.arena) == ["k", "ring_k", "ring_v", "v"]
    futs = [sess.submit(list(range(1, n)), max_new_tokens=4)
            for n in (30, 12)]
    sess.step()
    assert pool.state.in_use == 2       # a ring slot with each table row
    used = {}
    while not all(f.done() for f in futs):
        sess.step()
        g = sess.metrics.snapshot()["gauges"]
        used[g.get("window_ring_slots_in_use")] = g.get("window_ring_bytes")
    assert set(used.values()) == {pool.ring_bytes}
    assert sess.metrics.snapshot()["gauges"]["kv_tokens_live"] > 0
    assert pool.state.in_use == 0 == pool.pool.in_use
    # a 30-token prompt + 4: five pages of the one full layer, never more
    assert pool.pages_needed(29, 4) == 5
    sess.close()
