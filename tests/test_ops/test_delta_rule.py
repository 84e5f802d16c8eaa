"""`ops/delta_rule.py`: the chunked scan against the recurrence a position at
a time (float64, written here), whatever the block, with a carried state,
with beta in (1, 2) and keys that repeat; positions that do not count; the
decode kernel under the interpreter against its jnp form and one step of the
scan; the packed layout of the stored state; and the kernel cross-lowered
for a TPU at the published widths (30 heads, 96 x 192, 40 slots), beside the
paged attention kernels at 30 KV heads of 128 with ONE query row a KV head,
which no cell had."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops import delta_rule as dr
from easydist_tpu.ops.flash_attention import (flash_paged_chunk_attention,
                                              flash_paged_decode_attention)
from easydist_tpu.ops.ssm import causal_conv_tail

F32 = jnp.float32


def _recurrence(q, k, v, g, beta, state):
    """S = a S + beta (v - a S k) k^T, o = S q, a position at a time, in
    float64, on S [b, h, d_v, d_k] as the equations write it."""
    q, k, v, g, beta, st = (np.asarray(x, np.float64)
                            for x in (q, k, v, g, beta, state))
    outs = []
    for t in range(q.shape[1]):
        st = st * np.exp(g[:, t])[:, :, None, None]
        seen = np.einsum("bhvd,bhd->bhv", st, k[:, t])
        st = st + (beta[:, t][:, :, None] * (v[:, t] - seen))[..., None] \
            * k[:, t][:, :, None, :]
        outs.append(np.einsum("bhvd,bhd->bhv", st, q[:, t]))
    return np.stack(outs, axis=1), st


def _inputs(seed, b=2, s=100, h=4, d_k=8, d_v=64, beta_range=(1.0, 2.0)):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, s, h, d_k))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k[:, 10:20] = k[:, 9:10]          # a key that repeats: T far from I
    q = rng.normal(size=(b, s, h, d_k)) / np.sqrt(d_k)
    v = rng.normal(size=(b, s, h, d_v))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(1.6), size=(b, s, h)))
    beta = rng.uniform(*beta_range, size=(b, s, h))
    state = rng.normal(size=(b, h, d_v, d_k))
    return tuple(jnp.asarray(x, F32) for x in (q, k, v, g, beta, state))


def _stored(state, pack):
    """[b, h, d_v, d_k] as the equations write it -> as the op stores it."""
    return dr.pack_state(jnp.swapaxes(state, -1, -2), pack)


def _written(stored, pack):
    return np.swapaxes(np.asarray(dr.unpack_state(stored, pack)), -1, -2)


@pytest.mark.parametrize("d_v,pack", [(64, 2), (16, 1), (32, 4)])
def test_the_stored_state_packs_heads_to_whole_lanes(d_v, pack):
    assert dr.state_pack(4, d_v) == pack
    assert dr.state_pack(30, 192) == 2 and (2 * 192) % 128 == 0
    s = jnp.arange(2 * 4 * 8 * d_v, dtype=F32).reshape(2, 4, 8, d_v)
    packed = dr.pack_state(s, pack)
    assert packed.shape == (2, 4 // pack, 8, pack * d_v)
    # head h's columns lie at lanes (h % pack) * d_v of row h // pack
    np.testing.assert_array_equal(packed[:, 1 // pack, :,
                                         (1 % pack) * d_v:][..., :d_v],
                                  s[:, 1])
    np.testing.assert_array_equal(dr.unpack_state(packed, pack), s)


@pytest.mark.parametrize("block", [1, 16, 64, 7, 128])
def test_the_chunk_scan_is_the_recurrence_whatever_the_block(block):
    # 100 positions: no multiple, and fewer than a block of 128 (one block
    # of 100 then, nothing padded)
    q, k, v, g, beta, state = _inputs(0)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    o, st = dr.delta_chunk_scan(q, k, v, g, beta, _stored(state, 2),
                                block=block)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(_written(st, 2), want_s, atol=2e-5)


@pytest.mark.parametrize("d_v", [16, 64])
def test_two_windows_back_to_back_are_one(d_v):
    q, k, v, g, beta, state = _inputs(1, s=48, d_v=d_v)
    pack = dr.state_pack(4, d_v)
    whole_o, whole_s = dr.delta_chunk_scan(q, k, v, g, beta,
                                           _stored(state, pack), block=16)
    cut = 20
    o1, s1 = dr.delta_chunk_scan(*(x[:, :cut] for x in (q, k, v, g, beta)),
                                 _stored(state, pack), block=16)
    o2, s2 = dr.delta_chunk_scan(*(x[:, cut:] for x in (q, k, v, g, beta)),
                                 s1, block=16)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o,
                               atol=2e-5)
    np.testing.assert_allclose(s2, whole_s, atol=2e-5)


def test_positions_that_do_not_count_leave_the_state_bit_identical():
    q, k, v, g, beta, state = _inputs(2, s=24)
    stored = _stored(state, 2)
    zero = jnp.zeros_like(g)
    # a window with none that counts: the state as it was, bit for bit
    _, same = dr.delta_chunk_scan(q, k, v, zero, zero, stored, block=8)
    np.testing.assert_array_equal(same, stored)
    # a prefix of 10 counts: the state after those 10, the rest nothing
    counts = (jnp.arange(24) < 10)[None, :, None]
    _, part = dr.delta_chunk_scan(q, k, v, jnp.where(counts, g, 0.0),
                                  jnp.where(counts, beta, 0.0), stored,
                                  block=8)
    _, want = _recurrence(*(x[:, :10] for x in (q, k, v, g, beta)), state)
    np.testing.assert_allclose(_written(part, 2), want, atol=2e-5)
    # and the conv's tail with it
    # (flat: the three inputs side by side, oldest first)
    tail = jnp.asarray(np.random.default_rng(3).normal(size=(2, 3 * 5)), F32)
    x = jnp.ones((2, 6, 5), F32)
    _, kept = causal_conv_tail(tail, x, jnp.ones((4, 5), F32), None,
                               jnp.zeros((2, 6), bool))
    assert kept.shape == (2, 3 * 5)
    np.testing.assert_array_equal(kept, tail)
    _, moved = causal_conv_tail(tail, x, jnp.ones((4, 5), F32), None,
                                jnp.arange(6)[None, :] < jnp.array([[2], [6]]))
    np.testing.assert_array_equal(moved[0], jnp.concatenate(
        [tail[0, 2 * 5:], x[0, :2].reshape(-1)]))
    np.testing.assert_array_equal(moved[1], x[1, 3:].reshape(-1))


@pytest.mark.parametrize("d_v", [16, 64])
def test_the_decode_kernel_is_its_jnp_form_and_one_step_of_the_scan(d_v):
    q, k, v, g, beta, state = _inputs(4, b=5, s=1, d_v=d_v)
    assert float(beta.min()) > 1.0            # the negative eigenvalues
    pack = dr.state_pack(4, d_v)
    stored = _stored(state, pack)
    live = jnp.array([False, True, True, False, True])
    one = [x[:, 0] for x in (q, k, v)] + [
        jnp.where(live[:, None], x[:, 0], 0.0) for x in (g, beta)]
    new_k, o_k = dr.delta_decode_update(stored, *one, live=live,
                                        backend="pallas", interpret=True)
    new_x, o_x = dr.delta_decode_update(stored, *one, live=live,
                                        backend="xla")
    np.testing.assert_allclose(new_k, new_x, atol=1e-6)
    np.testing.assert_allclose(o_k, o_x, atol=1e-6)
    o_s, new_s = dr.delta_chunk_scan(q, k, v, one[3][:, None], one[4][:, None],
                                     stored, block=1)
    np.testing.assert_allclose(new_k, new_s, atol=2e-6)
    np.testing.assert_allclose(o_k[live], o_s[:, 0][live], atol=2e-6)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    np.testing.assert_allclose(_written(new_k, pack)[live], want_s[live],
                               atol=2e-6)
    np.testing.assert_allclose(o_k[live], want_o[:, 0][live], atol=2e-6)
    # a dead row: neither read nor written, and its o is 0
    for new in (new_k, new_x):
        np.testing.assert_array_equal(new[~live], stored[~live])
    np.testing.assert_array_equal(o_k[~live], 0.0)
    # no row live: everything as it was
    none = jnp.zeros((5,), bool)
    kept, o = dr.delta_decode_update(
        stored, *one[:3], jnp.zeros_like(one[3]), jnp.zeros_like(one[4]),
        live=none, backend="pallas", interpret=True)
    np.testing.assert_array_equal(kept, stored)
    np.testing.assert_array_equal(o, 0.0)


def test_the_blocks_of_a_grid_step_fit_fast_memory():
    # the published widths: 15 packs of [96, 384] float32 are 2.2 MB; read
    # and written and double-buffered, 5 a step are 2.9 MB
    assert dr._packs_per_step(15, 96, 384) == 5
    assert dr._packs_per_step(2, 8, 128) == 2
    assert 4 * 5 * 96 * 384 * 4 <= 4 * 2 ** 20 < 4 * 15 * 96 * 384 * 4


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _aval(shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("slots", [40, 1])
def test_the_decode_kernel_lowers_for_a_tpu_at_the_published_widths(slots):
    h, d_k, d_v = 30, 96, 192
    lowered = _lower_for_tpu(
        functools.partial(dr.delta_decode_update, backend="pallas",
                          interpret=False),
        _aval((slots, 15, d_k, 2 * d_v)), _aval((slots, h, d_k)),
        _aval((slots, h, d_k)), _aval((slots, h, d_v)), _aval((slots, h)),
        _aval((slots, h)), _aval((slots,), jnp.bool_))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "delta_decode_update" in text


def test_the_paged_kernels_lower_at_thirty_kv_heads_of_one_query_row():
    bf16 = jnp.bfloat16
    pages = _aval((288, 30, 256, 128), bf16)
    _lower_for_tpu(
        lambda q, k, v, t, n: flash_paged_decode_attention(
            q, k, v, t, n, interpret=False),
        _aval((40, 30, 128), bf16), pages, pages,
        _aval((40, 16), jnp.int32), _aval((40,), jnp.int32))
    _lower_for_tpu(
        lambda q, k, v, t, n: flash_paged_chunk_attention(
            q, k, v, t, n, interpret=False),
        _aval((1, 30, 256, 128), bf16), pages, pages,
        _aval((1, 16), jnp.int32), _aval((1,), jnp.int32))
