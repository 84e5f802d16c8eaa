"""`ops/ssm.py::causal_conv_tail` with the tail carried FLAT, [b, (taps - 1)
* channels], against a frozen copy of the body it replaced (the tail as
[b, taps - 1, channels], a concatenate and a gather along the rows): the
same BITS, outputs and tail — the products are summed in the same order —
for one position a row (a decode round: lane slices and a select, no
gather), a short window and a whole chunk, with and without the bias, at
channel counts that are and are not whole 128-lane tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops.ssm import causal_conv_tail

F32 = jnp.float32
TAPS, ROWS = 4, 3


def _frozen(tail, x, w, bias, valid):
    """The body as it was at the commit before the tail lay flat."""
    s, taps = x.shape[1], w.shape[0]
    full = jnp.concatenate([tail, x], axis=1)                # [b, s+taps-1, c]
    w = w.astype(jnp.float32)
    conv = sum(full[:, j:j + s] * w[j] for j in range(taps))
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    out = jax.nn.silu(conv)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    new_tail = jnp.take_along_axis(
        full, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    return out, new_tail


def _case(s, c, with_bias, counted):
    """Three rows whose valid prefixes are `counted` of the window: none,
    some (one of each on the rows, where the window has room) or all."""
    rng = np.random.default_rng([s, c, with_bias])
    tail = jnp.asarray(rng.normal(size=(ROWS, TAPS - 1, c)), F32)
    x = jnp.asarray(rng.normal(size=(ROWS, s, c)), F32)
    w = jnp.asarray(rng.normal(size=(TAPS, c)), F32)
    bias = jnp.asarray(rng.normal(size=(c,)), F32) if with_bias else None
    n = {"none": [0, 0, 0], "all": [s, s, s],
         "some": [min(s, 1), s // 2, 0]}[counted]
    valid = jnp.arange(s)[None, :] < jnp.asarray(n)[:, None]
    return tail, x, w, bias, valid


@pytest.mark.parametrize("counted", ["none", "some", "all"])
@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("c", [64, 320, 256])
@pytest.mark.parametrize("s", [1, 5, 256], ids=["one", "window-5", "chunk"])
def test_the_flat_tail_gives_the_bits_of_the_tail_in_rows(s, c, with_bias,
                                                          counted):
    tail, x, w, bias, valid = _case(s, c, with_bias, counted)
    flat = tail.reshape(ROWS, (TAPS - 1) * c)
    # eager against eager and compiled against compiled: a compiled
    # multiply-and-add is one fused operation on the CPU, in either body
    for run in (lambda f: f, jax.jit):
        want_out, want_tail = run(_frozen)(tail, x, w, bias, valid)
        out, new = run(causal_conv_tail)(flat, x, w, bias, valid)
        assert out.dtype == new.dtype == F32
        assert new.shape == flat.shape
        np.testing.assert_array_equal(out, want_out)
        np.testing.assert_array_equal(new.reshape(want_tail.shape),
                                      want_tail)
        # a row with no valid position keeps its tail, bit for bit
        kept = np.flatnonzero(~np.asarray(valid).any(axis=1))
        np.testing.assert_array_equal(np.asarray(new)[kept],
                                      np.asarray(flat)[kept])


def test_one_position_a_row_gathers_nothing():
    """The decode round's form is slices, a concatenate and a select: a
    gather along the tail is what made a v5e re-lay the whole leaf out."""
    tail, x, w, bias, valid = _case(1, 256, True, "some")
    flat = tail.reshape(ROWS, -1)
    names = {e.primitive.name for e in
             jax.make_jaxpr(causal_conv_tail)(flat, x, w, bias, valid).eqns}
    assert not names & {"gather", "scatter", "transpose", "reshape",
                        "dynamic_slice"}, names


# ---- `activation`: silu unless told otherwise; None gives the conv itself
# (a gated short convolution, `models/lfm2_moe.py`, applies nothing to it)


def _plain_conv(tail, x, w):
    """A causal depthwise conv over [tail | x], a position and a tap at a
    time, in numpy: out[t] = sum_j w[j] * full[t + j]."""
    full = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    s, taps = x.shape[1], w.shape[0]
    out = np.zeros(x.shape, np.float64)
    for t in range(s):
        for j in range(taps):
            out[:, t] += np.asarray(w)[j] * full[:, t + j]
    return out, full


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("s", [1, 5, 64], ids=["one", "window-5", "chunk"])
def test_no_activation_is_a_plain_conv(s, taps):
    rng = np.random.default_rng([s, taps])
    c = 128
    tail = jnp.asarray(rng.normal(size=(ROWS, taps - 1, c)), F32)
    x = jnp.asarray(rng.normal(size=(ROWS, s, c)), F32)
    w = jnp.asarray(rng.normal(size=(taps, c)), F32)
    n = jnp.asarray([s, s // 2, 0])
    valid = jnp.arange(s)[None, :] < n[:, None]
    out, new_tail = causal_conv_tail(tail.reshape(ROWS, -1), x, w, None,
                                     valid, activation=None)
    want, full = _plain_conv(tail, x, w)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    # the tail after each row's valid prefix: the last taps - 1 inputs
    for r in range(ROWS):
        k = int(n[r])
        np.testing.assert_array_equal(
            np.asarray(new_tail[r]).reshape(taps - 1, c),
            full[r, k:k + taps - 1])
    # the default is what it was: silu of that conv, bit for bit the
    # explicit activation's
    silu, same_tail = causal_conv_tail(tail.reshape(ROWS, -1), x, w, None,
                                       valid)
    np.testing.assert_array_equal(
        np.asarray(silu), np.asarray(jax.nn.silu(out)))
    np.testing.assert_array_equal(np.asarray(same_tail),
                                  np.asarray(new_tail))
