"""`ops/ssm.py`'s selective (Mamba-1) pair: the chunk scan against the
recurrence written out a position, a channel and a state index at a time
(float64 numpy), at windows of 1, 16, 256 and ones that are no multiple of
the kernel's tiles of positions, over channels that are no multiple of its
channel block; both Pallas kernels under the interpreter against their jnp
forms; positions that do not count and dead rows, bit for bit; a decode
round after a scan against one longer scan; and both kernels cross-lowered
for TPU at the Jamba2 cell's widths (Pallas' own jaxpr -> Mosaic lowering;
Mosaic's compile is `tests/test_kv/test_arena_inplace.py` and the chip's
job)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops.ssm import (_SCAN_LANES, _SCAN_POSITIONS,
                                  _channel_block, selective_chunk_scan,
                                  selective_chunk_scan_xla,
                                  selective_decode_update,
                                  selective_decode_update_xla)


def _inputs(b=2, s=24, e=256, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(x=f(b, s, e),
                dt=jnp.asarray(rng.uniform(0.001, 0.3, (b, s, e)),
                               jnp.float32),
                # a decay of its own for every channel AND state index
                a=-jnp.asarray(rng.uniform(0.5, 16, (n, e)), jnp.float32),
                b_mat=f(b, s, n), c_mat=f(b, s, n),
                d_skip=jnp.asarray(rng.uniform(0.5, 1.5, (e,)), jnp.float32),
                state=f(b, n, e))


def _by_position(i):
    """h[d, n] = exp(dt[d] A[d, n]) h[d, n] + dt[d] x[d] B[n]; y[d] = sum_n
    h[d, n] C[n] + D[d] x[d] — a position at a time, float64."""
    v = {k: np.asarray(a, np.float64) for k, a in i.items()}
    b, s, e = v["x"].shape
    h = v["state"].transpose(0, 2, 1).copy()             # [b, e, n]
    a = v["a"].T                                          # [e, n]
    y = np.zeros((b, s, e))
    for t in range(s):
        dt, x = v["dt"][:, t], v["x"][:, t]
        h = np.exp(dt[:, :, None] * a) * h \
            + (dt * x)[:, :, None] * v["b_mat"][:, t, None, :]
        y[:, t] = (h * v["c_mat"][:, t, None, :]).sum(-1) + v["d_skip"] * x
    return y, h.transpose(0, 2, 1)


def _call(fn, i, **kw):
    return fn(i["x"], i["dt"], i["a"], i["b_mat"], i["c_mat"], i["d_skip"],
              i["state"], **kw)


# 40 and 300: no multiple of the kernel's 8 positions a tile (windows up to
# 128) or of its 128 (longer ones); 640 and 384 channels: blocks of 128, no
# multiple of the block of 512 that Jamba's 5,120 take; 48: no lane tile
# (the unrolled jnp form serves windows of tens: the long ones are the
# kernel's alone)
WINDOWS = [(1, 256), (16, 256), (40, 384), (5, 48)]


@pytest.mark.parametrize("form,s,e", [("jnp", s, e) for s, e in WINDOWS] + [
    ("pallas", s, e) for s, e in WINDOWS + [(256, 640), (300, 128)]])
def test_the_scan_is_the_recurrence_a_position_at_a_time(form, s, e):
    i = _inputs(s=s, e=e)
    want_y, want_state = _by_position(i)
    y, state = _call(selective_chunk_scan, i, interpret=True,
                     backend="pallas") if form == "pallas" \
        else _call(selective_chunk_scan_xla, i)
    assert y.shape == i["x"].shape and state.shape == i["state"].shape
    assert y.dtype == state.dtype == jnp.float32
    # float32 against float64 over up to 300 positions of a carried state
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-5)


def test_the_tiles_and_blocks_the_kernel_takes():
    assert (_SCAN_POSITIONS, _SCAN_LANES) == (8, 128)
    # Jamba2's 5,120 channels: ten blocks of 512 ([16, 512] float32 is 8
    # vector registers of state); a decode round a row's whole state
    assert _channel_block(5120) == 512
    assert _channel_block(5120, most=2 ** 20 // 64) == 5120
    assert _channel_block(640) == 128 and _channel_block(384) == 384
    assert _channel_block(1024) == 512 and _channel_block(128) == 128
    assert _channel_block(48) == 48 and _channel_block(200) == 200


def test_two_windows_back_to_back_are_one():
    i = _inputs(s=32)
    y, state = _call(selective_chunk_scan, i, interpret=True,
                     backend="pallas")
    cut = {k: v[:, :16] if k in ("x", "dt", "b_mat", "c_mat") else v
           for k, v in i.items()}
    y0, mid = _call(selective_chunk_scan, cut, interpret=True,
                    backend="pallas")
    rest = {k: v[:, 16:] if k in ("x", "dt", "b_mat", "c_mat") else v
            for k, v in i.items()}
    rest["state"] = mid
    y1, end = _call(selective_chunk_scan, rest, interpret=True,
                    backend="pallas")
    # the same operations on the same numbers in the same order
    np.testing.assert_array_equal(jnp.concatenate([y0, y1], axis=1), y)
    np.testing.assert_array_equal(end, state)


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_positions_that_do_not_count_leave_the_state_bit_identical(form):
    i = _inputs(s=24)
    kw = dict(interpret=True, backend="pallas") if form == "pallas" \
        else dict(backend="xla")
    i["dt"] = i["dt"].at[:, 10:].set(0.0)      # 10 real positions, 14 padded
    i["dt"] = i["dt"].at[1].set(0.0)           # and a row with none at all
    _, state = _call(selective_chunk_scan, i, **kw)
    short = {k: v[:, :10] if k in ("x", "dt", "b_mat", "c_mat") else v
             for k, v in i.items()}
    _, want = _call(selective_chunk_scan, short, **kw)
    np.testing.assert_array_equal(state, want)
    np.testing.assert_array_equal(state[1], i["state"][1])


@pytest.mark.parametrize("e", [256, 5120 // 4])
def test_the_decode_kernel_is_its_jnp_form_and_one_step_of_the_scan(e):
    i = _inputs(b=5, s=1, e=e)
    args = (i["state"], i["x"][:, 0], i["dt"][:, 0], i["a"],
            i["b_mat"][:, 0], i["c_mat"][:, 0], i["d_skip"])
    want_state, want_y = selective_decode_update_xla(*args)
    state, y = selective_decode_update(*args, interpret=True,
                                       backend="pallas")
    np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    y_scan, state_scan = _call(selective_chunk_scan, i, interpret=True,
                               backend="pallas")
    np.testing.assert_allclose(state, state_scan, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, y_scan[:, 0], rtol=1e-5, atol=1e-5)


def test_decode_after_a_scan_is_one_longer_scan():
    i = _inputs(s=17)
    y, state = _call(selective_chunk_scan, i, interpret=True,
                     backend="pallas")
    head = {k: v[:, :16] if k in ("x", "dt", "b_mat", "c_mat") else v
            for k, v in i.items()}
    _, mid = _call(selective_chunk_scan, head, interpret=True,
                   backend="pallas")
    end, last = selective_decode_update(
        mid, i["x"][:, 16], i["dt"][:, 16], i["a"], i["b_mat"][:, 16],
        i["c_mat"][:, 16], i["d_skip"], interpret=True, backend="pallas")
    np.testing.assert_allclose(end, state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(last, y[:, 16], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 0], [0, 0, 1, 0, 1, 1],
                                  [0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1]],
                         ids=["dead-among-live", "dead-first", "all-dead",
                              "all-live"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_the_decode_kernel_neither_reads_nor_writes_a_dead_rows_state(
        backend, live):
    """A dead row's state is NaN here: the kernel never reads it (its y is
    0, no NaN reaches a live row) and hands it back as it was."""
    i = _inputs(b=6, s=1)
    live = jnp.asarray(live, bool)
    state = jnp.where(live[:, None, None], i["state"], jnp.nan) \
        if backend == "pallas" else i["state"]
    dt = jnp.where(live[:, None], i["dt"][:, 0], 0.0)
    new, y = selective_decode_update(
        state, i["x"][:, 0], dt, i["a"], i["b_mat"][:, 0], i["c_mat"][:, 0],
        i["d_skip"], live=live, interpret=True, backend=backend)
    want_state, want_y = selective_decode_update_xla(
        i["state"], i["x"][:, 0], dt, i["a"], i["b_mat"][:, 0],
        i["c_mat"][:, 0], i["d_skip"])
    alive = np.asarray(live)
    np.testing.assert_allclose(np.asarray(new)[alive],
                               np.asarray(want_state)[alive], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[alive],
                               np.asarray(want_y)[alive], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(y)[~alive].any()
    np.testing.assert_array_equal(np.asarray(new)[~alive],
                                  np.asarray(state)[~alive])


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _aval(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_both_kernels_lower_for_a_tpu_at_the_cells_widths():
    """Jamba2-3B: 5,120 channels, 16 state indices; a chunk call of two
    rows of 256 positions, a decode round of 128 slots.  ONE custom call
    each, the state leaf handed to it once and aliased to its result."""
    e, n = 5120, 16
    text = _lower_for_tpu(
        lambda *a: selective_chunk_scan(*a, interpret=False,
                                        backend="pallas"),
        _aval(2, 256, e), _aval(2, 256, e), _aval(n, e), _aval(2, 256, n),
        _aval(2, 256, n), _aval(e), _aval(2, n, e)).as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "selective_chunk_scan" in text
    assert call.count(f"tensor<2x{n}x{e}xf32>") >= 2
    assert "output_operand_aliases" in call or "operand_index" in text
    text = _lower_for_tpu(
        lambda s, x, dt, a, b, c, d, live: selective_decode_update(
            s, x, dt, a, b, c, d, live=live, interpret=False,
            backend="pallas"),
        _aval(128, n, e), _aval(128, e), _aval(128, e), _aval(n, e),
        _aval(128, n), _aval(128, n), _aval(e),
        _aval(128, dtype=jnp.bool_)).as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "selective_decode_update" in text
    assert call.count(f"tensor<128x{n}x{e}xf32>") >= 2


@pytest.mark.parametrize("kernel", ["scan", "decode"])
def test_a_models_selective_layers_share_one_kernel(kernel):
    """A 26-layer program holds 26 `pallas_call` equations of ONE kernel
    whose `jaxpr` and grid mapping are ONE object each: the body (128
    unrolled positions of the scan) was traced once, and jax lowers equal
    equations once a module."""
    from easydist_tpu.ops import ssm

    layers, e, n = 26, 5120, 16
    rows, s = (2, 256) if kernel == "scan" else (128, 1)
    build = ssm._selective_scan_call if kernel == "scan" \
        else ssm._selective_decode_call

    def program(states, x, dt, a, b_mat, c_mat, d):
        if kernel == "scan":
            return [selective_chunk_scan(x, dt, a, b_mat, c_mat, d, st,
                                         interpret=False, backend="pallas")
                    for st in states]
        return [selective_decode_update(st, x[:, 0], dt[:, 0], a,
                                        b_mat[:, 0], c_mat[:, 0], d,
                                        interpret=False, backend="pallas")
                for st in states]

    build.cache_clear()
    closed = jax.make_jaxpr(program)(
        [_aval(rows, n, e)] * layers, _aval(rows, s, e), _aval(rows, s, e),
        _aval(n, e), _aval(rows, s, n), _aval(rows, s, n), _aval(e))
    calls = [q for q in closed.jaxpr.eqns if q.primitive.name == "pallas_call"]
    assert len(calls) == layers
    assert {q.params["name"] for q in calls} == {
        "selective_chunk_scan" if kernel == "scan"
        else "selective_decode_update"}
    assert len({id(q.params["jaxpr"]) for q in calls}) == 1
    assert len({id(q.params["grid_mapping"]) for q in calls}) == 1
    assert build.cache_info().misses == 1
