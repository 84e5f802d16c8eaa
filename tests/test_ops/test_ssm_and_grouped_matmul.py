"""`ops/ssm.py` and `ops/grouped_matmul.py`: the chunked scan against the
one-position recurrence, both Pallas kernels under the interpreter against
their jnp forms, the blocked layout's bookkeeping, and both kernels
cross-lowered for TPU at the Granite 4.0-H cell's widths (Pallas' own jaxpr
-> Mosaic lowering; Mosaic's compile is `tests/test_kv/test_arena_inplace.py`
and the chip's job)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops.grouped_matmul import (_tile, group_rows,
                                             grouped_matmul)
from easydist_tpu.ops.ssm import (_heads_per_step, ssd_chunk_scan,
                                  ssm_decode_update, ssm_decode_update_xla)


def _ssm_inputs(b=2, s=24, h=4, p=8, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(x=f(b, s, h, p),
                dt=jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32),
                a=-jnp.asarray(rng.uniform(1, 4, (h,)), jnp.float32),
                b_mat=f(b, s, n), c_mat=f(b, s, n),
                d_skip=jnp.asarray(rng.uniform(0.5, 1.5, (h,)), jnp.float32),
                state=f(b, h, p, n))


def _sequential(i):
    state, ys = i["state"], []
    for t in range(i["x"].shape[1]):
        state, y = ssm_decode_update_xla(
            state, i["x"][:, t], i["dt"][:, t], i["a"], i["b_mat"][:, t],
            i["c_mat"][:, t], i["d_skip"])
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("block", [7, 8, 16, 24, 256])
def test_the_chunked_scan_is_the_recurrence_whatever_the_block(block):
    i = _ssm_inputs()
    want_y, want_state = _sequential(i)
    y, state = ssd_chunk_scan(i["x"], i["dt"], i["a"], i["b_mat"],
                              i["c_mat"], i["d_skip"], i["state"],
                              block=block)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-6)


def test_positions_whose_dt_is_zero_leave_the_state_bit_identical():
    i = _ssm_inputs()
    dt = i["dt"].at[:, 10:].set(0.0)       # 10 real positions, 14 padded
    _, state = ssd_chunk_scan(i["x"], dt, i["a"], i["b_mat"], i["c_mat"],
                              i["d_skip"], i["state"], block=8)
    _, short = ssd_chunk_scan(i["x"][:, :10], dt[:, :10], i["a"],
                              i["b_mat"][:, :10], i["c_mat"][:, :10],
                              i["d_skip"], i["state"], block=8)
    np.testing.assert_allclose(state, short, rtol=1e-6, atol=1e-7)
    for backend in ("xla", "pallas"):
        same, _ = ssm_decode_update(
            i["state"], i["x"][:, 0], jnp.zeros_like(i["dt"][:, 0]), i["a"],
            i["b_mat"][:, 0], i["c_mat"][:, 0], i["d_skip"],
            backend=backend, interpret=True)
        np.testing.assert_array_equal(same, i["state"])


def test_the_decode_kernel_is_the_jnp_update():
    i = _ssm_inputs(b=3, h=16, p=8, n=128)
    args = (i["state"], i["x"][:, 0], i["dt"][:, 0], i["a"],
            i["b_mat"][:, 0], i["c_mat"][:, 0], i["d_skip"])
    want_state, want_y = ssm_decode_update_xla(*args)
    state, y = ssm_decode_update(*args, backend="pallas", interpret=True)
    np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    # the cell's widths: 32 heads a grid step, a 1 MiB state block
    assert _heads_per_step(128, 64, 128) == 32
    assert _heads_per_step(4, 8, 16) == 4


@pytest.mark.parametrize("tm", [8, 16])
def test_group_rows_lays_every_held_row_in_its_experts_blocks(tm):
    rng = np.random.default_rng(1)
    n_experts, rows = 5, 61
    expert = rng.integers(0, n_experts + 1, size=rows)   # 5 = not held
    expert[expert == 3] = 0                              # expert 3 gets none
    g = group_rows(jnp.asarray(expert, jnp.int32), n_experts, tm)
    sizes = np.bincount(expert, minlength=n_experts + 1)[:n_experts]
    np.testing.assert_array_equal(g.sizes, sizes)
    assert int(g.live_blocks) == sum(-(-s // tm) for s in sizes)
    n_blocks = -(-rows // tm) + n_experts
    assert g.block_expert.shape == (n_blocks,)
    dest, source = np.asarray(g.dest), np.asarray(g.source)
    held = expert < n_experts
    assert (dest[~held] == n_blocks * tm).all()
    assert len(set(dest[held])) == held.sum()            # no two rows share
    np.testing.assert_array_equal(source[dest[held]], np.nonzero(held)[0])
    # every held row sits in a live block of ITS expert
    block = dest[held] // tm
    assert (block < int(g.live_blocks)).all()
    np.testing.assert_array_equal(np.asarray(g.block_expert)[block],
                                  expert[held])
    # dead blocks repeat the last live block's expert: nothing new to read
    live = int(g.live_blocks)
    assert (np.asarray(g.block_expert)[live:]
            == np.asarray(g.block_expert)[live - 1]).all()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("none_held", [False, True])
def test_grouped_matmul_multiplies_each_row_by_its_experts_weights(
        backend, none_held):
    rng = np.random.default_rng(2)
    rows, n_experts, k, n, tm = 50, 4, 256, 384, 8
    expert = np.full(rows, n_experts) if none_held \
        else rng.integers(0, n_experts + 1, size=rows)
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n_experts, k, n)), jnp.float32)
    g = group_rows(jnp.asarray(expert, jnp.int32), n_experts, tm)
    blocked = jnp.take(x, g.source, axis=0, mode="clip")
    out = grouped_matmul(blocked, w, g.block_expert, g.live_blocks, tm,
                         backend=backend, interpret=True)
    assert out.shape == (blocked.shape[0], n)
    held = expert < n_experts
    got = np.asarray(jnp.take(out, g.dest, axis=0, mode="clip"))[held]
    want = np.einsum("rk,rkn->rn", np.asarray(x)[held],
                     np.asarray(w)[expert[held]])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_tiles_divide_and_fit():
    assert _tile(1536, 2048) == 1536 and _tile(4096, 2048) == 2048
    assert _tile(4096, 1024) == 1024 and _tile(768, 768) == 768
    assert _tile(1000, 512) == 1000          # nothing divides: whole


@pytest.mark.parametrize("pattern", [
    [1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0], [0, 0, 1, 0, 1, 0],
    [1, 0, 0, 0, 0, 1], [0, 0, 0, 0, 0, 1], [1, 0, 0, 0, 0, 0]],
    ids=lambda p: "".join(map(str, p)))
def test_the_decode_kernel_neither_reads_nor_writes_a_dead_rows_state(
        pattern):
    """Under the TPU interpreter, which keeps the pipeline's buffers and
    fills what was never written with NaN: a dead row stands on a live
    neighbour's blocks, its own state comes back bit for bit, its y is 0,
    and no live row is written from a buffer nothing filled."""
    from jax.experimental.pallas import tpu as pltpu

    i = _ssm_inputs(b=6, h=16, p=8, n=128, seed=3)
    live = jnp.asarray(pattern, bool)
    dt = jnp.where(live[:, None], i["dt"][:, 0], 0.0)
    args = (i["state"], i["x"][:, 0], dt, i["a"], i["b_mat"][:, 0],
            i["c_mat"][:, 0], i["d_skip"])
    want_state, want_y = ssm_decode_update(*args, live=live, backend="xla")
    state, y = ssm_decode_update(
        *args, live=live, backend="pallas",
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(state)[dead],
                                  np.asarray(i["state"])[dead])
    np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[dead].any()


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_the_state_update_lowers_for_tpu_at_the_cells_widths():
    f32 = jnp.float32
    b, h, p, n = 64, 128, 64, 128
    text = _lower_for_tpu(
        lambda *a: ssm_decode_update(*a, backend="pallas", interpret=False),
        _aval((b, h, p, n), f32), _aval((b, h, p), f32), _aval((b, h), f32),
        _aval((h,), f32), _aval((b, n), f32), _aval((b, n), f32),
        _aval((h,), f32)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,tm", [(640, 32), (10240, 128)],
                         ids=["decode-round", "chunk-call"])
@pytest.mark.parametrize("k,n", [(4096, 1536), (768, 4096)],
                         ids=["in", "out"])
def test_the_grouped_matmul_lowers_for_tpu_at_the_cells_widths(rows, tm, k,
                                                               n):
    n_blocks = -(-rows // tm) + 36
    text = _lower_for_tpu(
        lambda x, w, be, live: grouped_matmul(
            x, w, be, live, tm, backend="pallas", interpret=False),
        _aval((n_blocks * tm, k), jnp.bfloat16),
        _aval((36, k, n), jnp.bfloat16), _aval((n_blocks,), jnp.int32),
        _aval((), jnp.int32)).as_text()
    assert "tpu_custom_call" in text
