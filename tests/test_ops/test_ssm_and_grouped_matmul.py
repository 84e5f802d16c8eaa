"""`ops/ssm.py` and `ops/grouped_matmul.py`: the chunked scan — its jnp form
and its Pallas kernel under the interpreter — against the one-position
recurrence, the other Pallas kernels under the interpreter against
their jnp forms, the blocked layout's bookkeeping, and the kernels
cross-lowered for TPU at the Granite 4.0-H cell's widths (Pallas' own jaxpr
-> Mosaic lowering; Mosaic's compile is `tests/test_kv/test_arena_inplace.py`
and the chip's job)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops import grouped_matmul as gm
from easydist_tpu.ops.grouped_matmul import (_tile, group_rows,
                                             grouped_matmul,
                                             grouped_matmul_sum)
from easydist_tpu.ops import ssm
from easydist_tpu.ops.ssm import (_heads_per_step, _ssd_tiles, ssd_chunk_scan,
                                  ssd_chunk_scan_xla, ssm_decode_update,
                                  ssm_decode_update_xla)


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


def _scan(form, i, **kw):
    """The chunked scan in one of its two forms: the jnp one, or the kernel
    under the interpreter."""
    fn = ssd_chunk_scan_xla if form == "xla" else functools.partial(
        ssd_chunk_scan, backend="pallas", interpret=True)
    return fn(i["x"], i["dt"], i["a"], i["b_mat"], i["c_mat"], i["d_skip"],
              i["state"], **kw)


@pytest.mark.parametrize("block", [7, 8, 16, 24, 256])
def test_the_chunked_scan_is_the_recurrence_whatever_the_block(block):
    i = _ssm_inputs()
    want_y, want_state = _sequential(i)
    y, state = _scan("xla", i, block=block)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-6)
    # the kernel walks the window in blocks of its own: `block` is the jnp
    # form's, and any value gives the same function
    ky, kstate = _scan("pallas", i, block=block)
    np.testing.assert_allclose(ky, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(kstate, want_state, rtol=2e-5, atol=2e-6)


# (b, s, h, p, n) -> how the kernel tiles it (`_ssd_tiles`: heads a unit,
# heads a step, positions a block; None = no whole lane tiles: the
# interpreter alone takes it, all heads one unit)
SCAN_SHAPES = {
    "tiny": ((2, 24, 4, 8, 16), None),
    "a-window-of-9": ((3, 9, 2, 8, 8), None),
    "three-blocks-six-heads": ((1, 300, 6, 32, 16), None),
    "the-cells-heads-not-whole-blocks": ((2, 200, 4, 64, 128), (2, 4, 128)),
    "the-cells-heads-one-block": ((1, 128, 6, 64, 128), (2, 6, 128)),
    "four-heads-a-unit": ((2, 144, 8, 32, 64), (4, 8, 128)),
    "a-head-a-unit": ((1, 40, 2, 128, 32), (1, 2, 40)),
}


@pytest.mark.parametrize("shape", list(SCAN_SHAPES))
def test_the_scan_kernel_is_the_recurrence_and_the_jnp_form(shape):
    (b, s, h, p, n), tiles = SCAN_SHAPES[shape]
    assert _ssd_tiles(s, h, p, n) == tiles
    i = _ssm_inputs(b, s, h, p, n, seed=s)
    want_y, want_state = _sequential(i)
    scale = float(jnp.abs(want_y).max())
    y, state = _scan("pallas", i)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)
    xla_y, xla_state = _scan("xla", i, block=128)
    np.testing.assert_allclose(y, xla_y, rtol=2e-5, atol=4e-6 * scale)
    np.testing.assert_allclose(state, xla_state, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hb", [2, 4])
def test_the_scan_kernel_over_several_blocks_of_heads(hb, monkeypatch):
    """A budget of fast memory that holds `hb` of the eight heads a grid
    step: every block of heads reads and writes ITS rows of the state (the
    first kernel handed every block the first block's, and no case above
    has a second block)."""
    (b, s, h, p, n) = (2, 200, 8, 64, 128)
    monkeypatch.setattr(ssm, "_SSD_VMEM", hb * 16 * p * (256 + n))
    assert _ssd_tiles(s, h, p, n) == (2, hb, 128)
    i = _ssm_inputs(b, s, h, p, n, seed=19)
    want_y, want_state = _sequential(i)
    y, state = _scan("pallas", i)
    np.testing.assert_allclose(y, want_y, rtol=2e-5,
                               atol=2e-6 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)


def test_the_scan_kernels_tiles_fit_fast_memory():
    # the cell: two heads of 64 a lane tile, 16 heads (1,024 lanes) a step
    assert _ssd_tiles(256, 128, 64, 128) == (2, 16, 128)
    assert _ssd_tiles(1024, 128, 64, 128) == (2, 4, 128)
    assert _ssd_tiles(64, 8, 64, 128) == (2, 8, 64)      # chip_smoke's
    # what falls to the jnp form: heads that fill no whole lane tiles, a
    # window under a sublane tile, one over the budget
    assert _ssd_tiles(256, 3, 64, 128) is None
    assert _ssd_tiles(7, 128, 64, 128) is None
    assert _ssd_tiles(8192, 128, 64, 128) is None


@pytest.mark.parametrize("form", ["xla", "pallas"])
@pytest.mark.parametrize("shape", ["tiny",
                                   "the-cells-heads-not-whole-blocks"])
def test_a_state_carried_through_two_windows_is_one_window_of_both(form,
                                                                   shape):
    (b, s, h, p, n), _ = SCAN_SHAPES[shape]
    i = _ssm_inputs(b, s, h, p, n, seed=7)
    cut = s // 3
    windowed = ("x", "dt", "b_mat", "c_mat")
    y0, mid = _scan(form, {**i, **{k: i[k][:, :cut] for k in windowed}})
    y1, end = _scan(form, {**i, **{k: i[k][:, cut:] for k in windowed},
                           "state": mid})
    y, state = _scan(form, i)
    scale = float(jnp.abs(y).max())
    np.testing.assert_allclose(jnp.concatenate([y0, y1], axis=1), y,
                               rtol=2e-5, atol=4e-6 * scale)
    np.testing.assert_allclose(end, state, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_positions_whose_dt_is_zero_leave_the_state_bit_identical(form):
    i = _ssm_inputs(b=3)
    # 10 real positions and 14 padded; a row of none that count
    dt = i["dt"].at[:, 10:].set(0.0).at[2].set(0.0)
    _, state = _scan(form, {**i, "dt": dt}, block=8)
    _, short = _scan(form, {**i, **{k: v[:, :10] for k, v in (
        ("x", i["x"]), ("dt", dt), ("b_mat", i["b_mat"]),
        ("c_mat", i["c_mat"]))}}, block=8)
    np.testing.assert_allclose(state, short, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(state[2], i["state"][2])
    for backend in ("xla", "pallas"):
        same, _ = ssm_decode_update(
            i["state"], i["x"][:, 0], jnp.zeros_like(i["dt"][:, 0]), i["a"],
            i["b_mat"][:, 0], i["c_mat"][:, 0], i["d_skip"],
            backend=backend, interpret=True)
        np.testing.assert_array_equal(same, i["state"])


def test_a_block_of_the_kernels_with_no_position_that_counts_changes_nothing():
    """The cell's head shape, three blocks of 128: a row whose last two
    blocks are padding, and a row that is padding whole — its state comes
    back bit for bit (the kernel is told which blocks count and passes
    over the others)."""
    (b, s, h, p, n) = (2, 384, 4, 64, 128)
    i = _ssm_inputs(b, s, h, p, n, seed=11)
    dt = i["dt"].at[0, 100:].set(0.0).at[1].set(0.0)
    y, state = _scan("pallas", {**i, "dt": dt})
    np.testing.assert_array_equal(state[1], i["state"][1])
    # such a block skips its mix and its state's products, not its y: C . S
    # + D * x at every position, as the jnp form gives it
    want_y, _ = _scan("xla", {**i, "dt": dt}, block=128)
    np.testing.assert_allclose(y, want_y, rtol=2e-5,
                               atol=4e-6 * float(jnp.abs(want_y).max()))
    _, short = _scan("pallas", {
        k: v[:1, :100] if k in ("x", "dt", "b_mat", "c_mat") else v[:1]
        if k == "state" else v for k, v in {**i, "dt": dt}.items()})
    np.testing.assert_array_equal(state[0], short[0])


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_a_strongly_decaying_window_stays_finite(form):
    """sum(dt * a) about -80 over the window: exp(cs_t) * exp(-cs_r)
    factored apart would be 0 * inf; the difference is taken first."""
    (b, s, h, p, n) = (1, 256, 4, 64, 128)
    i = _ssm_inputs(b, s, h, p, n, seed=13)
    i["dt"] = jnp.full_like(i["dt"], 0.125)
    i["a"] = jnp.asarray([-2.5, -2.0, -3.0, -2.5], jnp.float32)
    assert -100 < float((i["dt"][0, :, 0] * i["a"][0]).sum()) < -75
    want_y, want_state = _sequential(i)
    y, state = _scan(form, i)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(state)).all()
    np.testing.assert_allclose(y, want_y, rtol=2e-5,
                               atol=2e-6 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)


def test_the_scan_kernel_with_the_chips_operands_is_near_the_recurrence(
        monkeypatch):
    """On the chip the products take bfloat16 operands (what the default
    precision hands the MXU for the jnp form's float32 einsums) and
    accumulate in float32; the interpreter can be given the same."""
    build = ssm._ssd_scan_call
    monkeypatch.setattr(ssm, "_ssd_scan_call", lambda *a: build(
        *a[:-2], "bfloat16", a[-1]))
    (b, s, h, p, n), _ = SCAN_SHAPES["the-cells-heads-not-whole-blocks"]
    i = _ssm_inputs(b, s, h, p, n, seed=17)
    want_y, want_state = _sequential(i)
    y, state = _scan("pallas", i)
    for got, want in ((y, want_y), (state, want_state)):
        err = np.abs(np.asarray(got) - np.asarray(want))
        assert 1e-5 * float(jnp.abs(want).max()) < err.max() \
            < 2e-2 * float(jnp.abs(want).max())
        # bfloat16 keeps 8 bits: a product's operands are off by 2 ** -9
        assert err.mean() < 1e-2 * float(jnp.abs(want).mean())


def test_a_models_state_layers_share_one_scan_kernel():
    """Granite's nine state layers a period call the scan at one signature:
    the program's equations carry ONE kernel jaxpr (`_ssd_scan_call` is
    built once a signature), traced once and lowered once a module."""
    layers, (b, s, h, p, n) = 9, (4, 256, 128, 64, 128)
    f32 = jnp.float32

    def program(states, x, dt, a, b_mat, c_mat, d):
        return [ssd_chunk_scan(x, dt, a, b_mat, c_mat, d, st,
                               interpret=False, backend="pallas")
                for st in states]

    ssm._ssd_scan_call.cache_clear()
    closed = jax.make_jaxpr(program)(
        [_aval((b, h, p, n), f32)] * layers, _aval((b, s, h, p), f32),
        _aval((b, s, h), f32), _aval((h,), f32), _aval((b, s, n), f32),
        _aval((b, s, n), f32), _aval((h,), f32))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["ssd_chunk_scan"] * layers
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    assert len({id(e.params["grid_mapping"]) for e in calls}) == 1
    assert ssm._ssd_scan_call.cache_info().misses == 1
    # y comes FIRST and is 3-D: a reader of device traces takes a Mosaic
    # call whose first result is 4-D float32 for the decode update
    assert [v.aval.shape for v in calls[0].outvars] \
        == [(b, s, h * p), (b, h * p, n)]


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


def _routed_sum(rows, tm, first=0, valid=None, choose=None, seed=3,
                experts=8, held=4, k=3, kk=16, n=128):
    """What `models/experts.py::expert_ffn` hands the fused product, from a
    routing of `rows` tokens over `experts` (their top `k`, the `held` from
    `first` on here): every PAIR has a row of its own (no token's), so a
    pair added to another token's sum shows.  -> (the op's arguments —
    x, w, the `RowGroups`, token_at, gate_at, rows —, the dense sum over held
    choices)."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((rows, experts)), axis=1)[:, :k]
    if choose is not None:
        idx = choose(idx)
    gate = rng.random((rows, k)).astype(np.float32)
    pair_x = rng.normal(size=(k * rows, kk)).astype(np.float32)  # slot-major
    w = rng.normal(size=(held, kk, n)).astype(np.float32)
    local = idx.T - first
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine &= valid[None, :]
    expert = np.where(mine, local, held).reshape(k * rows)
    g = group_rows(jnp.asarray(expert, jnp.int32), held, tm)
    source = np.asarray(g.source)
    token_at = np.where(source < k * rows, source % rows, rows)
    gate_at = np.append(gate.T.reshape(-1), 0)[source]
    x = np.append(pair_x, np.ones((1, kk), np.float32), axis=0)[source]
    want = np.zeros((rows, n), np.float32)
    for p in np.nonzero(expert < held)[0]:
        want[p % rows] += gate.T.reshape(-1)[p] * (pair_x[p] @ w[expert[p]])
    return (jnp.asarray(x), jnp.asarray(w), g, jnp.asarray(token_at),
            jnp.asarray(gate_at), rows), want


BACKENDS = pytest.mark.parametrize("backend", ["xla", "pallas"])


@BACKENDS
@pytest.mark.parametrize("first", [0, 3], ids=["held-0-3", "held-3-6"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("tm", [8, 32, 128])
@pytest.mark.parametrize("rows", [1, 5, 64, 300])
def test_the_fused_product_is_the_dense_sum_over_held_choices(
        rows, tm, masked, first, backend):
    valid = np.arange(rows) % 3 != 1 if masked else None
    args, want = _routed_sum(rows, tm, first, valid, seed=rows + tm)
    got = grouped_matmul_sum(*args, backend=backend, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if masked:
        assert not np.asarray(got)[~valid].any()


ROUTINGS = {   # name -> (what it does to the tokens' choices, live blocks)
    "an expert with no pair": (lambda idx: np.where(idx == 1, 7, idx), None),
    "no pair held at all": (lambda idx: np.full_like(idx, 6), 0),
    "an expert over several blocks": (
        lambda idx: np.where(np.arange(idx.shape[1]) == 0, 2, idx + 4), 8),
}


@BACKENDS
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_fused_product_under_uneven_routing(routing, backend):
    choose, live = ROUTINGS[routing]
    args, want = _routed_sum(64, 8, choose=choose)
    g = args[2]
    if live is not None:
        assert int(g.live_blocks) == live
    if routing == "an expert with no pair":
        assert int(g.sizes[1]) == 0 and int(g.live_blocks) > 3
    got = grouped_matmul_sum(*args, backend=backend, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@BACKENDS
def test_rows_no_pair_sits_at_cannot_reach_the_fused_sum(backend):
    """The first product leaves the rows of dead blocks UNWRITTEN, and a
    place of a live block that holds no pair was multiplied from whatever
    row the clipped gather read: NaN at both (where the parent's `out` had
    it: `test_products_nothing_wrote_cannot_reach_the_output`) must not
    reach a token through a zero gate or a one-hot zero."""
    (x, w, g, token_at, gate_at, rows), want = _routed_sum(21, 8)
    empty = np.asarray(token_at) == rows
    dead = np.arange(x.shape[0]) >= int(g.live_blocks) * 8
    assert dead.any() and (empty & ~dead).any() and not (dead & ~empty).any()
    got = grouped_matmul_sum(jnp.where(empty[:, None], jnp.nan, x), w, g,
                             token_at, gate_at, rows, backend=backend,
                             interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_a_models_expert_layers_share_one_kernel():
    """Ten layers call each product at one signature: the program's
    equations carry TWO kernel jaxprs, not twenty (`_gmm_call` is built once
    a signature, as `ops/flash_attention.py::_paged_call`)."""
    rows, tm, held, layers = 64, 32, 36, 10
    nb = -(-rows * 10 // tm) + held
    bf16 = jnp.bfloat16

    def program(xs, w1, w2, be, live, token_at, gate_at):
        g = gm.RowGroups(None, None, be, live, None, tm)
        outs = []
        for x in xs:
            hid = grouped_matmul(x, w1, be, live, tm, backend="pallas",
                                 interpret=False)
            outs.append(grouped_matmul_sum(
                hid[:, :768], w2, g, token_at, gate_at, rows,
                backend="pallas", interpret=False))
        return outs

    gm._gmm_call.cache_clear()
    closed = jax.make_jaxpr(program)(
        [_aval((nb * tm, 4096), bf16)] * layers,
        _aval((held, 4096, 1536), bf16), _aval((held, 768, 4096), bf16),
        _aval((nb,), jnp.int32), _aval((), jnp.int32),
        _aval((nb * tm,), jnp.int32), _aval((nb * tm,), jnp.float32))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] \
        == ["grouped_matmul", "grouped_matmul_sum"] * layers
    assert len({id(e.params["jaxpr"]) for e in calls}) == 2
    assert len({id(e.params["grid_mapping"]) for e in calls}) == 2
    assert gm._gmm_call.cache_info().misses == 2


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


def test_the_chunked_scan_lowers_for_tpu_at_the_cells_widths():
    f32 = jnp.float32
    b, s, h, p, n = 4, 256, 128, 64, 128
    text = _lower_for_tpu(
        lambda *a: ssd_chunk_scan(*a, backend="pallas", interpret=False),
        _aval((b, s, h, p), f32), _aval((b, s, h), f32), _aval((h,), f32),
        _aval((b, s, n), f32), _aval((b, s, n), f32), _aval((h,), f32),
        _aval((b, h, p, n), f32)).as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "ssd_chunk_scan" in text
    # the state goes in and comes out as one operand: aliased
    assert call.count(f"tensor<{b}x{h * p}x{n}xf32>") >= 2
    assert "output_operand_alias<output_tuple_indices = [1], " \
        "operand_index = 9" in call


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


CELL_WIDTHS = {   # cell -> (dim, an expert's width, experts held, top_k,
    #                      rows of a round, rows of a chunk call)
    "granite": (4096, 768, 36, 10, 64, 1024),
    "kexaone": (6144, 2048, 16, 8, 64, 512),
    "axk1": (7168, 2048, 12, 8, 32, 512),
}


@pytest.mark.parametrize("program", ["round", "chunk"])
@pytest.mark.parametrize("cell", list(CELL_WIDTHS))
def test_the_fused_product_lowers_for_tpu_at_the_cells_widths(cell, program):
    dim, width, held, k, round_rows, chunk_rows = CELL_WIDTHS[cell]
    rows = round_rows if program == "round" else chunk_rows
    tm = 128 if rows * k >= 64 * held else 32    # `expert_ffn`'s rule
    n_blocks = -(-rows * k // tm) + held
    text = _lower_for_tpu(
        lambda x, w, be, live, token_at, gate_at: grouped_matmul_sum(
            x, w, gm.RowGroups(None, None, be, live, None, tm), token_at,
            gate_at, rows, backend="pallas", interpret=False),
        _aval((n_blocks * tm, width), jnp.bfloat16),
        _aval((held, width, dim), jnp.bfloat16),
        _aval((n_blocks,), jnp.int32), _aval((), jnp.int32),
        _aval((n_blocks * tm,), jnp.int32),
        _aval((n_blocks * tm,), jnp.float32)).as_text()
    assert "tpu_custom_call" in text
