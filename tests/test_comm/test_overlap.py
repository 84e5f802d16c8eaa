"""Overlapped gradient collectives (comm/overlap.py): the backward-ordered,
barrier-pinned bucket flush and the double-buffered accumulation must be
bitwise-identical to the sequential path with quantization off, stay within
1e-2 of exact fp32 with int8 on, and the OVL lint family must fire exactly
on seeded mutations and never on clean presets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu import config as edconfig
from easydist_tpu.analyze import (AnalysisError, check_overlap_plan,
                                  lint_overlap_fn, lint_overlap_plan)
from easydist_tpu.comm import (comm_counters, grad_emission_order,
                               overlapped_reduce_gradients)
from easydist_tpu.jaxfront import make_device_mesh
from easydist_tpu.models import mlp_apply, mlp_init
from easydist_tpu.parallel import ddp_step, zero2_step, zero3_step


@pytest.fixture(scope="module")
def mesh_dp(cpu_devices):
    return make_device_mesh((8,), ("dp",))


@pytest.fixture
def exact_comm(monkeypatch):
    """Quantization off, bucketing on: the configuration under which the
    overlapped flush must be BITWISE-identical to the sequential one."""
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "none")
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", 256 << 10)
    monkeypatch.setattr(edconfig, "comm_overlap", False)
    monkeypatch.setattr(edconfig, "grad_accum_microbatches", 0)
    comm_counters.reset()


@pytest.fixture
def int8_comm(monkeypatch):
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "int8")
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", 256 << 10)
    monkeypatch.setattr(edconfig, "comm_quant_min_numel", 512)
    monkeypatch.setattr(edconfig, "comm_overlap", False)
    monkeypatch.setattr(edconfig, "grad_accum_microbatches", 0)
    comm_counters.reset()


def loss_fn(params, x, y):
    return jnp.mean((mlp_apply(params, x) - y) ** 2)


def _data(key=10):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    params = mlp_init(ks[0], sizes=(32, 64, 32))
    x = jax.random.normal(ks[1], (64, 32))
    y = jax.random.normal(ks[2], (64, 32))
    return params, x, y


def _run_ddp(mesh, params, x, y, steps=3, **kw):
    step = ddp_step(loss_fn, mesh, lr=0.05, **kw)
    losses = []
    for _ in range(steps):
        params, l = step(params, x, y)
        losses.append(float(l))
    return params, losses


def _assert_bitwise(tree_a, tree_b, losses_a, losses_b):
    assert losses_a == losses_b, (losses_a, losses_b)
    for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                    jax.tree_util.tree_leaves(tree_b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- ordering

def test_grad_emission_order_is_backward_first():
    params, x, y = _data()
    n = len(jax.tree_util.tree_leaves(params))
    order = grad_emission_order(loss_fn, params, x, y)
    assert sorted(order) == list(range(n))
    # the last layer's grads are produced FIRST in the backward pass, so
    # for a >1-layer MLP the order must be a non-trivial permutation
    assert order != list(range(n))


def test_schedulable_overlap_fraction():
    from easydist_tpu.comm import schedulable_overlap_fraction

    params, x, y = _data()
    frac = schedulable_overlap_fraction(loss_fn, params, x, y)
    # the last layer's grads are emitted mid-backward, so a nonzero share
    # of the flush bytes is launchable under outstanding compute; the
    # first layer's grads arrive at the very end, so the bound stays < 1
    assert 0.0 < frac < 1.0, frac
    # deterministic (it is a pure function of the traced program)
    assert frac == schedulable_overlap_fraction(loss_fn, params, x, y)

    def untraceable(p, x, y):
        raise RuntimeError("not traceable")

    assert schedulable_overlap_fraction(untraceable, params, x, y) == 0.0


def test_grad_emission_order_falls_back_to_identity():
    params, x, y = _data()
    n = len(jax.tree_util.tree_leaves(params))

    def untraceable(p, x, y):
        raise RuntimeError("not traceable")

    assert grad_emission_order(untraceable, params, x, y) == list(range(n))


# ----------------------------------------------------- bitwise flush parity

@pytest.mark.world_8
@pytest.mark.parametrize("bucket_bytes", [0, 256 << 10],
                         ids=["per-leaf", "bucketed"])
def test_ddp_overlapped_flush_bitwise(mesh_dp, exact_comm, monkeypatch,
                                      bucket_bytes):
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", bucket_bytes)
    params, x, y = _data()
    p_seq, l_seq = _run_ddp(mesh_dp, params, x, y)
    monkeypatch.setattr(edconfig, "comm_overlap", True)
    p_ovl, l_ovl = _run_ddp(mesh_dp, params, x, y)
    _assert_bitwise(p_seq, p_ovl, l_seq, l_ovl)


@pytest.mark.world_8
def test_ddp_accum_overlapped_bitwise(mesh_dp, exact_comm, monkeypatch):
    """Double-buffered K=4 accumulation: identical fold order means the
    overlapped scan is bitwise-equal to the sequential one."""
    params, x, y = _data()
    p_seq, l_seq = _run_ddp(mesh_dp, params, x, y,
                            grad_accum_microbatches=4)
    monkeypatch.setattr(edconfig, "comm_overlap", True)
    p_ovl, l_ovl = _run_ddp(mesh_dp, params, x, y,
                            grad_accum_microbatches=4)
    _assert_bitwise(p_seq, p_ovl, l_seq, l_ovl)


def _run_zero(mode, mesh, params, x, y, steps=3, **kw):
    maker = zero2_step if mode == "zero2" else zero3_step
    step, init = maker(loss_fn, mesh, lr=1e-2, **kw)
    state = (params, init(params), jnp.zeros((), jnp.int32)) \
        if mode == "zero2" else init(params)
    losses = []
    for _ in range(steps):
        state, l = step(state, x, y)
        losses.append(float(l))
    return state, losses


@pytest.mark.world_8
@pytest.mark.parametrize("mode", ["zero2", "zero3"])
@pytest.mark.parametrize("accum", [0, 4], ids=["noaccum", "accum4"])
def test_zero_overlapped_bitwise(mesh_dp, exact_comm, monkeypatch, mode,
                                 accum):
    params, x, y = _data(20 if mode == "zero2" else 30)
    s_seq, l_seq = _run_zero(mode, mesh_dp, params, x, y,
                             grad_accum_microbatches=accum)
    monkeypatch.setattr(edconfig, "comm_overlap", True)
    s_ovl, l_ovl = _run_zero(mode, mesh_dp, params, x, y,
                             grad_accum_microbatches=accum)
    if accum:
        # the REDUCED GRADS are bitwise-equal between variants (asserted
        # directly below); the full step is allowed ulp-level drift because
        # XLA may fuse the downstream Adam update differently in the two
        # programs (FMA contraction is context-dependent)
        assert l_seq == l_ovl, (l_seq, l_ovl)
        for a, b in zip(jax.tree_util.tree_leaves(s_seq),
                        jax.tree_util.tree_leaves(s_ovl)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)
    else:
        _assert_bitwise(s_seq, s_ovl, l_seq, l_ovl)


@pytest.mark.world_8
def test_accum_grads_bitwise_with_zero_style_reducer(mesh_dp, exact_comm,
                                                     monkeypatch):
    """The accumulate_gradients contract itself, isolated from the Adam
    update: with a caller-supplied per-leaf reduce_scatter reducer (the
    ZeRO shape), the overlapped double-buffered scan returns REDUCED GRADS
    and mean loss bitwise-equal to the sequential fold."""
    from jax.sharding import PartitionSpec as P

    from easydist_tpu import comm
    from jax import shard_map

    params, x, y = _data(20)
    n = 8

    def accum_grads(overlap):
        monkeypatch.setattr(edconfig, "comm_overlap", overlap)

        def local(params, *batch):
            flat_p, tdef = jax.tree_util.tree_flatten(params)

            def reduce_leaf(i, g):
                return comm.reduce_scatter_grad(g, "dp", n, path=str(i))

            order = comm.grad_emission_order(loss_fn, params, *batch) \
                if overlap else None

            def reduce_tree(gt):
                fg = jax.tree_util.tree_flatten(gt)[0]
                fg = comm.chain_leaf_reduces(fg, order, reduce_leaf) \
                    if overlap else \
                    [reduce_leaf(i, g) for i, g in enumerate(fg)]
                return jax.tree_util.tree_unflatten(tdef, fg)

            acc_shapes = jax.tree_util.tree_unflatten(tdef, [
                jax.ShapeDtypeStruct((p.shape[0] // n,) + p.shape[1:],
                                     jnp.result_type(p)) for p in flat_p])
            return comm.accumulate_gradients(
                loss_fn, params, batch, axis_name="dp", axis_size=n,
                n_micro=4, reduce_tree=reduce_tree, acc_shapes=acc_shapes,
                overlapped=overlap)

        g_spec = jax.tree_util.tree_map(lambda _: P("dp"), params)
        fn = shard_map(local, mesh=mesh_dp,
                       in_specs=(jax.tree_util.tree_map(lambda _: P(),
                                                        params),
                                 P("dp"), P("dp")),
                       out_specs=(g_spec, P()), check_vma=False)
        return jax.jit(fn)(params, x, y)

    g_seq, l_seq = accum_grads(False)
    g_ovl, l_ovl = accum_grads(True)
    _assert_bitwise(g_seq, g_ovl, [float(l_seq)], [float(l_ovl)])


# --------------------------------------------------------- int8 loss parity

@pytest.mark.world_8
@pytest.mark.parametrize("mode", ["ddp", "zero2", "zero3"])
def test_int8_overlapped_loss_parity(mesh_dp, int8_comm, monkeypatch, mode):
    """With int8 quantization on, the overlapped flush must stay within the
    same 1e-2 loss envelope of the exact fp32 sequential run that the
    sequential quantized path is held to."""
    params, x, y = _data({"ddp": 10, "zero2": 20, "zero3": 30}[mode])
    monkeypatch.setattr(edconfig, "comm_overlap", True)
    if mode == "ddp":
        _, l_q = _run_ddp(mesh_dp, params, x, y)
    else:
        _, l_q = _run_zero(mode, mesh_dp, params, x, y)
    snap = comm_counters.snapshot()
    assert snap["quantized_launches"] > 0, snap

    monkeypatch.setattr(edconfig, "comm_quant_dtype", "none")
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", 0)
    monkeypatch.setattr(edconfig, "comm_overlap", False)
    if mode == "ddp":
        _, l_f = _run_ddp(mesh_dp, params, x, y)
    else:
        _, l_f = _run_zero(mode, mesh_dp, params, x, y)
    np.testing.assert_allclose(l_q, l_f, atol=1e-2, rtol=1e-2)


# ------------------------------------------------------------- OVL linting

_FLUSH_GRADS = {"w": jnp.ones((16, 16), jnp.float32),
                "b": jnp.ones((16,), jnp.float32)}


def _lint_flush(pin_chain, monkeypatch):
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "none")
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", 0)
    return lint_overlap_fn(
        lambda g: overlapped_reduce_gradients(g, "dp", 8,
                                              pin_chain=pin_chain),
        _FLUSH_GRADS, axis_sizes={"dp": 8})


def test_ovl002_fires_exactly_once_on_dropped_barrier(monkeypatch):
    """Seeded mutation: dropping the barrier pin from a 2-bucket flush must
    produce exactly ONE OVL002 finding (the single consecutive collective
    pair with no ordering dependency)."""
    findings = _lint_flush(False, monkeypatch)
    assert len(findings) == 1, findings
    assert findings[0].rule_id == "OVL002"


def test_ovl002_silent_on_clean_flush(monkeypatch):
    assert _lint_flush(True, monkeypatch) == []


def test_ovl001_rejects_non_permutation_order(monkeypatch):
    monkeypatch.setattr(edconfig, "enable_analyze", True)
    monkeypatch.setattr(edconfig, "analyze_raise", True)
    leaves = [jnp.ones((4,)), jnp.ones((2,))]
    findings = lint_overlap_plan(leaves, [0, 0])
    assert [f.rule_id for f in findings] == ["OVL001"]
    with pytest.raises(AnalysisError):
        check_overlap_plan(leaves, [0, 0])
    # a valid permutation passes the hook silently
    check_overlap_plan(leaves, [1, 0])


def test_bad_emission_order_rejected_at_trace_time(monkeypatch):
    """A corrupt emission_order handed to the flush must hit the OVL001
    trace-time check (analyze on), not silently drop/duplicate leaves."""
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "none")
    monkeypatch.setattr(edconfig, "enable_analyze", True)
    monkeypatch.setattr(edconfig, "analyze_raise", True)
    with pytest.raises(AnalysisError):
        jax.make_jaxpr(
            lambda g: overlapped_reduce_gradients(g, "dp", 8,
                                                  emission_order=[0, 0]),
            axis_env=[("dp", 8)])(_FLUSH_GRADS)


# -------------------------------------------------- calibration + discount

@pytest.mark.world_8
def test_calibrate_overlap_persists_and_applies(mesh_dp, monkeypatch):
    import importlib

    cal = importlib.import_module("easydist_tpu.runtime.calibrate")

    monkeypatch.setattr(cal, "_applied", None)
    monkeypatch.setattr(cal, "_device_applied", None)
    monkeypatch.setattr(edconfig, "comm_overlap_ratio_measured", None)

    result = cal.calibrate_overlap(mesh_dp, n_elems=1 << 16)
    frac = result["comm_overlap_ratio_measured"]
    assert 0.0 <= frac <= 1.0
    assert edconfig.comm_overlap_ratio_measured == frac

    # a fresh process (caches cleared) must reload the fraction from the
    # PerfDB — including a legitimate 0.0 measurement
    monkeypatch.setattr(edconfig, "comm_overlap_ratio_measured", None)
    monkeypatch.setattr(cal, "_applied", None)
    assert cal.apply_calibration() is True
    assert edconfig.comm_overlap_ratio_measured == frac


@pytest.mark.parametrize(
    "source,measured,expected",
    [("config", 0.9, 0.5),     # flat guess regardless of measurement
     ("measured", None, 0.0),  # uncalibrated -> discount off
     ("measured", 0.3, 0.3),
     ("auto", None, 0.5),      # falls back to the config guess
     ("auto", 0.2, 0.2),
     ("auto", 1.7, 1.0)],      # clamped to [0, 1]
)
def test_overlap_discount_ratio_sources(monkeypatch, source, measured,
                                        expected):
    from easydist_tpu.autoflow.cost_model import (overlap_discount_ratio,
                                                  overlap_ratio_is_measured)

    monkeypatch.setattr(edconfig, "comm_overlap_ratio", 0.5)
    monkeypatch.setattr(edconfig, "comm_overlap_ratio_source", source)
    monkeypatch.setattr(edconfig, "comm_overlap_ratio_measured", measured)
    assert overlap_discount_ratio() == pytest.approx(expected)
    assert overlap_ratio_is_measured() is (measured is not None)


# --------------------------------------------------- device-constant detect

def test_detect_device_constants_datasheet():
    from easydist_tpu.runtime.calibrate import detect_device_constants

    assert detect_device_constants("TPU v4")["peak_flops"] == 275e12
    # longest-prefix: v5 lite must not be swallowed by the v5p row
    assert detect_device_constants("TPU v5 lite")["peak_flops"] == 197e12
    assert detect_device_constants("TPU v5p")["peak_flops"] == 459e12
    assert detect_device_constants("TPU v6 lite")["hbm_bandwidth"] == 1.6e12
    # unknown kinds (CPU hosts, future TPUs) keep the configured defaults
    assert detect_device_constants("cpu") is None
    assert detect_device_constants("Quantum TPU v9") is None


def test_apply_device_constants_env_override(monkeypatch):
    import importlib

    cal = importlib.import_module("easydist_tpu.runtime.calibrate")

    monkeypatch.setattr(cal, "_device_applied", None)
    monkeypatch.setattr(
        cal, "detect_device_constants",
        lambda device_kind=None: {"peak_flops": 275e12,
                                  "hbm_bandwidth": 1.2e12})
    monkeypatch.setattr(edconfig, "peak_flops", 4.9e13)
    monkeypatch.setattr(edconfig, "hbm_bandwidth", 1.0e11)
    monkeypatch.setenv("EASYDIST_PEAK_FLOPS", "7e13")

    assert cal.apply_device_constants(force=True) is True
    # explicit env override wins over the datasheet...
    assert edconfig.peak_flops == 4.9e13
    # ...but un-overridden constants take the datasheet value
    assert edconfig.hbm_bandwidth == 1.2e12


def test_apply_device_constants_noop_on_unknown_backend(monkeypatch):
    import importlib

    cal = importlib.import_module("easydist_tpu.runtime.calibrate")

    monkeypatch.setattr(cal, "_device_applied", None)
    monkeypatch.setattr(cal, "detect_device_constants",
                        lambda device_kind=None: None)
    before = edconfig.peak_flops
    assert cal.apply_device_constants(force=True) is False
    assert edconfig.peak_flops == before


# ------------------------------------------------------- strategy-cache salt

def test_cache_salt_covers_overlap_knobs(monkeypatch):
    from easydist_tpu.jaxfront.api import _compile_cache_key

    closed = jax.make_jaxpr(lambda x: x * 2.0 + 1.0)(jnp.ones((4,)))
    keys = {}
    for name, value in [("comm_overlap", True),
                        ("grad_accum_microbatches", 4),
                        ("comm_overlap_ratio_source", "measured"),
                        ("comm_overlap_ratio_measured", 0.25)]:
        base = _compile_cache_key(closed, ())
        monkeypatch.setattr(edconfig, name, value)
        keys[name] = _compile_cache_key(closed, ())
        assert keys[name] != base, f"salt misses {name}"
    # all five configurations must be distinct
    assert len({*keys.values()}) == len(keys)
