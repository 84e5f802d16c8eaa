"""Block-scaled quantized collectives: roundtrip bounds, determinism,
collective parity vs exact jax.lax, and the bitwise fp32 fallback contract
(the program emitted with quantization OFF must be the pre-subsystem one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from easydist_tpu import config as edconfig
from easydist_tpu.comm import (bf16_psum, comm_counters, dequantize_blockwise,
                               leaf_quantizable, quantize_blockwise,
                               quantized_psum, quantized_psum_scatter,
                               reduce_gradients)
from easydist_tpu.jaxfront import make_device_mesh
from jax import shard_map


@pytest.fixture(scope="module")
def mesh_dp(cpu_devices):
    return make_device_mesh((8,), ("dp",))


def test_roundtrip_error_bounded_per_block():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 10.0
    q, s = quantize_blockwise(x, 256)
    dq = dequantize_blockwise(q, s, 256)
    err = np.abs(np.asarray(dq) - np.asarray(x)).reshape(-1, 256)
    amax = np.max(np.abs(np.asarray(x)).reshape(-1, 256), axis=1)
    # rint quantization error is at most half an LSB = scale/2 = amax/254
    assert np.all(err.max(axis=1) <= amax / 254.0 + 1e-6)


def test_quantize_deterministic():
    x = jax.random.normal(jax.random.PRNGKey(1), (2048,))
    q1, s1 = quantize_blockwise(x, 128)
    q2, s2 = quantize_blockwise(x, 128)
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_zero_blocks_roundtrip_exact():
    x = jnp.zeros((512,))
    q, s = quantize_blockwise(x, 256)
    assert np.array_equal(np.asarray(dequantize_blockwise(q, s, 256)),
                          np.zeros(512, np.float32))


@pytest.mark.world_8
def test_quantized_psum_matches_exact(mesh_dp):
    # odd trailing size: exercises the pad-to-(n*block) path
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 1000))

    def body(v):
        return quantized_psum(v, "dp", 8), jax.lax.psum(v, "dp")

    fn = shard_map(body, mesh=mesh_dp, in_specs=P("dp"),
                   out_specs=(P(), P()), check_vma=False)
    got, exact = (np.asarray(a) for a in fn(x))
    tol = 0.03 * np.max(np.abs(exact)) + 1e-6
    np.testing.assert_allclose(got, exact, rtol=0, atol=tol)
    # identical on every device was implied by out_specs=P() replication


@pytest.mark.world_8
def test_quantized_pmean_and_dtype_preserved(mesh_dp):
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 64, 33)) \
        .astype(jnp.bfloat16)

    def body(v):
        return (quantized_psum(v, "dp", 8, mean=True),
                jax.lax.pmean(v, "dp"))

    fn = shard_map(body, mesh=mesh_dp, in_specs=P("dp"),
                   out_specs=(P(), P()), check_vma=False)
    got, exact = fn(x)
    assert got.dtype == jnp.bfloat16
    g, e = (np.asarray(a, np.float32) for a in (got, exact))
    np.testing.assert_allclose(g, e, rtol=0, atol=0.05 * np.max(np.abs(e)) + 1e-3)


@pytest.mark.world_8
def test_quantized_psum_scatter_matches_exact(mesh_dp):
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 16, 30))

    def body(v):
        g = v[0]
        return (quantized_psum_scatter(g, "dp", 8, mean=True),
                jax.lax.psum_scatter(g, "dp", scatter_dimension=0,
                                     tiled=True) / 8)

    fn = shard_map(body, mesh=mesh_dp, in_specs=P("dp"),
                   out_specs=(P("dp"), P("dp")), check_vma=False)
    got, exact = (np.asarray(a) for a in fn(x))
    np.testing.assert_allclose(got, exact, rtol=0,
                               atol=0.03 * np.max(np.abs(exact)) + 1e-6)


@pytest.mark.world_8
def test_bf16_psum_halfwidth_close(mesh_dp):
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 256))

    def body(v):
        return bf16_psum(v, "dp"), jax.lax.psum(v, "dp")

    fn = shard_map(body, mesh=mesh_dp, in_specs=P("dp"),
                   out_specs=(P(), P()), check_vma=False)
    got, exact = (np.asarray(a) for a in fn(x))
    np.testing.assert_allclose(got, exact, rtol=0.02,
                               atol=0.02 * np.max(np.abs(exact)))


# ------------------------------------------------------- fp32 fallback path

def test_fallback_emits_bitwise_identical_program():
    """Tier-1 guard: with quantization and bucketing OFF (the defaults),
    reduce_gradients must trace to EXACTLY the per-leaf pmean program the
    grad paths emitted before this subsystem existed — and the counters
    must show the fallback path was the one exercised."""
    assert edconfig.comm_quant_dtype == "none"
    assert edconfig.comm_bucket_bytes == 0
    grads = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    mesh = make_device_mesh((8,), ("dp",))

    def with_comm(g):
        return reduce_gradients(g, "dp", 8, op="pmean")

    def pre_subsystem(g):
        return jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, "dp"), g)

    def jaxpr_of(f):
        fn = shard_map(f, mesh=mesh,
                       in_specs=({"w": P(), "b": P()},),
                       out_specs={"w": P(), "b": P()}, check_vma=False)
        return str(jax.make_jaxpr(fn)(grads))

    comm_counters.reset()
    assert jaxpr_of(with_comm) == jaxpr_of(pre_subsystem)
    snap = comm_counters.snapshot()
    assert snap["fallback_launches"] == snap["launches"] > 0
    assert snap["quantized_launches"] == 0
    # fallback wire bytes == fp32 bytes: no compression claimed
    assert snap["bytes_on_wire"] == snap["bytes_fp32_equiv"] > 0


# ---------------------------------------------------------- per-leaf opt-out

def test_leaf_quantizable_skip_and_minsize(monkeypatch):
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "int8")
    monkeypatch.setattr(edconfig, "comm_quant_min_numel", 100)
    assert leaf_quantizable("['w']", 1000)
    assert not leaf_quantizable("['w']", 99)  # too small
    assert not leaf_quantizable("['layer_norm']['scale']", 10_000)
    assert not leaf_quantizable("[0]['b']", 10_000)  # bias dict key
    assert not leaf_quantizable("['decoder']['bias']", 10_000)
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "none")
    assert not leaf_quantizable("['w']", 1000)


def test_invalid_mode_raises(monkeypatch):
    monkeypatch.setattr(edconfig, "comm_quant_dtype", "fp4")
    with pytest.raises(ValueError):
        reduce_gradients({"w": jnp.ones((4,))}, "dp", 8)
