"""Gradient bucketer: packing plan, bit-exact roundtrip, and launch-count
fusion with value-identical fp32 reductions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from easydist_tpu import config as edconfig
from easydist_tpu.comm import (comm_counters, pack, plan_buckets,
                               reduce_gradients, unpack)
from easydist_tpu.jaxfront import make_device_mesh
from jax import shard_map


def _leaves():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return [jax.random.normal(k[0], (64, 32)),        # 8 KiB
            jax.random.normal(k[1], (32,)),           # 128 B
            jax.random.normal(k[2], (128, 64)),       # 32 KiB
            jax.random.normal(k[3], (16, 8))]         # 512 B


def test_plan_respects_bucket_bytes_and_dtype_groups():
    leaves = _leaves() + [jnp.ones((64,), jnp.bfloat16)]
    flags = [True, False, True, False, True]
    buckets = plan_buckets(leaves, bucket_bytes=16 << 10,
                           quantize_flags=flags)
    for b in buckets:
        # one dtype and one quantize flag per bucket; size respected except
        # for single oversized leaves
        dts = {jnp.dtype(leaves[i].dtype) for i in b.indices}
        assert len(dts) == 1
        if len(b.indices) > 1:
            assert b.nbytes <= 16 << 10
    # every leaf appears exactly once
    seen = sorted(i for b in buckets for i in b.indices)
    assert seen == list(range(len(leaves)))
    # bf16 leaf cannot share a bucket with f32 leaves
    bf_bucket = next(b for b in buckets if 4 in b.indices)
    assert bf_bucket.indices == [4]


def test_zero_bucket_bytes_means_per_leaf():
    leaves = _leaves()
    buckets = plan_buckets(leaves, 0, [True] * 4)
    assert [b.indices for b in buckets] == [[0], [1], [2], [3]]


def test_pack_unpack_bit_exact_roundtrip():
    leaves = _leaves()
    buckets = plan_buckets(leaves, 1 << 20, [True] * 4)
    for b in buckets:
        flat = pack(leaves, b)
        back = unpack(flat, b, leaves)
        for i, leaf in back.items():
            assert np.array_equal(np.asarray(leaf), np.asarray(leaves[i]))


@pytest.mark.world_8
def test_bucketed_fp32_pmean_value_identical(cpu_devices, monkeypatch):
    """Bucketing without quantization is pure launch fusion: an elementwise
    psum over a concatenation must produce the same values as per-leaf
    psums — and fewer launches."""
    mesh = make_device_mesh((8,), ("dp",))
    grads = {"a": jax.random.normal(jax.random.PRNGKey(1), (8, 64, 32)),
             "b": jax.random.normal(jax.random.PRNGKey(2), (8, 32)),
             "c": jax.random.normal(jax.random.PRNGKey(3), (8, 16, 16))}

    def per_leaf(g):
        return jax.tree_util.tree_map(lambda t: jax.lax.pmean(t, "dp"), g)

    def bucketed(g):
        return reduce_gradients(g, "dp", 8, op="pmean")

    def run(f):
        specs = jax.tree_util.tree_map(lambda _: P("dp"), grads)
        fn = shard_map(f, mesh=mesh, in_specs=(specs,), out_specs=specs,
                       check_vma=False)
        return fn(grads)

    ref = run(per_leaf)
    monkeypatch.setattr(edconfig, "comm_bucket_bytes", 1 << 20)
    comm_counters.reset()
    got = run(bucketed)
    snap = comm_counters.snapshot()
    assert snap["launches"] == 1          # 3 leaves fused into one bucket
    assert snap["bucketed_leaves"] == 3
    assert snap["quantized_launches"] == 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
