"""Layer-2 golden fixtures: emitted collective programs (jaxprs traced from
shard_map bodies) and bucket plans, each mutation firing exactly one rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from easydist_tpu import config as edconfig
from easydist_tpu.analyze import (AnalysisError, check_bucket_plan, lint_fn,
                                  lint_bucket_plan, lint_jaxpr)
from easydist_tpu.comm.bucketer import plan_buckets
from jax import shard_map


def dp_mesh(devices):
    return Mesh(np.array(devices), ("dp",))


def traced(mesh, body, *args, in_specs, out_specs):
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return jax.make_jaxpr(fn)(*args)


def fired(findings, rule_id):
    return [f for f in findings if f.rule_id == rule_id]


# ------------------------------------------------------------ axis existence

def test_known_axis_clean(cpu_devices):
    mesh = dp_mesh(cpu_devices)
    j = traced(mesh, lambda x: jax.lax.psum(x, "dp"), jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P())
    assert lint_jaxpr(j.jaxpr, {"dp": 8}) == []


def test_coll001_unknown_axis_fires_once(cpu_devices):
    mesh = dp_mesh(cpu_devices)
    j = traced(mesh, lambda x: jax.lax.psum(x, "dp"), jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P())
    # lint against a mesh that lost the axis (mis-wired mesh handoff)
    findings = lint_jaxpr(j.jaxpr, {"tp": 8})
    assert [f.rule_id for f in findings] == ["COLL001"]
    assert "'dp'" in findings[0].message


# ------------------------------------------------- cond/while deadlock shape

def test_coll002_cond_branch_collective_mismatch(cpu_devices):
    mesh = dp_mesh(cpu_devices)

    def body(x):
        return jax.lax.cond(x[0] > 0,
                            lambda y: jax.lax.psum(y, "dp"),
                            lambda y: y * 8.0, x)

    j = traced(mesh, body, jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P("dp"))
    findings = lint_jaxpr(j.jaxpr, {"dp": 8})
    assert [f.rule_id for f in findings] == ["COLL002"]
    assert findings[0].severity == "error"


def test_cond_branches_agree_clean(cpu_devices):
    mesh = dp_mesh(cpu_devices)

    def body(x):
        return jax.lax.cond(x[0] > 0,
                            lambda y: jax.lax.psum(y, "dp"),
                            lambda y: jax.lax.psum(y * 2.0, "dp"), x)

    j = traced(mesh, body, jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P("dp"))
    assert lint_jaxpr(j.jaxpr, {"dp": 8}) == []


def test_coll005_while_predicate_collective_warns_once(cpu_devices):
    mesh = dp_mesh(cpu_devices)

    def body(x):
        return jax.lax.while_loop(
            lambda s: jax.lax.psum(s, "dp")[0] < 3.0, lambda s: s + 1.0, x)

    j = traced(mesh, body, jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P("dp"))
    findings = lint_jaxpr(j.jaxpr, {"dp": 8})
    assert [f.rule_id for f in findings] == ["COLL005"]
    assert findings[0].severity == "warning"


# --------------------------------------------------------- int8 accumulation

def test_coll004_int8_psum_fires_once(cpu_devices):
    mesh = dp_mesh(cpu_devices)

    def body(x):
        return jax.lax.psum(x.astype(jnp.int8), "dp")

    j = traced(mesh, body, jnp.arange(16.0),
               in_specs=(P("dp"),), out_specs=P())
    findings = lint_jaxpr(j.jaxpr, {"dp": 8})
    assert [f.rule_id for f in findings] == ["COLL004"]


def test_quantized_two_pass_program_clean(cpu_devices):
    """The real quantized reduction (int8 payload moved by all_to_all /
    all_gather, summed in f32 after dequantize) must NOT trip COLL004."""
    from easydist_tpu.comm.quant import quantized_psum

    mesh = dp_mesh(cpu_devices)
    j = traced(mesh, lambda x: quantized_psum(x, "dp", 8),
               jnp.arange(4096.0),
               in_specs=(P("dp"),), out_specs=P("dp"))
    assert lint_jaxpr(j.jaxpr, {"dp": 8}) == []


# ------------------------------------------------------------- lint_fn entry

def test_lint_fn_on_ddp_step(cpu_devices):
    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.models import mlp_apply, mlp_init
    from easydist_tpu.parallel import ddp_step

    mesh = make_device_mesh((8,), ("dp",), devices=cpu_devices)
    params = mlp_init(jax.random.PRNGKey(0), sizes=(16, 32, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 16))

    def loss(p, xb, yb):
        return jnp.mean((mlp_apply(p, xb) - yb) ** 2)

    step = ddp_step(loss, mesh, lr=0.05)
    findings = lint_fn(step, params, x, y, axis_sizes={"dp": 8})
    assert [f for f in findings if f.severity == "error"] == []


# --------------------------------------------------------------- bucket lint

def make_leaves():
    rng = np.random.RandomState(0)
    return [rng.randn(n).astype(np.float32) for n in (300, 300, 300, 50)]


def test_bucket_plan_clean():
    leaves = make_leaves()
    buckets = plan_buckets(leaves, 2048, [True] * len(leaves))
    assert lint_bucket_plan(leaves, buckets) == []


def test_coll003_overlapping_slice_fires_once():
    leaves = make_leaves()
    buckets = plan_buckets(leaves, 2048, [True] * len(leaves))
    # seeded mutation: leaf 0 packed twice (nbytes adjusted so only the
    # overlap is wrong, not the byte accounting)
    buckets[-1].indices.append(0)
    buckets[-1].nbytes += leaves[0].size * leaves[0].dtype.itemsize
    findings = lint_bucket_plan(leaves, buckets)
    assert [f.rule_id for f in findings] == ["COLL003"]
    assert "overlap" in findings[0].message


def test_coll003_gap_fires_once():
    leaves = make_leaves()
    buckets = plan_buckets(leaves, 2048, [True] * len(leaves))
    dropped = buckets[-1].indices.pop()
    buckets[-1].nbytes -= leaves[dropped].size * leaves[dropped].dtype.itemsize
    findings = lint_bucket_plan(leaves, buckets)
    assert [f.rule_id for f in findings] == ["COLL003"]
    assert "never packed" in findings[0].message


def test_coll003_off_by_one_slice_fires_once():
    leaves = make_leaves()
    buckets = plan_buckets(leaves, 2048, [True] * len(leaves))
    buckets[0].nbytes -= 4  # one f32 short: unpack would mis-slice
    findings = lint_bucket_plan(leaves, buckets)
    assert [f.rule_id for f in findings] == ["COLL003"]
    assert "tile" in findings[0].message


def test_check_bucket_plan_raises_and_escape_hatch(monkeypatch):
    leaves = make_leaves()
    buckets = plan_buckets(leaves, 2048, [True] * len(leaves))
    buckets[0].nbytes -= 4
    with pytest.raises(AnalysisError):
        check_bucket_plan(leaves, buckets)
    monkeypatch.setattr(edconfig, "analyze_raise", False)
    check_bucket_plan(leaves, buckets)  # demoted to logging
