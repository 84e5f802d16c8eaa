"""A row-parallel Pallas kernel is a shardable op: the predicate that finds
one (`presets.pallas_row_extent`), the plan the solver gives it on a (2, 2)
mesh, and the program emission binds from that plan (`api._bind_pallas_rows`)
— each kernel under a `shard_map` at its shard's row count — against the
one-device step under the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.analyze.jaxpr_rules import _sub_jaxprs
from easydist_tpu.jaxfront import make_device_mesh
from easydist_tpu.jaxfront.api import compile_step
from easydist_tpu.jaxfront.presets import pallas_row_extent, preset_rule
from easydist_tpu.models import GPTConfig, gpt_init
from easydist_tpu.models.gpt import gpt_loss
from easydist_tpu.ops.flash_attention import (
    flash_attention, flash_decode_attention, flash_paged_decode_attention,
    flash_paged_decode_quant_attention)
from easydist_tpu.runtime import spans

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _pallas_eqns(fn, *args):
    """{kernel name: [equations]} of every `pallas_call` in `fn`'s jaxpr,
    nested jaxprs (custom_vjp, pjit, shard_map) included."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.setdefault(eqn.params["name"], []).append(eqn)
            for _, sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# ------------------------------------------------------------ the predicate

# (batch, heads, positions, head_dim, blocks, steps of the grid's third axis):
# a small shape; the train cell's own (100 rows of 1,024 x 64, a row's other
# side held whole and walked by the body); and one too long to hold, streamed
# through the grid half a row a step
ROW_SHAPES = [pytest.param(4, 5, 64, 16, 32, 1, id="small"),
              pytest.param(4, 25, 1024, 64, 256, 1, id="train-cell"),
              pytest.param(1, 4, 8192, 128, 256, 2, id="streamed")]


@pytest.mark.parametrize("kernel", FLASH_KERNELS)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("b,h,t,d,block,steps", ROW_SHAPES)
def test_predicate_accepts_the_training_kernels(kernel, causal, b, h, t, d,
                                                block, steps):
    q = jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16)
    eqns = _pallas_eqns(
        lambda q, k, v: jax.grad(lambda *qkv: flash_attention(
            *qkv, causal, block_q=block, block_k=block).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v), q, q, q)
    (eqn,) = eqns[kernel]
    # held whole, the walked side is ONE step of the grid's third axis
    assert eqn.params["grid_mapping"].grid[2] == steps
    assert pallas_row_extent(eqn) == b * h
    rule = preset_rule(eqn, 2)
    assert rule["shard_where_valid"]
    assert all(row[0].group == 1 and not any(d.group for d in row[1:])
               for row in rule["space"].table)
    assert len(rule["recombines"][1]) == len(eqn.outvars)


def _decode_call(kind):
    b, h, d, pt, n_pages, max_pages = 2, 4, 16, 8, 8, 4
    q = jnp.ones((b, h, d), jnp.float32)
    lengths = jnp.array([9, 17], jnp.int32)
    if kind == "bucketed":
        cache = jnp.ones((b, h, 32, d), jnp.float32)
        return (lambda: flash_decode_attention(q, cache, cache, lengths,
                                               interpret=True))
    table = jnp.zeros((b, max_pages), jnp.int32)
    if kind == "paged":
        pages = jnp.ones((n_pages, h, pt, d), jnp.float32)
        return (lambda: flash_paged_decode_attention(
            q, pages, pages, table, lengths, interpret=True))
    pages = jnp.ones((n_pages, h, pt, d), jnp.int8)
    scales = jnp.ones((n_pages, h, pt, 1), jnp.float32)
    return (lambda: flash_paged_decode_quant_attention(
        q, pages, pages, scales, scales, table, lengths, interpret=True))


@pytest.mark.parametrize("kind", ["bucketed", "paged", "paged-int8"])
def test_predicate_refuses_the_decode_kernels(kind):
    eqns = _pallas_eqns(_decode_call(kind))
    assert eqns
    for (eqn,) in eqns.values():
        assert pallas_row_extent(eqn) is None
        rule = preset_rule(eqn, 2)
        assert not rule["recombines"] and not rule.get("shard_where_valid")


def test_predicate_refuses_a_body_that_reads_its_row():
    """Block size 1 and an identity index map are not enough: a body that
    reads `program_id(0)` computes something else at another row count."""
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] + pl.program_id(0).astype(jnp.float32)

    def call(body):
        spec = pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))
        return lambda x: pl.pallas_call(
            body, grid=(4,), in_specs=[spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True, name="rows")(x)

    x = jnp.ones((4, 8, 128), jnp.float32)
    (eqn,) = _pallas_eqns(call(body), x)["rows"]
    assert pallas_row_extent(eqn) is None

    def plain(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    (eqn,) = _pallas_eqns(call(plain), x)["rows"]
    assert pallas_row_extent(eqn) == 4


# ------------------------------------------------- the plan and the program

def _loss_and_grads(cfg):
    def step(params, tokens, targets):
        return jax.value_and_grad(gpt_loss)(params, cfg, tokens, targets)

    return step


def _emitted_kernels(result):
    """(kernel name, leading extents of operands and results) of every
    `pallas_call` in the emitted program."""
    eqns = _pallas_eqns(result.jitted, *result.in_avals)
    return [(name, {v.aval.shape[0] for v in eqn.invars + eqn.outvars})
            for name, found in eqns.items() for eqn in found]


def _counted():
    return {k: v for k, v in spans.snapshot()["counters"].items()
            if k.startswith("pallas_calls{")}


# 5 heads: the heads alone divide by neither axis of the mesh.  rows x 5
# (batch x head) rows divide by 4, by 2 once (dp, solved first), or by none
CASES = [pytest.param(4, 4, id="rows-over-both-axes"),
         pytest.param(2, 2, id="rows-over-dp-only"),
         pytest.param(1, 1, id="rows-whole")]


@pytest.mark.parametrize("batch,row_shards", CASES)
def test_flash_step_on_a_mesh_shards_rows_and_matches_one_device(
        cpu_devices, batch, row_shards):
    cfg = GPTConfig(vocab=128, seq=32, dim=40, heads=5, layers=2,
                    attention="flash")
    step = _loss_and_grads(cfg)
    params = gpt_init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.seq), 0,
                                cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    mesh = make_device_mesh((2, 2), ("dp", "tp"), devices=cpu_devices[:4])

    spans.clear()
    result = compile_step(step, (params, tokens, targets), {}, mesh=mesh,
                          state_io=None)
    n = batch * cfg.heads
    calls = 3 * cfg.layers
    assert _counted() == {
        f"pallas_calls{{kernel={k},row_shards={row_shards}}}": cfg.layers
        for k in FLASH_KERNELS}
    emitted = _emitted_kernels(result)
    assert len(emitted) == calls
    assert all(extents == {n // row_shards} for _, extents in emitted), \
        emitted

    flat = result.jitted(*jax.tree_util.tree_leaves(
        (params, tokens, targets)))
    want_loss, want_grads = jax.jit(step)(params, tokens, targets)
    loss, grads = flat[0], flat[1:]
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-3,
                               atol=1e-5)
    want = jax.tree_util.tree_leaves(want_grads)
    assert len(grads) == len(want)
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-5)
