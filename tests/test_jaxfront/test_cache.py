"""Compile-cache key robustness (ADVICE round-1 findings): dataflow wiring
and large-literal contents must be part of the key — plus the persistent
strategy-cache HIT path (a second compile of the same jaxpr/mesh must skip
ShardCombine discovery and reuse the per-axis strategies)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.jaxfront.api import _compile_cache_key


def _key(fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    return _compile_cache_key(closed, axis_specs=())


def test_wiring_distinguishes_programs():
    # same op/shape sequence, different operand routing
    def f(a, b):
        c = a * b
        d = a + b
        return c * d

    def g(a, b):
        c = a * b
        d = a + b
        return d * d

    x = jnp.ones((4, 4))
    assert _key(f, x, x) != _key(g, x, x)


def test_large_literal_contents_distinguish_programs():
    big0 = np.zeros((100, 100), np.float32)
    big1 = np.zeros((100, 100), np.float32)
    big1[50, 50] = 1.0  # repr() of both truncates identically

    def f(a):
        return a + big0

    def g(a):
        return a + big1

    x = jnp.ones((100, 100))
    assert _key(f, x) != _key(g, x)


def test_identical_programs_share_key():
    def f(a, b):
        return a @ b + a

    x = jnp.ones((4, 4))
    assert _key(f, x, x) == _key(f, x, x)


def test_a_jaxpr_held_by_many_equations_is_printed_once():
    """A model's layers call a Pallas kernel at one signature and their
    equations share its jaxpr: an equation's signature prints that jaxpr
    once an object (`_jaxpr_text`), and reads letter for letter what
    `str(sorted(params.items()))` gives — the keys of the rule store and of
    the strategy cache do not move."""
    from easydist_tpu.jaxfront import interpreter
    from easydist_tpu.jaxfront.inline import inline_calls
    from easydist_tpu.ops.flash_attention import flash_paged_decode_attention

    pages = jax.ShapeDtypeStruct((8, 2, 8, 128), jnp.float32)

    def layers(q, table, lengths, *leaves):
        for k, v in zip(leaves[::2], leaves[1::2]):
            q = flash_paged_decode_attention(q, k, v, table, lengths,
                                             interpret=True)
        return jax.lax.cond(lengths[0] > 0, jnp.sin, jnp.cos, q)

    closed = jax.make_jaxpr(layers)(
        jax.ShapeDtypeStruct((2, 4, 128), jnp.float32),
        jax.ShapeDtypeStruct((2, 4), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), *[pages] * 6)
    eqns = inline_calls(closed).jaxpr.eqns
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    interpreter._jaxpr_text.cache_clear()
    for eqn in eqns:
        sig = interpreter.eqn_signature(eqn, None)
        assert sig.endswith("|" + str(sorted(eqn.params.items())))
    info = interpreter._jaxpr_text.cache_info()
    assert (info.misses, info.hits) == (1, 2)


@pytest.mark.world_8
def test_strategy_cache_hit_skips_discovery(cpu_devices, tmp_path,
                                            monkeypatch, caplog):
    """Persistent strategy-cache hit path: the second compile of the same
    jaxpr/mesh must (a) log the cache hit, (b) never run ShardCombine
    discovery, and (c) produce identical per-axis strategies."""
    from easydist_tpu import config as edconfig
    from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
    from easydist_tpu.jaxfront.interpreter import ShardingAnalyzer

    monkeypatch.setattr(edconfig, "enable_compile_cache", True)
    monkeypatch.setattr(edconfig, "compile_cache_dir", str(tmp_path))

    discovery_runs = []
    orig_run = ShardingAnalyzer.run

    def counting_run(self):
        discovery_runs.append(1)
        return orig_run(self)

    monkeypatch.setattr(ShardingAnalyzer, "run", counting_run)
    mesh = make_device_mesh((8,), ("dp",))

    def step(w, x):
        return jnp.tanh(x @ w).sum()

    w = jnp.ones((16, 16))
    x = jnp.ones((32, 16))

    caplog.set_level(logging.INFO, logger="easydist_tpu.jaxfront.api")
    first = easydist_compile(step, mesh=mesh, compile_only=True)
    res1 = first.get_compiled(w, x)
    assert len(discovery_runs) == 1
    assert first.cache_stats() == {"size": 1, "hits": 0, "misses": 1}

    # fresh CompiledFunction: the in-memory signature cache cannot serve
    # this, only the persistent strategy pickle can
    second = easydist_compile(step, mesh=mesh, compile_only=True)
    res2 = second.get_compiled(w, x)
    assert len(discovery_runs) == 1, \
        "second compile re-ran ShardCombine discovery despite a cache hit"
    assert second.cache_stats()["misses"] == 1  # compiled, but from cache
    assert any("[compile cache] hit" in rec.getMessage()
               for rec in caplog.records)

    assert len(res1.strategies) == len(res2.strategies)
    for ax1, ax2 in zip(res1.strategies, res2.strategies):
        assert sorted(ax1) == sorted(ax2)
        for name in ax1:
            assert repr(ax1[name]) == repr(ax2[name]), name
