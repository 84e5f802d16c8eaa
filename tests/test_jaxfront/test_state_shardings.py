"""A paired state leaf has ONE sharding, the solved `in_shardings` entry of
the input it replaces: `materialize` makes it so, the compiled step takes it
so and hands it back so, as the very object, and the second call of a step
hits the jit's cache instead of tracing, lowering and compiling the step
again (`jaxfront/api.py::_finish_compile`, `out_pins`).

A tiny GPT train step (momentum SGD: Adam turns the rounding of a bias
whose gradient is zero into steps of the learning rate, in either sign) on
a `("dp", "tp") = (2, 2)` mesh over four of the eight CPU devices, sized so
that the solver shards some leaves and keeps others whole."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from easydist_tpu.jaxfront import easydist_compile
from easydist_tpu.models import GPTConfig
from easydist_tpu.models.gpt import gpt_init, gpt_loss
from easydist_tpu.runtime import spans

CFG = GPTConfig(vocab=512, seq=64, dim=64, heads=4, layers=2)
BATCH, CALLS, LR = 8, 3, 0.05
KEY = jax.random.PRNGKey(0)


def init_state(key):
    params = gpt_init(CFG, key)
    return (params, {"momentum": jax.tree.map(jnp.zeros_like, params),
                     "count": jnp.zeros((), jnp.int32)})


def train_step(state, tokens, targets):
    params, opt = state
    loss, grads = jax.value_and_grad(gpt_loss)(params, CFG, tokens, targets)
    momentum = jax.tree.map(lambda m, g: 0.9 * m + g, opt["momentum"], grads)
    params = jax.tree.map(lambda p, m: p - LR * m, params, momentum)
    return (params, {"momentum": momentum, "count": opt["count"] + 1}), loss


@pytest.fixture(scope="module")
def mesh(cpu_devices):
    return Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "tp"))


def _batches():
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.integers(0, CFG.vocab, (BATCH, CFG.seq)),
                        jnp.int32) for _ in range(CALLS + 1)]


def _shardings(state):
    return [leaf.sharding for leaf in jax.tree_util.tree_leaves(state)]


@pytest.fixture(scope="module")
def run(mesh):
    """`CALLS` calls of the compiled step on a state born through
    `materialize`: what the recorder saw, the losses, the shardings the
    state came back in after every call, and the last state."""
    compiled = easydist_compile(train_step, mesh=mesh)
    tokens = jax.ShapeDtypeStruct((BATCH, CFG.seq), jnp.int32)
    result = compiled.get_compiled(jax.eval_shape(init_state, KEY), tokens,
                                   tokens)
    state = result.materialize(init_state, KEY)
    born = _shardings(state)
    spans.clear()
    losses, came_back = [], []
    for tokens, targets in zip(_batches(), _batches()[1:]):
        state, loss = compiled(state, tokens, targets)
        losses.append(float(loss))
        came_back.append(_shardings(state))
    snap = spans.snapshot()
    spans.clear()
    return dict(compiled=compiled, result=result, born=born, snap=snap,
                losses=losses, came_back=came_back, state=state)


@pytest.mark.world_8
def test_the_plan_shards_some_leaves_and_keeps_others_whole(run):
    """The case has power: were every leaf whole, XLA would have nothing
    to choose."""
    solved = run["result"].in_shardings[:len(run["born"])]
    assert run["born"] == solved
    assert any(s.is_fully_replicated for s in solved)
    assert any(not s.is_fully_replicated for s in solved)


@pytest.mark.world_8
def test_three_calls_compile_the_step_once(run):
    snap = run["snap"]
    assert snap["counters"]["xla_compiles{fn=train_step}"] == 1
    calls = sorted((r for r in snap["spans"]
                    if r["name"] == "easydist.step.call"
                    and r["attrs"]["fn"] == "train_step"),
                   key=lambda r: r["t0_ns"])
    assert len(calls) == CALLS
    compiles = [r for r in snap["spans"]
                if r["name"] == "easydist.step.compile"]
    assert [r["parent_id"] for r in compiles] == [calls[0]["id"]]
    assert run["result"].tree_jitted._cache_size() == 1


@pytest.mark.world_8
def test_every_state_leaf_comes_back_in_its_solved_sharding(run):
    """After every call, and as the declared object: equivalent alone
    (`P('dp')` for `P('dp', None)`) is another key to the jit's cache."""
    result = run["result"]
    n = len(run["born"])
    assert sorted(result.state_pairs.items()) == [(i, i) for i in range(n)]
    for after in run["came_back"]:
        assert len(after) == n
        for got, want, aval in zip(after, result.in_shardings,
                                   result.in_avals):
            assert got.is_equivalent_to(want, len(aval.shape))
            assert got == want


@pytest.mark.world_8
def test_losses_and_parameters_equal_the_unsharded_step(run):
    """The same step jitted unsharded on one device (the tolerances of
    `test_e2e.py`)."""
    ref = jax.jit(train_step)
    state = init_state(KEY)
    for i, (tokens, targets) in enumerate(zip(_batches(), _batches()[1:])):
        state, loss = ref(state, tokens, targets)
        np.testing.assert_allclose(run["losses"][i], float(loss),
                                   rtol=1e-4, atol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(run["state"][0]),
                         jax.tree_util.tree_leaves(state[0])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.world_8
@pytest.mark.parametrize("brought", ["whole_on_the_mesh", "on_one_device"])
def test_state_brought_in_another_sharding_runs_and_returns_the_solved_one(
        run, mesh, brought):
    """One placement, inside the first call; from then on the state is
    where the step declares it."""
    result = run["result"]
    state = init_state(KEY)      # on the default device, uncommitted
    if brought == "whole_on_the_mesh":
        state = jax.device_put(state, NamedSharding(mesh, P()))
    n = len(run["born"])
    solved = result.in_shardings[:n]
    assert _shardings(state) != solved
    tokens, targets = _batches()[:2]
    state, loss = run["compiled"](state, tokens, targets)
    np.testing.assert_allclose(float(loss), run["losses"][0], rtol=1e-4,
                               atol=1e-6)
    for leaf, want in zip(jax.tree_util.tree_leaves(state), solved):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    # committed to the mesh it came back as the declared objects; brought
    # uncommitted, JAX had no mesh to name what XLA returned, and names it
    # from the second call on: either way the step is not compiled again
    state, _ = run["compiled"](state, targets, tokens)
    assert _shardings(state) == solved
    before = result.tree_jitted._cache_size()
    state, _ = run["compiled"](state, tokens, targets)
    assert result.tree_jitted._cache_size() == before
    assert _shardings(state) == solved


@pytest.mark.world_8
def test_the_compiled_module_aliases_every_paired_leaf(run):
    """A donated buffer can be aliased only to an output of its own
    sharding: left to XLA, the leaves it re-placed were copied."""
    result = run["result"]
    n = len(run["born"])
    text = result.executable().as_text()
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}", re.search(
            r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert set(result.donated_invars) == set(range(n))
    assert aliased >= set(range(n))


def _forward(w, x):
    return jnp.tanh(x @ w[0]) @ w[1]


def _produced_by(jaxpr):
    return {v: eqn.primitive.name for eqn in jaxpr.eqns
            for v in eqn.outvars}


RECORDED_WITH = "0.9.0"
AT_THE_PARENT = {"tree": "32e41ac70ca6df04", "flat": "3629f637d3567e6e"}


@pytest.mark.world_8
def test_a_function_without_state_pairs_lowers_as_at_the_parent(run, mesh):
    """Nothing is constrained on the way out of a function that threads no
    state: its output is the emitted equation's, where each paired leaf of
    the train step (the scalar count left out, as on the way in) leaves
    through a constraint of its own; and both jits lower to the text they
    lowered to at the parent commit (digests taken there, on the CPU, of
    the text JAX prints: another JAX version skips them)."""
    w = (jnp.ones((256, 1024)), jnp.ones((1024, 256)))
    x = jnp.ones((2048, 256))
    result = easydist_compile(_forward, mesh=mesh).get_compiled(w, x)
    assert result.state_pairs == {} and result.donated_invars == ()
    assert [s.spec for s in result.in_shardings] \
        == [P(None, "tp"), P("tp"), P("dp")]
    traced = jax.make_jaxpr(result.jitted.__wrapped__)(*result.in_avals)
    (out,) = traced.jaxpr.outvars
    assert _produced_by(traced.jaxpr)[out] == "dot_general"
    step = run["result"]
    traced = jax.make_jaxpr(step.jitted.__wrapped__)(*step.in_avals)
    made = _produced_by(traced.jaxpr)
    n = len(run["born"])
    assert [made[v] for v in traced.jaxpr.outvars[:n] if v.aval.shape] \
        == ["sharding_constraint"] * (n - 1)
    if jax.__version__ != RECORDED_WITH:
        pytest.skip(f"digests recorded with jax {RECORDED_WITH}")
    flat = jax.tree_util.tree_leaves((w, x))
    texts = {"tree": result.tree_jitted.lower(w, x).as_text(),
             "flat": result.jitted.lower(*flat).as_text()}
    assert {k: hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in texts.items()} == AT_THE_PARENT
