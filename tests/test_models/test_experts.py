"""`models/experts.py::expert_ffn`, the one body both expert models run,
under each model's way of turning scores into (choices, gates): the held
part is the dense sum over the held choices, rows that are not valid are
routed nowhere, the counters count what was routed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.models import experts

ROWS, DIM, WIDTH, EXPERTS, K = 24, 32, 16, 8, 3


def _softmax_top_k(scores):        # granite_hybrid
    top, idx = jax.lax.top_k(scores, K)
    return idx, jax.nn.softmax(top, axis=-1)


def _sigmoid_biased(scores):       # exaone_moe
    s = jax.nn.sigmoid(scores)
    bias = 0.3 * jnp.cos(jnp.arange(EXPERTS, dtype=jnp.float32))
    _, idx = jax.lax.top_k(s + bias, K)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, 2.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


ROUTERS = {"softmax_top_k": _softmax_top_k, "sigmoid_biased": _sigmoid_biased}


@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return {"u": jax.random.normal(k[0], (ROWS, DIM)),
            "router": jax.random.normal(k[1], (DIM, EXPERTS)),
            "w1": jax.random.normal(k[2], (EXPERTS, DIM, 2 * WIDTH)) / 6,
            "w2": jax.random.normal(k[3], (EXPERTS, WIDTH, DIM)) / 4}


def _dense(layer, idx, gate, first, held, valid=None):
    u, out = np.asarray(layer["u"]), np.zeros((ROWS, DIM), np.float32)
    for r in range(ROWS):
        if valid is not None and not valid[r]:
            continue
        for e, g in zip(np.asarray(idx[r]), np.asarray(gate[r])):
            if first <= e < first + held:
                ab = u[r] @ np.asarray(layer["w1"][e])
                act = ab[:WIDTH] / (1 + np.exp(-ab[:WIDTH])) * ab[WIDTH:]
                out[r] += g * (act @ np.asarray(layer["w2"][e]))
    return out


@pytest.mark.parametrize("router", list(ROUTERS))
@pytest.mark.parametrize("first,held", [(0, 8), (0, 4), (4, 4), (2, 3)])
def test_the_held_part_is_the_dense_sum_over_held_choices(layer, router,
                                                          first, held):
    idx, gate = ROUTERS[router](layer["u"] @ layer["router"])
    got, counters = experts.expert_ffn(
        layer["u"], idx, gate, layer["w1"][first:first + held],
        layer["w2"][first:first + held], (first, held), jnp.float32)
    np.testing.assert_allclose(got, _dense(layer, idx, gate, first, held),
                               atol=2e-5, rtol=2e-5)
    mine = (np.asarray(idx) >= first) & (np.asarray(idx) < first + held)
    sizes = np.bincount(np.asarray(idx)[mine] - first, minlength=held)
    assert counters.tolist() == [mine.sum(), (sizes > 0).sum(), sizes.max()]


@pytest.mark.parametrize("router", list(ROUTERS))
def test_rows_that_are_not_valid_are_routed_nowhere(layer, router):
    idx, gate = ROUTERS[router](layer["u"] @ layer["router"])
    valid = np.arange(ROWS) % 3 != 1
    got, counters = experts.expert_ffn(
        layer["u"], idx, gate, layer["w1"][:4], layer["w2"][:4], (0, 4),
        jnp.float32, jnp.asarray(valid))
    np.testing.assert_allclose(got, _dense(layer, idx, gate, 0, 4, valid),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[~valid].any()
    assert int(counters[0]) == ((np.asarray(idx) < 4) & valid[:, None]).sum()
