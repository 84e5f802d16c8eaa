"""The routed expert FFN of a chip that holds SOME of a layer's experts,
dropless: one body for every expert model.

A model says how its router's scores become choices — `idx` int32 [rows, k]
(the k experts each token chose, numbered over ALL the layer's experts) and
`gate` float32 [rows, k] (the weight of each choice) — and `expert_ffn`
does the rest: which pairs are for the experts held here (`experts_held`:
first, how many), the rows of each held expert laid out in whole blocks
(`ops/grouped_matmul.py::group_rows`), the two grouped products of a
SwiGLU, the weighted sum back to tokens, and three counters.  What the
absent experts would add is left out: on one chip that is the whole
layer's work here — no exchange, and nothing stands in for the other
chips.  A shared expert is a dense SwiGLU (`glu`), added by the model.

    granite_hybrid   top-10 of the logits, softmax over the chosen
    exaone_moe       sigmoid scores, top-8 of score + bias, the chosen
                     scores normalised and scaled (`sigmoid_route`)
    axk1             the same without the bias
    lfm2_moe         the same with it, top-4 of 32, the normaliser 1e-6
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["expert_ffn", "glu", "sigmoid_route"]


def glu(x, w1, w2, dtype):
    """One expert as a dense SwiGLU — a shared expert, a dense layer's FFN:
    (silu(a) * b) @ w2 with [a | b] = x @ w1."""
    ab = x @ w1.astype(dtype)
    half = ab.shape[-1] // 2
    return (jax.nn.silu(ab[..., :half]) * ab[..., half:]) @ w2.astype(dtype)


def sigmoid_route(u, router, top_k: int, scale: float, bias=None,
                  eps: float = 1e-20):
    """u [rows, dim] -> (idx int32 [rows, top_k], gate float32 [rows,
    top_k]): sigmoid scores in float32, the top `top_k` of score (+ `bias`,
    which steers the choice and never gates), the chosen scores normalised
    (their sum + `eps`) and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        u, router.astype(u.dtype), preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def expert_ffn(u, idx, gate, w1, w2, experts_held: Tuple[int, int], dtype,
               valid=None):
    """The held experts' part of the routed FFN: u [rows, dim] (already
    normed), idx / gate [rows, k] as above, w1 [held, dim, 2 * expert_dim],
    w2 [held, expert_dim, dim], valid bool [rows] or None -> (out [rows,
    dim], counters int32 [3]: pairs routed to held experts, held experts
    hit, the busiest held expert's pairs).  Rows that are not valid are
    routed nowhere.

    The routed (token, choice) pairs are numbered SLOT-major: flat pair
    `j * rows + r` is token r's j-th choice, so `source % rows` is the
    token of the pair at a place of the blocked layout.  The sum back to
    tokens follows the PLACES, which hold the pairs of held experts and
    nothing else — not the k x rows pair slots, most of them for experts
    held elsewhere — and happens inside the second product
    (`grouped_matmul_sum`): a pair's row never reaches HBM."""
    from easydist_tpu.ops.grouped_matmul import (group_rows, grouped_matmul,
                                                 grouped_matmul_sum)

    dtype = jnp.dtype(dtype)
    rows, k = idx.shape
    first, held = experts_held
    local = idx.astype(jnp.int32).T - first
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine &= valid[None, :]
    expert = jnp.where(mine, local, held).reshape(k * rows)
    # a block per ~expert's share of the pairs: 128 rows where experts see
    # that many (prefill), 32 where a round gives each a handful (decode)
    # (float32, the tests' type, tiles in 8s)
    tm = 8 if dtype.itemsize == 4 else 128 if rows * k >= 64 * held else 32
    g = group_rows(expert, held, tm)
    # a place that holds no pair reads `k * rows`: row 0 (any real row
    # does), a token that is no row's and a gate of 0
    token_at = jnp.where(g.source < k * rows, g.source % rows, rows)
    gate_at = jnp.take(gate.astype(jnp.float32).T.reshape(k * rows),
                       g.source, mode="fill", fill_value=0.0)
    xb = jnp.take(u, g.source % rows, axis=0, mode="clip")
    hid = grouped_matmul(xb, w1.astype(dtype), g.block_expert,
                         g.live_blocks, tm)
    half = hid.shape[-1] // 2
    act = jax.nn.silu(hid[:, :half]) * hid[:, half:]
    total = grouped_matmul_sum(act, w2.astype(dtype), g, token_at, gate_at,
                               rows)
    counters = jnp.stack([jnp.sum(g.sizes), jnp.sum(g.sizes > 0),
                          jnp.max(g.sizes)]).astype(jnp.int32)
    return total, counters
