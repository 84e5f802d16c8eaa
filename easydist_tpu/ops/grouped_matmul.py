"""Grouped matrix products over the experts a chip holds, dropless.

A token routed to an expert is one ROW; `group_rows` lays the rows of each
held expert out in whole blocks of `block_rows` (an expert with no row gets
no block, an expert with 130 rows of 128 gets two), and `grouped_matmul`
multiplies block i by the weights of `block_expert[i]`:

    out[i * tm:(i + 1) * tm] = x[i * tm:(i + 1) * tm] @ w[block_expert[i]]

Nothing is dropped and there is no capacity: the number of blocks is
bounded by shapes alone (ceil(rows / tm) + experts) and the blocks past the
live ones are skipped — their expert index repeats the last live block's, so
no weight is fetched for them, and they compute nothing.  On a TPU this is
a Pallas kernel whose weight index map reads `block_expert` from
scalar-prefetch memory; elsewhere, a gather of the blocks' weights and a
batched einsum.

A row is whatever the caller numbers: `group_rows` sees a flat vector of
experts and hands back places (`dest`) and the rows at them (`source`) in
the caller's numbering.  `models/granite_hybrid.py::expert_ffn` numbers its
(token, choice) pairs SLOT-major, `choice * tokens + token`, so `source %
tokens` is the token to fetch and the products gathered back by `dest` are
`top_k` runs of `tokens` rows, summed slice by slice.  Token-major
(`token * top_k + choice`) the same sum needs a `[tokens, top_k, dim]`
view, whose second-minor `top_k` pads to the (8, 128) tile: a physical
copy (PR 32).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret

__all__ = ["RowGroups", "group_rows", "grouped_matmul"]


class RowGroups(NamedTuple):
    """Where `group_rows` put each row.  `dest` int32 [rows]: the row's
    place in the blocked layout, `n_blocks * block_rows` for a row of no
    held expert; `source` int32 [n_blocks * block_rows]: the row held at
    each place, in the order the caller flattened its rows in (`rows`
    where none is); `block_expert` int32 [n_blocks];
    `live_blocks` int32 []; `sizes` int32 [experts]: rows per expert."""
    dest: jax.Array
    source: jax.Array
    block_expert: jax.Array
    live_blocks: jax.Array
    sizes: jax.Array
    block_rows: int


def group_rows(expert, n_experts: int, block_rows: int) -> RowGroups:
    """`expert` int32 [rows]: the held expert (0..n_experts-1) each row
    goes to, `n_experts` for a row this chip computes nothing for."""
    rows = expert.shape[0]
    tm = block_rows
    n_blocks = -(-rows // tm) + n_experts
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[expert].add(1)
    sizes = sizes[:n_experts]
    blocks = -(-sizes // tm)                       # blocks per expert
    block_end = jnp.cumsum(blocks)                 # exclusive ends
    first = (block_end - blocks) * tm              # first place per expert
    order = jnp.argsort(expert, stable=True)       # rows by expert
    sorted_e = expert[order]
    rank = jnp.arange(rows, dtype=jnp.int32) - jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])[
            jnp.minimum(sorted_e, n_experts)]
    held = sorted_e < n_experts
    place = jnp.where(held, first[jnp.minimum(sorted_e, n_experts - 1)]
                      + rank, n_blocks * tm).astype(jnp.int32)
    dest = jnp.zeros((rows,), jnp.int32).at[order].set(place)
    source = jnp.full((n_blocks * tm,), rows, jnp.int32).at[place].set(
        order.astype(jnp.int32), mode="drop")
    live = block_end[-1]
    # the expert of block i; a dead block repeats the last live block's
    ids = jnp.minimum(jnp.arange(n_blocks, dtype=jnp.int32),
                      jnp.maximum(live - 1, 0))
    block_expert = jnp.minimum(
        jnp.sum(block_end[None, :] <= ids[:, None], axis=1,
                dtype=jnp.int32), n_experts - 1)
    return RowGroups(dest, source, block_expert, live.astype(jnp.int32),
                     sizes, tm)


def _gmm_xla(x, w, block_expert, live_blocks, tm: int):
    nb = block_expert.shape[0]
    xb = x.reshape(nb, tm, x.shape[-1])
    out = jnp.einsum("btk,bkn->btn", xb, w[block_expert],
                     preferred_element_type=jnp.float32)
    out = jnp.where((jnp.arange(nb) < live_blocks)[:, None, None], out, 0)
    return out.astype(x.dtype).reshape(nb * tm, w.shape[-1])


def _gmm_kernel(be_ref, live_ref, x_ref, w_ref, o_ref, acc):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(i < live_ref[0])
    def _live():
        @pl.when(k == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(2) - 1)
        def _write():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def _tile(n: int, want: int) -> int:
    """n where it is at most `want`, else the largest multiple of 128 that
    divides n and is at most `want` (n itself where none does)."""
    if n <= want:
        return n
    fits = [t for t in range(128, want + 1, 128) if n % t == 0]
    return fits[-1] if fits else n


def grouped_matmul(x, w, block_expert, live_blocks, block_rows: int,
                   interpret=None, backend=None):
    """x [n_blocks * block_rows, k] (the blocked layout of `group_rows`), w
    [experts, k, n] -> [n_blocks * block_rows, n] in x's dtype, float32
    accumulation.  Rows of dead blocks come back unwritten (the kernel) or
    zero (the fallback): nothing may read them."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    tm = block_rows
    if backend == "xla":
        return _gmm_xla(x, w, block_expert, live_blocks, tm)
    if interpret is None:
        interpret = _default_interpret()
    m, kk = x.shape
    _, _, n = w.shape
    nb = m // tm
    itemsize = jnp.dtype(w.dtype).itemsize
    tn = _tile(n, 2048)
    # a weight block of at most ~3 MiB (it is double-buffered)
    tk = _tile(kk, max(128, (3 * 2 ** 20) // (tn * itemsize) // 128 * 128))

    nj, nk = n // tn, kk // tk

    def dead_to_last(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    def x_map(i, j, k, be, live):
        # a dead block re-reads what the last live step read: no copy
        dead = i >= live[0]
        return (dead_to_last(i, live),
                jnp.where(dead, nk - 1, k))

    def w_map(i, j, k, be, live):
        dead = i >= live[0]
        return (be[i], jnp.where(dead, nk - 1, k),
                jnp.where(dead, nj - 1, j))

    def o_map(i, j, k, be, live):
        dead = i >= live[0]
        return (dead_to_last(i, live),
                jnp.where(dead, nj - 1, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, nj, nk),
        in_specs=[pl.BlockSpec((tm, tk), x_map),
                  pl.BlockSpec((1, tk, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    with jax.named_scope("grouped_matmul"):
        return pl.pallas_call(
            _gmm_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=48 * 2 ** 20),
            interpret=interpret,
            name="grouped_matmul",
        )(block_expert.astype(jnp.int32),
          jnp.reshape(live_blocks, (1,)).astype(jnp.int32), x, w)
