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
the caller's numbering.  `models/experts.py::expert_ffn` numbers its
(token, choice) pairs SLOT-major, `choice * tokens + token`, so `source %
tokens` is the token to fetch for a place — and the token the place's
product belongs to.

The way back to tokens is `grouped_matmul_sum`, the SECOND product of an
expert FFN: the same blocks and weights, but what leaves the kernel is
`[tokens, n]`, each live block's rows scaled by their pairs' gates and
added to their tokens where the float32 accumulator lies, in VMEM.  The
products themselves never exist in HBM.  A chip holds a share of a layer's
experts, so of the `top_k x tokens` pair slots most hold nothing for it
(half in the Granite cell, 7 of 8 in K-EXAONE's, 15 of 16 in A.X-K1's): a
gather of the products back by `dest` and a sum over `top_k` slices of it
(until PR 43) walked all of them, 6.35 + 1.1 ms of Granite's 47 ms chunk
call; the sum inside the kernel walks the places, which hold the held
experts' pairs and nothing else.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret

__all__ = ["RowGroups", "group_rows", "grouped_matmul",
           "grouped_matmul_sum"]


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
    return out.reshape(nb * tm, w.shape[-1])


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


def _gmm_sum_kernel(be_ref, live_ref, x_ref, w_ref, token_ref, gate_ref,
                    o_ref, acc, total):
    """Grid (column tile, block, contraction tile): `total` [rows, tn]
    float32 is the column tile's share of EVERY token's sum and outlives the
    blocks; a live block's finished product is scaled by its rows' gates in
    float32, rounded once, and added to the rows of `total` its tokens
    name by a one-hot product (a token is in a block at most once: exact)."""
    i, k = pl.program_id(1), pl.program_id(2)
    last_k = k == pl.num_programs(2) - 1

    @pl.when((i == 0) & (k == 0))
    def _zero():
        total[...] = jnp.zeros_like(total)

    @pl.when(i < live_ref[0])
    def _live():
        @pl.when(k == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

        @pl.when(last_k)
        def _add():
            # a place with no pair was multiplied from some real row, which
            # may hold anything: 0 x NaN would reach every token
            gate = gate_ref[0]
            pairs = jnp.where(gate != 0, acc[...] * gate, 0).astype(
                o_ref.dtype)
            token = jax.lax.broadcasted_iota(
                jnp.int32, (total.shape[0], pairs.shape[0]), 0)
            total[...] += jnp.dot(
                (token == token_ref[0]).astype(o_ref.dtype), pairs,
                preferred_element_type=jnp.float32)

    @pl.when((i == pl.num_programs(1) - 1) & last_k)
    def _write():
        o_ref[...] = total[...].astype(o_ref.dtype)


def _tile(n: int, want: int) -> int:
    """n where it is at most `want`, else the largest multiple of 128 that
    divides n and is at most `want` (n itself where none does)."""
    if n <= want:
        return n
    fits = [t for t in range(128, want + 1, 128) if n % t == 0]
    return fits[-1] if fits else n


@functools.lru_cache(maxsize=64)
def _gmm_call(m: int, kk: int, n: int, dtype: str, tm: int,
              rows: Optional[int], interpret: bool):
    """The pallas_call of a grouped product over x [m, kk] and w [experts,
    kk, n] of `dtype`, built ONCE a signature and a `jax.jit`, so that a
    model's expert layers share one kernel jaxpr and one lowering
    (`ops/flash_attention.py::_paged_call` says what a call a layer cost).
    `rows` None: `grouped_matmul`, grid (block, column tile, contraction
    tile), the product [m, n].  `rows` given: `grouped_matmul_sum`, the
    column tile OUTERMOST, so that the result's block [rows, tn] does not
    move while the blocks go by, and two more operands a block: its rows'
    tokens [1, tm] and gates [tm, 1]."""
    nb = m // tm
    itemsize = jnp.dtype(dtype).itemsize
    tn = _tile(n, 2048)
    # a weight block of at most ~3 MiB (it is double-buffered)
    tk = _tile(kk, max(128, (3 * 2 ** 20) // (tn * itemsize) // 128 * 128))
    nj, nk = n // tn, kk // tk
    fused = rows is not None

    def block(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    # a dead block re-reads what the last live step read: no copy.  That
    # step is the last of everything (block outermost) or of its column
    # tile (column tile outermost)
    def x_map(i, j, k, be, live):
        return block(i, live), jnp.where(i >= live[0], nk - 1, k)

    def w_map(i, j, k, be, live):
        dead = i >= live[0]
        return (be[i], jnp.where(dead, nk - 1, k),
                j if fused else jnp.where(dead, nj - 1, j))

    def o_map(i, j, k, be, live):
        return block(i, live), jnp.where(i >= live[0], nj - 1, j)

    def row_map(i, j, k, be, live):
        return block(i, live), 0, 0

    def spec(shape, index_map):
        if fused:   # the grid's ids come (column tile, block, ...)
            return pl.BlockSpec(
                shape, lambda j, i, *rest: index_map(i, j, *rest))
        return pl.BlockSpec(shape, index_map)

    in_specs = [spec((tm, tk), x_map), spec((1, tk, tn), w_map)]
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if fused:
        in_specs += [spec((1, 1, tm), row_map), spec((1, tm, 1), row_map)]
        out_spec = spec((rows, tn), lambda i, j, k, be, live: (0, j))
        scratch += [pltpu.VMEM((rows, tn), jnp.float32)]
    else:
        out_spec = spec((tm, tn), o_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nj, nb, nk) if fused else (nb, nj, nk),
        in_specs=in_specs, out_specs=out_spec, scratch_shapes=scratch)
    return pl.pallas_call(
        _gmm_sum_kernel if fused else _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows if fused else m, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="grouped_matmul_sum" if fused else "grouped_matmul",
    )


def _backend(backend, interpret):
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if interpret is None:
        interpret = _default_interpret()
    return backend, bool(interpret)


def _scalars(block_expert, live_blocks):
    return (block_expert.astype(jnp.int32),
            jnp.reshape(live_blocks, (1,)).astype(jnp.int32))


def grouped_matmul(x, w, block_expert, live_blocks, block_rows: int,
                   interpret=None, backend=None):
    """x [n_blocks * block_rows, k] (the blocked layout of `group_rows`), w
    [experts, k, n] -> [n_blocks * block_rows, n] in x's dtype, float32
    accumulation.  Rows of dead blocks come back unwritten (the kernel) or
    zero (the fallback): nothing may read them."""
    backend, interpret = _backend(backend, interpret)
    if backend == "xla":
        return _gmm_xla(x, w, block_expert, live_blocks,
                        block_rows).astype(x.dtype)
    call = _gmm_call(*x.shape, w.shape[-1], x.dtype.name, block_rows, None,
                     interpret)
    with jax.named_scope("grouped_matmul"):
        return call(*_scalars(block_expert, live_blocks), x, w)


def grouped_matmul_sum(x, w, groups: RowGroups, token_at, gate_at, rows: int,
                       interpret=None, backend=None):
    """The second product of an expert FFN and the weighted sum back to
    tokens, in one: x, w as `grouped_matmul`'s, `token_at` int32 and
    `gate_at` float32 [n_blocks * block_rows] — the token (0..rows-1) whose
    pair sits at each place of the blocked layout and the pair's gate;
    `rows` and 0 where the place holds no pair -> [rows, n] in x's dtype:

        out[t] = sum over places p with token_at[p] == t of
                 round(gate_at[p] * (x[p] @ w[block_expert[p // tm]]))

    A pair's product is accumulated in float32, scaled by its gate in
    float32 and rounded ONCE to x's dtype (what writing it to HBM did); a
    token's pairs are summed in float32.  A token may sit in a block at
    most once (a block is one expert's, and a token chooses an expert
    once).  The products never reach HBM: nothing a dead block left
    unwritten can be read, and a token with no pair here gets zeros."""
    backend, interpret = _backend(backend, interpret)
    tm = groups.block_rows
    if backend == "xla":
        pairs = _gmm_xla(x, w, groups.block_expert, groups.live_blocks, tm)
        pairs = (pairs * gate_at[:, None]).astype(x.dtype)
        return jnp.zeros((rows, w.shape[-1]), jnp.float32).at[token_at].add(
            pairs.astype(jnp.float32), mode="drop").astype(x.dtype)
    call = _gmm_call(*x.shape, w.shape[-1], x.dtype.name, tm, rows,
                     interpret)
    nb = x.shape[0] // tm
    with jax.named_scope("grouped_matmul_sum"):
        return call(*_scalars(groups.block_expert, groups.live_blocks), x, w,
                    token_at.astype(jnp.int32).reshape(nb, 1, tm),
                    gate_at.astype(jnp.float32).reshape(nb, tm, 1))
