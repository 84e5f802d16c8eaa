"""The device side of the paged KV arena: a pytree of per-layer leaves.

    {"k": (leaf_0, ..., leaf_{L-1}), "v": (...)}         exact
    + {"k_scale": (...), "v_scale": (...)}               block-scaled int8
    {"latent": (leaf_0, ..., leaf_{L-1})}                latent attention

Each leaf is ONE layer's pages, [n_pages, (kv_)heads, page_tokens,
head_dim] (scale leaves end in head_dim // block), and is a buffer of its
own.  An exact leaf of NARROW heads (head_dim 64 or 32: it divides 128 and
is less) is stored LANE-DENSE, [n_pages, kv_heads, page_tokens / parts,
parts * head_dim] with parts = 128 / head_dim (`lane_parts`): row r of a
page holds its positions r * parts + i, position i in the lanes [i *
head_dim, (i + 1) * head_dim) — the bytes of the plain leaf in the plain
order, so a reshape is all that lies between the two, and a v5e, which
keeps a plain narrow leaf with its PAGES on the lanes, keeps this one
row-major, which is how the paged kernels take it: nothing is laid out
again round a call.  Leaves of 128 and wider, the int8 arena (payload and
scales) and latent leaves are stored as they always were.  The writes below
tell the two forms by the new rows' own width; what reads a leaf outside
the kernels goes through `plain_pages`.  A latent leaf has no heads axis,
[n_pages, page_tokens, width]: one
row a position, which every head attends (`init_latent_arena`).  That is
what lets a compiled step update the arena in place: the
jit donates every leaf (`infer_state_io` pairs a tuple of leaves
positionally), a layer's write is a scatter whose operand is that layer's
donated input, and the written leaf is returned as it is — no layer is
ever sliced out of a stacked array and none is stacked back, so no
operation of a step produces a buffer the size of a leaf.  Presence of
the scale keys is the quantization signal the paged forwards branch on.

`export_page` / `import_page` move ONE page of every leaf and keep the
wire format the trie, the host tier and the fleet transport have always
had — `{key: [layers, heads, page_tokens, *]}`, a latent page `{"latent":
[layers, page_tokens, width]}` — so manifests and digests do not depend on
how the arena is laid out on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_page_arena", "init_latent_arena", "lane_parts",
           "plain_pages", "write_row", "write_rows", "write_chunk",
           "export_page", "import_page"]


def lane_parts(head_dim: int, page_tokens: int) -> int:
    """The positions of a page that share a 128-lane row of an exact leaf:
    128 / head_dim where head_dim divides 128 and is less than it and the
    page's positions divide so (heads of 64: 2, of 32: 4), else 1 — the
    leaf as it always was."""
    parts = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    return parts if page_tokens % parts == 0 else 1


def plain_pages(leaf, head_dim: int):
    """A leaf as [n_pages, heads, page_tokens, head_dim], whichever way it
    is stored: a lane-dense one reshaped (its bytes are in that order), any
    other — a scale leaf, whose minor dim is narrower than a head, too — as
    it is."""
    if leaf.ndim != 4 or leaf.shape[-1] <= head_dim:
        return leaf
    n, h, rows, lanes = leaf.shape
    return leaf.reshape(n, h, rows * lanes // head_dim, head_dim)


def init_page_arena(layers: int, n_pages: int, heads: int, page_tokens: int,
                    head_dim: int, dtype, quant_dtype=None,
                    quant_block: int = 0):
    """Zeroed arena of `layers` leaves a key.  `quant_dtype="int8"` stores
    the payload block-scaled int8 and adds f32 scale leaves
    ([..., head_dim // block]; `quant_block` 0 = one block per row)."""
    if n_pages < 1:
        raise ValueError(f"n_pages must be >= 1, got {n_pages}")
    if page_tokens < 1:
        raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")

    def leaves(last, dt, parts=1):
        # one allocation per leaf: leaves that shared a buffer could not
        # each be donated
        return tuple(jnp.zeros((n_pages, heads, page_tokens // parts,
                                parts * last), dt) for _ in range(layers))

    if quant_dtype in (None, "none"):
        parts = lane_parts(head_dim, page_tokens)
        return {"k": leaves(head_dim, dtype, parts),
                "v": leaves(head_dim, dtype, parts)}
    if quant_dtype != "int8":
        raise ValueError(f"quant_dtype must be None/'none'/'int8', "
                         f"got {quant_dtype!r}")
    block = quant_block or head_dim
    if head_dim % block:
        raise ValueError(f"quant_block {block} must divide head_dim "
                         f"{head_dim}")
    return {"k": leaves(head_dim, jnp.int8),
            "v": leaves(head_dim, jnp.int8),
            "k_scale": leaves(head_dim // block, jnp.float32),
            "v_scale": leaves(head_dim // block, jnp.float32)}


def init_latent_arena(layers: int, n_pages: int, page_tokens: int,
                      width: int, dtype):
    """Zeroed arena of a model with latent attention: ONE leaf a layer,
    [n_pages, page_tokens, width] — a position's row is what every head
    reads as its key and, in its leading columns, as its value."""
    if n_pages < 1:
        raise ValueError(f"n_pages must be >= 1, got {n_pages}")
    if page_tokens < 1:
        raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
    return {"latent": tuple(jnp.zeros((n_pages, page_tokens, width), dtype)
                            for _ in range(layers))}


# The three writes of a paged step.  `leaf` is one layer's pages
# [n_pages, h, pt, hd]; unmapped rows carry the sentinel page `n_pages`,
# which mode="drop" discards — dead rows touch nothing.
#
# A row write indexes (page, head, offset) — three index dims in the leaf's
# own major-to-minor order, the update a run of [hd] rows.  Indexed
# `[page, :, offset, :]` (two index dims round a window over heads) the same
# scatter made XLA's TPU layout assignment give the leaf the layout the
# scatter likes, pages-tokens-heads, and copy the WHOLE leaf into it before
# the write and back out for the kernel: two passes over the arena a round
# (PERF.md, PR 28).  tests/test_kv/test_arena_inplace.py compiles the decode
# step for a v5e to hold this.  A latent leaf [n_pages, pt, width] has no
# heads to range over: the same writes without that index.

def _write_latent(leaf, new, write_page, offset):
    """Rows of a latent leaf, one a (page, offset) pair: new [..., width],
    write_page / offset int32 [...]."""
    return leaf.at[write_page, offset].set(new.astype(leaf.dtype),
                                           mode="drop")


def _write_lanes(leaf, new, write_page, offset):
    """`s` consecutive rows a sequence into a lane-dense leaf [n_pages, h,
    pt / parts, parts * hd]: new [b, h, s, hd], write_page / offset int32
    [b, s].  Position `offset` of a page is the lanes [(offset % parts) *
    hd, + hd) of its row offset // parts, and neighbours SHARE a row, so
    whole rows cannot be written back: ONE scatter whose four index dims
    are (page, head, row, first lane) and whose update is a window of hd
    lanes — nothing reshapes the leaf.  For the chip XLA expands it into a
    loop of one update a turn; only a verify step (speculation) of a model
    with narrow heads takes it, and no cell serves one."""
    hd = new.shape[-1]
    parts = leaf.shape[-1] // hd
    heads = jnp.arange(leaf.shape[1], dtype=jnp.int32)
    page, offset, heads = (write_page[:, None, :], offset[:, None, :],
                           heads[None, :, None])
    idx = jnp.stack(jnp.broadcast_arrays(
        page, heads, offset // parts, (offset % parts) * hd), axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(3,), inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2, 3))
    return jax.lax.scatter(
        leaf, idx.astype(jnp.int32), new.astype(leaf.dtype), dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _write_row_lanes(leaf, new, write_page, offset):
    """One row a sequence into a lane-dense leaf: the 128-lane row that
    holds the position is READ (a gather of b x h rows), the position's hd
    lanes replaced, and the whole row written back by the scatter a plain
    leaf of heads of 128 takes — three index dims (page, head, row), the
    update a run of whole rows, which a v5e scatters natively and in place.
    (The window form of `_write_lanes` XLA expands for the chip into a
    `while` of one `dynamic-update-slice` a row a head, 2,048 turns a leaf
    at 256 slots on 8 heads: PERF.md section 6, PR 48.)  No two sequences
    share a page, so no two rows of the call share a row of the leaf; a
    dead row reads a clipped page and its write drops."""
    hd = new.shape[-1]
    parts = leaf.shape[-1] // hd
    heads = jnp.arange(leaf.shape[1], dtype=jnp.int32)
    at = (write_page[:, None], heads[None, :], (offset // parts)[:, None])
    row = leaf.at[at].get(mode="clip")                  # [b, h, parts * hd]
    mine = (jnp.arange(parts * hd, dtype=jnp.int32) // hd)[None, None, :] \
        == (offset % parts)[:, None, None]
    row = jnp.where(mine, jnp.tile(new.astype(leaf.dtype), (1, 1, parts)),
                    row)
    return leaf.at[at].set(row, mode="drop")


def write_row(leaf, new, write_page, offset):
    """One new K or V row per sequence (decode): new [b, h, hd],
    write_page / offset int32 [b]; a latent leaf takes new [b, width].  In
    a lane-dense leaf the row is hd lanes of one 128-lane row
    (`_write_row_lanes`)."""
    if leaf.ndim == 3:
        return _write_latent(leaf, new, write_page, offset)
    if leaf.shape[-1] != new.shape[-1]:
        return _write_row_lanes(leaf, new, write_page, offset)
    heads = jnp.arange(leaf.shape[1], dtype=jnp.int32)
    return leaf.at[write_page[:, None], heads[None, :],
                   offset[:, None]].set(new.astype(leaf.dtype), mode="drop")


def write_rows(leaf, new, write_page, offset):
    """`s` consecutive rows per sequence (verify; a window may straddle a
    page boundary, so each position resolves its own page): new
    [b, h, s, hd], write_page / offset int32 [b, s]; a latent leaf takes
    new [b, s, width]."""
    if leaf.ndim == 3:
        return _write_latent(leaf, new, write_page, offset)
    if leaf.shape[-1] != new.shape[-1]:
        return _write_lanes(leaf, new, write_page, offset)
    heads = jnp.arange(leaf.shape[1], dtype=jnp.int32)
    return leaf.at[write_page[:, None, :], heads[None, :, None],
                   offset[:, None, :]].set(new.astype(leaf.dtype),
                                           mode="drop")


def write_chunk(leaf, new, write_page):
    """One whole page per sequence (chunked prefill is page-aligned, so a
    chunk fills exactly one freshly allocated page): new [b, h, pt, hd]
    (a latent leaf: [b, pt, width]), write_page int32 [b].  A lane-dense
    leaf takes the page as it stores it, `parts` positions to a row: a
    reshape of the chunk's own rows, never of the leaf."""
    if leaf.ndim == 4 and leaf.shape[-1] != new.shape[-1]:
        new = new.reshape(new.shape[:2] + leaf.shape[2:])
    return leaf.at[write_page].set(new.astype(leaf.dtype), mode="drop")


def export_page(arena, page, head_dim=None):
    """One page of every leaf, stacked over layers:
    {key: [layers, heads, page_tokens, *]} ([layers, page_tokens, width]
    of a latent arena).  `head_dim` is the model's: with it a lane-dense
    leaf's page leaves in the wire format too (without it a leaf's page
    leaves as it is stored)."""
    def page_of(leaf):
        one = jax.lax.dynamic_index_in_dim(leaf, page, axis=0)
        return (plain_pages(one, head_dim) if head_dim else one)[0]

    return {k: jnp.stack([page_of(leaf) for leaf in leaves])
            for k, leaves in arena.items()}


def import_page(arena, chunk_kv, page):
    """Write an exported page back at `page`, leaf by leaf in place (a
    lane-dense leaf takes the wire format's page `parts` positions to a
    row)."""
    return {k: tuple(jax.lax.dynamic_update_index_in_dim(
                         leaf, chunk_kv[k][li].reshape(leaf.shape[1:])
                         .astype(leaf.dtype), page, axis=0)
                     for li, leaf in enumerate(leaves))
            for k, leaves in arena.items()}
