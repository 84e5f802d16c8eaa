"""The device side of the paged KV arena: a pytree of per-layer leaves.

    {"k": (leaf_0, ..., leaf_{L-1}), "v": (...)}         exact
    + {"k_scale": (...), "v_scale": (...)}               block-scaled int8
    {"latent": (leaf_0, ..., leaf_{L-1})}                latent attention

Each leaf is ONE layer's pages, [n_pages, (kv_)heads, page_tokens,
head_dim] (scale leaves end in head_dim // block), and is a buffer of its
own.  A latent leaf has no heads axis, [n_pages, page_tokens, width]: one
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

__all__ = ["init_page_arena", "init_latent_arena", "write_row",
           "write_rows", "write_chunk",
           "export_page", "import_page"]


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

    def leaves(last, dt):
        # one allocation per leaf: leaves that shared a buffer could not
        # each be donated
        return tuple(jnp.zeros((n_pages, heads, page_tokens, last), dt)
                     for _ in range(layers))

    if quant_dtype in (None, "none"):
        return {"k": leaves(head_dim, dtype), "v": leaves(head_dim, dtype)}
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


def write_row(leaf, new, write_page, offset):
    """One new K or V row per sequence (decode): new [b, h, hd],
    write_page / offset int32 [b]; a latent leaf takes new [b, width]."""
    if leaf.ndim == 3:
        return _write_latent(leaf, new, write_page, offset)
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
    heads = jnp.arange(leaf.shape[1], dtype=jnp.int32)
    return leaf.at[write_page[:, None, :], heads[None, :, None],
                   offset[:, None, :]].set(new.astype(leaf.dtype),
                                           mode="drop")


def write_chunk(leaf, new, write_page):
    """One whole page per sequence (chunked prefill is page-aligned, so a
    chunk fills exactly one freshly allocated page): new [b, h, pt, hd]
    (a latent leaf: [b, pt, width]), write_page int32 [b]."""
    return leaf.at[write_page].set(new.astype(leaf.dtype), mode="drop")


def export_page(arena, page):
    """One page of every leaf, stacked over layers:
    {key: [layers, heads, page_tokens, *]} ([layers, page_tokens, width]
    of a latent arena)."""
    return {k: jnp.stack([jax.lax.dynamic_index_in_dim(
                              leaf, page, axis=0, keepdims=False)
                          for leaf in leaves])
            for k, leaves in arena.items()}


def import_page(arena, chunk_kv, page):
    """Write an exported page back at `page`, leaf by leaf in place."""
    return {k: tuple(jax.lax.dynamic_update_index_in_dim(
                         leaf, chunk_kv[k][li].astype(leaf.dtype), page,
                         axis=0)
                     for li, leaf in enumerate(leaves))
            for k, leaves in arena.items()}
