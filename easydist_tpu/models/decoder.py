"""The serving forward of a decoder-only transformer, written once.

A model file fills in a `Decoder` — its sizes and the arithmetic of one
block — and gets every serving step from here: `chunk` (chunked prefill),
`verify` (speculative scoring) and `decode` (one token), each against
either KV layout.  The layouts are the two adapters below, `Contiguous`
(the stacked [layers, batch, kv_heads, T, head_dim] cache of the bucketed
pools) and `Paged` (`kv/arena.py`: one leaf per layer, written in place
through a page table; the int8 arena lives here and nowhere else).

    cache, logits = decode(dec, Paged(pages, table), params, token, pos)

Every step is a pure function returning the updated cache first, so a jit
with the cache as argument 0 donates it (`serve/generation.py`).  There is
one kind of layer and no table of models: a model whose layers differ
branches inside the functions it supplies, all of which receive the block.

K and V are cached as the model's `qkv` returns them — positions already
applied (roped keys), at kv_heads granularity; the GQA repeat happens at
attention time, so cache bytes scale with kv_heads.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from easydist_tpu.kv.arena import (init_page_arena, write_chunk, write_row,
                                   write_rows)

__all__ = ["Decoder", "Contiguous", "Paged", "chunk", "verify", "decode",
           "split_heads"]


@dataclass(frozen=True)
class Decoder:
    """What the loop needs of a model.  Activations `x` are [b, s, dim] in
    a window step and [b, dim] in a decode step; `pos` is the absolute
    position of every row of `x` (int32 [b, s] / [b])."""
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    dtype: Any                       # compute dtype
    max_positions: Optional[int]     # learned position table; None = RoPE
    blocks: Callable      # params -> [block] * layers
    embed: Callable       # (params, tokens, pos) -> x
    qkv: Callable         # (block, x, pos) -> q, k, v  (`split_heads` form)
    attn_out: Callable    # (block, x, att) -> x; att is [..., heads*head_dim]
    ffn: Callable         # (block, x) -> x
    final_norm: Callable  # (params, x) -> x
    unembed: Callable     # (params, x) -> float32 logits [..., vocab]


def split_heads(y, n: int):
    """[b, s, n*hd] -> [b, n, s, hd] (window) or [b, n*hd] -> [b, n, hd]
    (decode): the shapes `qkv` returns and the adapters store."""
    if y.ndim == 2:
        return y.reshape(y.shape[0], n, -1)
    b, s, _ = y.shape
    return y.reshape(b, s, n, -1).transpose(0, 2, 1, 3)


def _merge_heads(att):
    if att.ndim == 3:
        return att.reshape(att.shape[0], -1)
    b, _, s, _ = att.shape
    return att.transpose(0, 2, 1, 3).reshape(b, s, -1)


def _window_positions(start, n: int):
    return start[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]


def _storage_dtype(dec: Decoder, dtype):
    return jnp.dtype(dec.dtype if dtype in (None, "auto") else dtype)


# ------------------------------------------------------------ cache adapters
#
# An adapter wraps ONE call's cache while a step is traced.  `seek` fixes
# where the step's new rows land (once, before the layers) and gives back
# their absolute positions; per layer, `write` stores the layer's new K/V
# and `attend` reads the layer's cache back, new rows included; `cache()`
# hands back the updated pytree.  Layers are written in order.


class Contiguous:
    """{"k", "v"}: [layers, batch, kv_heads, T, head_dim], a row per
    sequence.  Two leaves whatever the depth; the heads axis (dim 2) is the
    tensor-parallel shard dim.  Each step re-stacks the layers it wrote."""

    @staticmethod
    def init(dec: Decoder, batch: int, max_len: int, dtype=None):
        """Zeroed cache; `dtype=None`/"auto" stores at the compute dtype."""
        if dec.max_positions is not None and max_len > dec.max_positions:
            raise ValueError(
                f"max_len {max_len} exceeds the learned position table "
                f"(cfg.seq={dec.max_positions})")
        shape = (dec.layers, batch, dec.kv_heads, max_len, dec.head_dim)
        dt = _storage_dtype(dec, dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def __init__(self, cache):
        self._old, self._k, self._v = cache, [], []

    def seek(self, start, n: Optional[int] = None, aligned: bool = False):
        self._at = start
        return start if n is None else _window_positions(start, n)

    def _put(self, layer, new):
        """layer [b, n, T, hd]; new [b, n, hd] (one row) or [b, n, s, hd].
        Per-sequence dynamic_update_slice at a traced start: one compiled
        signature across every position."""
        def one(c, r, p):
            r = r[:, None, :] if new.ndim == 3 else r
            return jax.lax.dynamic_update_slice(c, r.astype(c.dtype),
                                                (0, p, 0))
        return jax.vmap(one)(layer, new, self._at)

    def write(self, k, v):
        li = len(self._k)
        self._k.append(self._put(self._old["k"][li], k))
        self._v.append(self._put(self._old["v"][li], v))

    def attend(self, dec: Decoder, q, pos):
        from easydist_tpu.ops import chunk_attention, decode_attention

        kf, vf = self._k[-1].astype(dec.dtype), self._v[-1].astype(dec.dtype)
        rep = dec.heads // dec.kv_heads
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
        if q.ndim == 3:
            return decode_attention(q, kf, vf, pos + 1)
        return chunk_attention(q, kf, vf, pos)

    def cache(self):
        return {"k": jnp.stack(self._k), "v": jnp.stack(self._v)}


class Paged:
    """`kv/arena.py`'s arena, {"k": (leaf per layer), "v": (...)} with each
    leaf [n_pages, kv_heads, page_tokens, head_dim], read and written
    through `table` (int32 [batch, max_pages]: the arena page of each
    `page_tokens` window of a sequence; unmapped entries hold the sentinel
    `n_pages`, through which writes drop and reads clip to a real page
    whose rows the length mask zeroes).  A layer's write lands in that
    layer's own donated leaf and the leaf is returned as it is.

    An int8 arena carries {"k_scale", "v_scale"} leaves ([..., head_dim //
    block], f32) beside the payload, and their presence is the signal:
    quantize on write, scales through the same indices, dequantize on read
    (after the gather, or inside the decode kernel).  A {"k", "v"} arena
    traces the exact program."""

    @staticmethod
    def init(dec: Decoder, n_pages: int, page_tokens: int, dtype=None,
             quant_dtype=None, quant_block: int = 0):
        """Zeroed arena; `quant_dtype="int8"` adds the scale leaves
        (`quant_block` 0 = one block per row)."""
        return init_page_arena(dec.layers, n_pages, dec.kv_heads,
                               page_tokens, dec.head_dim,
                               _storage_dtype(dec, dtype), quant_dtype,
                               quant_block)

    def __init__(self, pages, table):
        self._old, self._table = pages, table
        self._new = {key: [] for key in pages}
        self._pt = pages["k"][0].shape[2]
        self._quant_nb = pages["k_scale"][0].shape[-1] \
            if "k_scale" in pages else 0

    def seek(self, start, n: Optional[int] = None, aligned: bool = False):
        """One row at `start` (n None), `n` rows from `start` that may
        straddle a page boundary, or (`aligned`) a chunk that fills exactly
        the page of window `start // page_tokens`."""
        pt = self._pt
        if aligned and n != pt:
            raise ValueError(f"paged prefill chunk {n} != page_tokens {pt} "
                             f"(chunks must fill exactly one page)")
        tbl = self._tbl = self._table.astype(jnp.int32)

        def page_of(pos):       # sentinel for unmapped windows: writes drop
            return jnp.take_along_axis(tbl, (pos // pt)[:, None],
                                       axis=1)[:, 0]
        if n is None:
            page, offset = page_of(start), start % pt
            self._put = lambda leaf, new: write_row(leaf, new, page, offset)
            return start
        if aligned:
            page = page_of(start)
            self._put = lambda leaf, new: write_chunk(leaf, new, page)
            return _window_positions(start, n)
        pos = _window_positions(start, n)
        pages, offsets = jnp.take_along_axis(tbl, pos // pt, axis=1), pos % pt
        self._put = lambda leaf, new: write_rows(leaf, new, pages, offsets)
        return pos

    def write(self, k, v):
        from easydist_tpu.ops import kv_quantize

        old, new, li = self._old, self._new, len(self._new["k"])
        if self._quant_nb:
            k, k_scale = kv_quantize(k, self._quant_nb)
            v, v_scale = kv_quantize(v, self._quant_nb)
            new["k_scale"].append(self._put(old["k_scale"][li], k_scale))
            new["v_scale"].append(self._put(old["v_scale"][li], v_scale))
        new["k"].append(self._put(old["k"][li], k))
        new["v"].append(self._put(old["v"][li], v))

    def attend(self, dec: Decoder, q, pos):
        from easydist_tpu.ops import (chunk_attention, gather_pages,
                                      kv_dequantize, paged_decode_attention)

        tbl, quant = self._tbl, self._quant_nb
        last = {key: leaves[-1] for key, leaves in self._new.items()}
        if q.ndim == 3:
            # the kernel reads whole pages through the table, one KV read
            # per GQA group; int8 pages stream as they are
            if quant:
                return paged_decode_attention(
                    q, last["k"], last["v"], tbl, pos + 1,
                    k_scale=last["k_scale"], v_scale=last["v_scale"])
            return paged_decode_attention(
                q, last["k"].astype(dec.dtype), last["v"].astype(dec.dtype),
                tbl, pos + 1)

        def virtual(key):
            # the contiguous cache the table describes, GQA-repeated AFTER
            # the gather (payload and scales alike, so dequant commutes)
            got = gather_pages(last[key], tbl, n_heads=dec.heads)
            if quant:
                return kv_dequantize(
                    got, gather_pages(last[key + "_scale"], tbl,
                                      n_heads=dec.heads), dec.dtype)
            return got.astype(dec.dtype)

        return chunk_attention(q, virtual("k"), virtual("v"), pos)

    def cache(self):
        return {key: tuple(leaves) for key, leaves in self._new.items()}


# ------------------------------------------------------------------- steps


def _forward(dec: Decoder, kv, params, tokens, pos):
    """Embed, run every layer against `kv`, final norm: (cache, x)."""
    x = dec.embed(params, tokens, pos)
    for blk in dec.blocks(params):
        q, k, v = dec.qkv(blk, x, pos)
        kv.write(k, v)
        x = dec.attn_out(blk, x, _merge_heads(kv.attend(dec, q, pos)))
        x = dec.ffn(blk, x)
    return kv.cache(), dec.final_norm(params, x)


def chunk(dec: Decoder, kv, params, tokens, start_pos, lengths):
    """One fixed-size prefill chunk: `tokens` (int32 [batch, chunk]) at
    absolute positions `start_pos + [0..chunk)`; attention covers the FULL
    cache window masked to `key_pos <= query_pos`, so the traced shape does
    not depend on how much prompt is cached and a restored prefix is
    consumed as if recomputed.  Returns (cache, logits [batch, vocab]) at
    each row's last real position (`lengths - 1`): valid for rows whose
    chunk contains it, garbage nobody reads otherwise.  Against `Paged` a
    chunk fills exactly one page."""
    c_len = tokens.shape[1]
    start = start_pos.astype(jnp.int32)
    cache, x = _forward(dec, kv, params, tokens,
                        kv.seek(start, c_len, aligned=True))
    rel_last = jnp.clip(lengths.astype(jnp.int32) - 1 - start, 0, c_len - 1)
    last = jnp.take_along_axis(x, rel_last[:, None, None], axis=1)[:, 0]
    return cache, dec.unembed(params, last)


def verify(dec: Decoder, kv, params, tokens, pos):
    """Speculative verify: score `tokens` (int32 [batch, s]: the last
    committed token, then s-1 drafts) at positions `pos + [0..s)` in ONE
    forward and return (cache, logits [batch, s, vocab]) for all of them.
    Position i's logits equal what `decode` would give after feeding the
    first i tokens; rows written past the accepted prefix are the stale
    rows the position mask keeps out of every later step.  Callers
    guarantee pos + s fits the cache (every touched page mapped)."""
    cache, x = _forward(dec, kv, params, tokens,
                        kv.seek(pos.astype(jnp.int32), tokens.shape[1]))
    return cache, dec.unembed(params, x)


def decode(dec: Decoder, kv, params, token, pos):
    """One cached decode step: `token` (int32 [batch]) at position `pos`
    (int32 [batch], the row's current length) -> (cache, logits
    [batch, vocab]).  O(layers * pos) attention reads a token."""
    cache, x = _forward(dec, kv, params, token,
                        kv.seek(pos.astype(jnp.int32)))
    return cache, dec.unembed(params, x)
