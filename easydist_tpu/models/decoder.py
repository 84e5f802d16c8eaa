"""The serving forward of a decoder-only transformer, written once.

A model file fills in a `Decoder` — its sizes and the arithmetic of one
block — and gets every serving step from here: `chunk` (chunked prefill),
`verify` (speculative scoring) and `decode` (one token), each against
any cache adapter.  The adapters are below: `Contiguous` (a
stacked [layers, batch, kv_heads, T, head_dim] cache: the draft model's,
and the one-sequence step functions'; no session pool), `Paged` (`kv/arena.py`: one leaf per layer, written in place
through a page table; the int8 arena lives here and nowhere else) and
`Latent` (a paged arena of ONE row a position for all the heads, for a
model whose attention is latent).

    cache, logits = decode(dec, Paged(pages, table), params, token, pos)

Every step is a pure function returning the updated cache first, so a jit
with the cache as argument 0 donates it (`serve/generation.py`).  There is
no table of models: a model whose layers differ branches inside the
functions it supplies, all of which receive the block.  The loop knows two
KINDS of layer, by what they cache: "attention" (rows per position,
through `Contiguous`, `Paged` or `Latent`) and "state" (a recurrent state
per SEQUENCE, through `State`); a model with state layers says which is
which in `kinds`.  An attention layer that sees only its last `window`
positions says so in `windows`, and caches a ring of that many rows per
SEQUENCE (`Ring`, which `State` carries) instead of pages.  A model whose
attention is latent says so in `latent`, and caches one row a position
where the others cache a K and a V row a KV head.  What a model's `ffn`
counts (an expert layer's routing) is a matter of its own, `counts`: it
leaves a step on the adapter that ran it, with or without a `State`.

K and V are cached as the model's `qkv` returns them — positions already
applied (roped keys), at kv_heads granularity; the GQA repeat happens at
attention time, so cache bytes scale with kv_heads.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from easydist_tpu.kv.arena import (init_latent_arena, init_page_arena,
                                   write_chunk, write_row, write_rows)

__all__ = ["Decoder", "Contiguous", "Paged", "Latent", "State", "Ring",
           "chunk", "verify", "decode", "split_heads"]


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
    # A model with state layers fills in the three below; `valid` (bool,
    # the leading shape of x) marks the rows and positions that are real.
    # A model that keeps anything a SEQUENCE (state layers, window layers:
    # `per_sequence`) is stepped with a `State`.
    kinds: Optional[Tuple[str, ...]] = None   # "attention" | "state" a block
    state: Optional[Callable] = None  # (block, x, carry, valid) -> x, carry
    state_shapes: Optional[Dict[str, tuple]] = None  # name -> (shape, dtype)
    #                                                  of ONE sequence's carry
    # one entry per ATTENTION layer: how many positions back, its own
    # included, the layer sees (key j is visible to query i iff
    # i - window < j <= i); None = all of them.  Left None: every layer all.
    windows: Optional[Tuple[Optional[int], ...]] = None
    # latent attention: `qkv` returns (q, row, None) — q [b, heads, (s,)
    # head_dim] with the keys' up-projection absorbed and the scale applied,
    # row [b, (s,) head_dim] the ONE row the position caches for all heads —
    # and `attn_out` is handed, a head, the softmax-weighted sum of the
    # rows' leading `latent` columns (its values, still to be up-projected).
    # Then kv_heads is 1 and head_dim the row's width.
    latent: Optional[int] = None
    # the `ffn` counts: it is (block, x, valid) -> (x, int32 counters [n] or
    # None), and the loop sums the counters over the layers that gave some
    # and leaves them on the step's adapter (`.counters`)
    counts: bool = False
    # the (token, choice) slots a token offers such a model's expert layers,
    # top_k x expert layers: what `moe_pair_slots` counts a row of a program
    pair_slots: int = 0

    @property
    def ring_windows(self) -> Tuple[int, ...]:
        """The windows of the window layers, in layer order: a ring each."""
        return tuple(w for w in self.windows or () if w is not None)

    @property
    def kv_layers(self) -> int:
        """The layers that cache K/V rows for EVERY position: the leaves of
        an arena (a window layer keeps a ring instead)."""
        attention = self.layers if self.kinds is None \
            else self.kinds.count("attention")
        return attention - len(self.ring_windows)

    @property
    def state_layers(self) -> int:
        return 0 if self.kinds is None else self.kinds.count("state")

    @property
    def per_sequence(self) -> bool:
        """Whether the model keeps anything by the sequence, in a slot."""
        return bool(self.state_layers or self.ring_windows)


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
        if dec.ring_windows:
            raise ValueError("a model with window layers has no contiguous "
                             "cache: its rings live beside a paged arena")
        if dec.latent:
            raise ValueError("a model with latent attention has no "
                             "contiguous cache: its rows live in a paged "
                             "arena (`Latent`)")
        shape = (dec.kv_layers, batch, dec.kv_heads, max_len, dec.head_dim)
        dt = _storage_dtype(dec, dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def __init__(self, cache):
        self._old, self._k, self._v = cache, [], []

    def seek(self, start, n: Optional[int] = None, aligned: bool = False,
             head_dim: Optional[int] = None):
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
    leaf [n_pages, kv_heads, page_tokens, head_dim] — an exact leaf of
    heads of 64 or 32 LANE-DENSE, [n_pages, kv_heads, page_tokens / parts,
    128] (`kv/arena.py::lane_parts`), which is why a step tells `seek` the
    model's `head_dim`: a leaf's own shape does not say which of the two it
    is.  `init` picks the form; the writes and the kernels take either as
    it lies, and only the gather path (the CPU's) sees a reshape of it.
    Read and written
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
        return init_page_arena(dec.kv_layers, n_pages, dec.kv_heads,
                               page_tokens, dec.head_dim,
                               _storage_dtype(dec, dtype), quant_dtype,
                               quant_block)

    @staticmethod
    def page_tokens(pages, head_dim: Optional[int] = None) -> int:
        """The positions a page holds, off the arena's leaves (`head_dim`:
        the model's, without which a leaf is read as a plain one)."""
        _, _, rows, lanes = pages["k"][0].shape
        return rows * lanes // (head_dim or lanes)

    def __init__(self, pages, table):
        self._old, self._table = pages, table
        self._new = {key: [] for key in pages}
        self._quant_nb = pages["k_scale"][0].shape[-1] \
            if "k_scale" in pages else 0
        self.counters = None      # the ffns' counters, summed over layers

    @property
    def live(self):
        """bool [batch]: the rows whose first window is mapped — the rows
        that are sequences (the session hands every other row sentinels)."""
        n_pages = next(iter(self._old.values()))[0].shape[0]
        return self._table[:, 0].astype(jnp.int32) < n_pages

    def seek(self, start, n: Optional[int] = None, aligned: bool = False,
             head_dim: Optional[int] = None):
        """One row at `start` (n None), `n` rows from `start` that may
        straddle a page boundary, or (`aligned`) a chunk that fills exactly
        the page of window `start // page_tokens`.  `head_dim` is the
        model's (the steps pass it): what tells a lane-dense leaf's
        `page_tokens` from its shape."""
        pt = self.page_tokens(self._old, head_dim)
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
                                      kv_dequantize, paged_chunk_attention,
                                      paged_decode_attention)

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
        if not quant:
            # a chunk of queries (prefill, verify) reads its row's pages
            # through the table too, as far as the row's extent
            return paged_chunk_attention(
                q, last["k"].astype(dec.dtype), last["v"].astype(dec.dtype),
                tbl, pos)

        def virtual(key):
            # int8 pages (never lane-dense): the contiguous cache the table
            # describes, GQA-repeated AFTER the gather (payload and scales
            # alike, so dequant commutes)
            return kv_dequantize(
                gather_pages(last[key], tbl, n_heads=dec.heads),
                gather_pages(last[key + "_scale"], tbl, n_heads=dec.heads),
                dec.dtype)

        return chunk_attention(q, virtual("k"), virtual("v"), pos)

    def cache(self):
        return {key: tuple(leaves) for key, leaves in self._new.items()}


class Latent(Paged):
    """The arena of a model with latent attention, {"latent": (a leaf per
    layer)} with each leaf [n_pages, page_tokens, width]: ONE row a position,
    [the normed latent | the shared rotary key], which every head reads as
    its key and, in its leading `Decoder.latent` columns, as its value — no
    heads axis, no second leaf.  `width` is the row (`Decoder.head_dim`)
    padded with zeros to whole 128-lane tiles: the TPU tiles an array's
    minor dimension, and a leaf whose rows were not whole tiles would be
    given another layout than the kernels read, and copied, whole, every
    call.  Read and written through `table` as `Paged` is, by the same three
    writes, each in the layer's own donated leaf."""

    @staticmethod
    def width(dec: Decoder) -> int:
        return -(-dec.head_dim // 128) * 128

    @staticmethod
    def init(dec: Decoder, n_pages: int, page_tokens: int, dtype=None):
        return init_latent_arena(dec.kv_layers, n_pages, page_tokens,
                                 Latent.width(dec),
                                 _storage_dtype(dec, dtype))

    @staticmethod
    def page_tokens(pages, head_dim: Optional[int] = None) -> int:
        return pages["latent"][0].shape[1]

    def __init__(self, pages, table):
        self._old, self._table = pages, table
        self._new = []
        self.counters = None

    def _padded(self, x):
        pad = self._old["latent"][0].shape[-1] - x.shape[-1]
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def write(self, row, _=None):
        leaf = self._old["latent"][len(self._new)]
        self._new.append(self._put(leaf, self._padded(row)))

    def attend(self, dec: Decoder, q, pos):
        from easydist_tpu.ops import (latent_chunk_attention,
                                      latent_decode_attention)

        pages = self._new[-1].astype(dec.dtype)
        if q.ndim == 3:
            return latent_decode_attention(self._padded(q), pages, self._tbl,
                                           pos + 1, dec.latent)
        return latent_chunk_attention(self._padded(q), pages, self._tbl, pos,
                                      dec.latent)

    def cache(self):
        return {"latent": tuple(self._new)}


class Ring:
    """The K/V of a model's window layers, {"ring_k": (a leaf per WINDOW
    layer), "ring_v": (...)} with each leaf [n_slots, kv_heads, ring,
    head_dim]: a slot per live sequence, holding its last `ring` positions,
    position p in row p % ring — so the bytes a sequence holds in a window
    layer do not grow with it.  `ring` is the layer's window rounded up to
    the 8 rows of a tile.  A leaf has an arena leaf's layout with the slot
    as its page, and is written in place by the same scatters.

    Nothing is ever re-ordered: a step knows the absolute position every
    row of a ring holds from the step's own positions, and masks by it
    (`ops.window_attention`), which also keeps out what an earlier tenant
    of the slot left behind.  `State` builds one for a model with window
    layers; the step tells it (`seek`) its positions and which are real,
    and only real positions are written."""

    KEYS = ("ring_k", "ring_v")

    @staticmethod
    def rows(window: int) -> int:
        return -(-window // 8) * 8

    @staticmethod
    def init(dec: Decoder, n_slots: int, dtype=None):
        dt = _storage_dtype(dec, dtype)
        return {key: tuple(jnp.zeros((n_slots, dec.kv_heads, Ring.rows(w),
                                      dec.head_dim), dt)
                           for w in dec.ring_windows)
                for key in Ring.KEYS}

    def __init__(self, rings, slots=None):
        self._old, self._slots = rings, slots
        self._new = {key: [] for key in Ring.KEYS}

    def seek(self, pos, valid):
        """`pos` int32 [b] (a decode round: the rows are the slots) or
        [b, s] (a chunk: row r is slot `slots[r]`), `valid` bool alike, a
        prefix of each chunk row."""
        self._pos, self._valid = pos, valid

    def write(self, k, v):
        li = len(self._new["ring_k"])
        old_k, old_v = self._old["ring_k"][li], self._old["ring_v"][li]
        n_slots, _, ring, _ = old_k.shape
        pos, valid = self._pos, self._valid
        if k.ndim == 3:
            page = jnp.where(valid, jnp.arange(n_slots, dtype=jnp.int32),
                             n_slots)
            put = lambda leaf, new: write_row(leaf, new, page, pos % ring)
        else:
            # what a chunk attends beside itself: the ring as it was
            self._before = tuple(
                jnp.take(leaf, self._slots, axis=0, mode="clip")
                for leaf in (old_k, old_v))
            self._own = (k, v)
            # the last `ring` real positions: any earlier one would share
            # a row with one of them
            end = pos[:, :1] + jnp.sum(valid, axis=1, keepdims=True)
            page = jnp.where(valid & (pos >= end - ring),
                             self._slots[:, None], n_slots)
            put = lambda leaf, new: write_rows(leaf, new, page, pos % ring)
        self._new["ring_k"].append(put(old_k, k))
        self._new["ring_v"].append(put(old_v, v))

    def attend(self, dec: Decoder, q, pos):
        from easydist_tpu.ops import window_attention

        li = len(self._new["ring_k"]) - 1
        ring, window = self._old["ring_k"][li].shape[2], dec.ring_windows[li]
        row = jnp.arange(ring, dtype=jnp.int32)[None, :]
        if q.ndim == 3:
            # after the write, row j holds the last position <= pos that
            # lands on it (a negative one: nothing of this sequence)
            k, v = self._new["ring_k"][li], self._new["ring_v"][li]
            k_pos = pos[:, None] - (pos[:, None] - row) % ring
            return window_attention(
                q[:, :, None], k.astype(dec.dtype), v.astype(dec.dtype),
                pos[:, None], k_pos, window)[:, :, 0]
        last = pos[:, :1] - 1      # before the write: the last one < start
        k_pos = jnp.concatenate([last - (last - row) % ring, pos], axis=1)
        k, v = (jnp.concatenate([before.astype(dec.dtype), own], axis=2)
                for before, own in zip(self._before, self._own))
        return window_attention(q, k, v, pos, k_pos, window)

    def cache(self):
        return {key: tuple(leaves) for key, leaves in self._new.items()}


class State:
    """What a model keeps a SEQUENCE: the recurrent state of its state
    layers, {name: (a leaf per STATE layer)} with each leaf [n_slots, *shape
    of one sequence's carry] (`Decoder.state_shapes`), and the rings of its
    window layers (`Ring`, whose two keys ride in the same dict): a slot per
    live sequence, whatever its length.  Like an arena leaf, each is a
    buffer of its own, donated and written in place.

    `slots` (int32 [rows]) says which slot each row of the call is, and
    `n_slots` marks a row that is none (reads clip, writes drop); `None`
    means the rows ARE the slots, in order — a decode round — and then a
    layer's carry is the leaf itself and the model's update of it must
    leave dead rows as they were, which is what `valid` is for.  `live`
    (bool [rows]) marks the rows that are sequences; `fresh` (bool [rows])
    the rows that start from zero state (a prompt's first chunk)."""

    @staticmethod
    def init(dec: Decoder, n_slots: int, ring_dtype=None):
        state = {name: tuple(jnp.zeros((n_slots,) + tuple(shape), dt)
                             for _ in range(dec.state_layers))
                 for name, (shape, dt) in (dec.state_shapes or {}).items()}
        if dec.ring_windows:
            state.update(Ring.init(dec, n_slots, ring_dtype))
        return state

    @staticmethod
    def split(dec: Decoder, cache):
        """The one donated pytree of a model that keeps slots -> (the
        arena's keys, the slots' keys)."""
        mine = tuple(dec.state_shapes or ()) \
            + (Ring.KEYS if dec.ring_windows else ())
        return ({k: v for k, v in cache.items() if k not in mine},
                {k: cache[k] for k in mine})

    def __init__(self, state, live, slots=None, fresh=None):
        self.live = live
        self._slots, self._fresh = slots, fresh
        self._old = {name: leaves for name, leaves in state.items()
                     if name not in Ring.KEYS}
        self._new = {name: [] for name in self._old}
        self.ring = Ring({key: state[key] for key in Ring.KEYS}, slots) \
            if Ring.KEYS[0] in state else None

    def read(self):
        li = len(next(iter(self._new.values())))
        carry = {name: leaves[li] for name, leaves in self._old.items()}
        if self._slots is None:
            return carry
        carry = {name: jnp.take(leaf, self._slots, axis=0, mode="clip")
                 for name, leaf in carry.items()}
        if self._fresh is not None:
            carry = {name: jnp.where(
                self._fresh.reshape((-1,) + (1,) * (c.ndim - 1)),
                jnp.zeros_like(c), c) for name, c in carry.items()}
        return carry

    def write(self, carry):
        for name, new in self._new.items():
            li = len(new)
            if self._slots is None:
                new.append(carry[name])
            else:
                new.append(self._old[name][li].at[self._slots].set(
                    carry[name], mode="drop"))

    def cache(self):
        out = {name: tuple(leaves) for name, leaves in self._new.items()}
        return out if self.ring is None else {**out, **self.ring.cache()}


# ------------------------------------------------------------------- steps


def _forward(dec: Decoder, kv, params, tokens, pos, st=None, valid=None):
    """Embed, run every layer against `kv` (and `st`, the `State` of a
    model that keeps slots), final norm: (cache, x).  `valid` marks the
    rows and positions that are real, for the state layers, the rings and
    an `ffn` that counts; the counters are left on `kv`."""
    x = dec.embed(params, tokens, pos)
    counters = []
    ring = None if st is None else st.ring
    if ring is not None:
        ring.seek(pos, valid)
    windows = iter(dec.windows or ())
    for kind, blk in zip(dec.kinds or ("attention",) * dec.layers,
                         dec.blocks(params)):
        if kind == "state":
            x, carry = dec.state(blk, x, st.read(), valid)
            st.write(carry)
        else:
            q, k, v = dec.qkv(blk, x, pos)
            at = kv if next(windows, None) is None else ring
            at.write(k, v)
            x = dec.attn_out(blk, x, _merge_heads(at.attend(dec, q, pos)))
        if not dec.counts:
            x = dec.ffn(blk, x)
        else:
            x, c = dec.ffn(blk, x, valid)
            if c is not None:     # a layer without experts counts nothing
                counters.append(c)
    if dec.counts:
        kv.counters = sum(counters[1:], counters[0])
    cache = kv.cache() if st is None else {**kv.cache(), **st.cache()}
    return cache, dec.final_norm(params, x)


def _live(dec: Decoder, kv, state):
    """Which rows of a step are sequences: what the `State` was told, or
    for a model that only counts, what the page table shows; None for a
    model that asks for neither."""
    if state is not None:
        return state.live
    return kv.live if dec.counts else None


def chunk(dec: Decoder, kv, params, tokens, start_pos, lengths, state=None):
    """One fixed-size prefill chunk: `tokens` (int32 [batch, chunk]) at
    absolute positions `start_pos + [0..chunk)`; attention covers the FULL
    cache window masked to `key_pos <= query_pos`, so the traced shape does
    not depend on how much prompt is cached and a restored prefix is
    consumed as if recomputed.  Returns (cache, logits [batch, vocab]) at
    each row's last real position (`lengths - 1`): valid for rows whose
    chunk contains it, garbage nobody reads otherwise.  Against `Paged` a
    chunk fills exactly one page.  With `state` (a `State`), positions at
    or past a row's length leave its state and its rings untouched."""
    c_len = tokens.shape[1]
    start = start_pos.astype(jnp.int32)
    pos = kv.seek(start, c_len, aligned=True, head_dim=dec.head_dim)
    live = _live(dec, kv, state)
    valid = None if live is None else \
        live[:, None] & (pos < lengths.astype(jnp.int32)[:, None])
    cache, x = _forward(dec, kv, params, tokens, pos, state, valid)
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
    guarantee pos + s fits the cache (every touched page mapped).  Not for
    a model that keeps slots: a state has no position mask to hide a
    rejected draft behind, and a rejected draft has overwritten the ring
    rows of positions still inside the window."""
    pos = kv.seek(pos.astype(jnp.int32), tokens.shape[1],
                  head_dim=dec.head_dim)
    live = _live(dec, kv, None)
    valid = None if live is None else \
        jnp.broadcast_to(live[:, None], pos.shape)
    cache, x = _forward(dec, kv, params, tokens, pos, valid=valid)
    return cache, dec.unembed(params, x)


def decode(dec: Decoder, kv, params, token, pos, state=None):
    """One cached decode step: `token` (int32 [batch]) at position `pos`
    (int32 [batch], the row's current length) -> (cache, logits
    [batch, vocab]).  O(layers * pos) attention reads a token.  With
    `state`, rows that are not `state.live` leave their state and their
    rings untouched."""
    cache, x = _forward(dec, kv, params, token,
                        kv.seek(pos.astype(jnp.int32),
                                head_dim=dec.head_dim), state,
                        _live(dec, kv, state))
    return cache, dec.unembed(params, x)
