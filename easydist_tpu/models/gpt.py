"""GPT-2-style decoder transformer, pure jax (reference headline model:
benchmark/torch/model/gpt.py; config GPT bs4 seq1024 d12288 h48 in
benchmark/bench_case.py:5-14).

TPU-first choices: bf16-ready matmuls on the MXU, static causal mask via
lax.select on an iota comparison (no data-dependent control flow), shapes
kept multiples of 128 at real sizes."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from easydist_tpu.kv.arena import (init_page_arena, write_chunk, write_row,
                                   write_rows)
from .optim import adam_init, adam_update


@dataclass
class GPTConfig:
    vocab: int = 50257
    seq: int = 1024
    dim: int = 768
    heads: int = 12
    layers: int = 12
    dtype: str = "float32"  # compute dtype; params stay float32
    # attention backend: "einsum" (XLA), "flash" (Pallas kernel), "ring"
    # (sequence-parallel ring attention; needs attn_mesh + attn_axis), or
    # "auto" (solver-visible composite — the auto-parallel ILP chooses
    # batch/head/seq-ring/seq-Ulysses per mesh axis)
    attention: str = "einsum"
    attn_mesh: object = None
    attn_axis: str = "sp"
    # per-block rematerialization: "none", "full" (jax.checkpoint each
    # block), or "dots" (save matmul outputs only) — trades recompute for
    # O(layers) instead of O(layers x activations) live memory in the bwd
    remat: str = "none"
    # rolled layers: params["blocks"] is a layer-stacked pytree (leading dim
    # = layers) and the forward runs one lax.scan over it — XLA compiles the
    # block once regardless of depth (the idiomatic Llama-scale form; the
    # auto-parallel path shards through the scan via the composite rule in
    # jaxfront/interpreter.py::_discover_scan)
    scan_layers: bool = False

    @staticmethod
    def small(**kw):
        return GPTConfig(**kw)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=128, seq=32, dim=32, heads=4, layers=2)
        base.update(kw)
        return GPTConfig(**base)


def _init_linear(key, n_in, n_out, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(n_in)
    wk, _ = jax.random.split(key)
    return {"w": jax.random.normal(wk, (n_in, n_out)) * scale,
            "b": jnp.zeros((n_out,))}


def gpt_init(cfg: GPTConfig, key) -> Dict:
    keys = jax.random.split(key, 2 + cfg.layers)
    params = {
        "wte": jax.random.normal(keys[0], (cfg.vocab, cfg.dim)) * 0.02,
        "wpe": jax.random.normal(keys[1], (cfg.seq, cfg.dim)) * 0.01,
        "blocks": [],
        "ln_f": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
    }
    proj_scale = 1.0 / math.sqrt(cfg.dim) / math.sqrt(2.0 * cfg.layers)
    for i in range(cfg.layers):
        bk = jax.random.split(keys[2 + i], 4)
        params["blocks"].append({
            "ln1": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
            "attn": {
                "qkv": _init_linear(bk[0], cfg.dim, 3 * cfg.dim),
                "proj": _init_linear(bk[1], cfg.dim, cfg.dim, proj_scale),
            },
            "ln2": {"g": jnp.ones((cfg.dim,)), "b": jnp.zeros((cfg.dim,))},
            "mlp": {
                "fc": _init_linear(bk[2], cfg.dim, 4 * cfg.dim),
                "proj": _init_linear(bk[3], 4 * cfg.dim, cfg.dim, proj_scale),
            },
        })
    if cfg.scan_layers:
        params["blocks"] = stack_gpt_blocks(params["blocks"])
    return params


def stack_gpt_blocks(blocks):
    """Per-layer block list -> one layer-stacked pytree (leading dim L)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def _layernorm(x, g, b, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _attention(x, p, cfg: "GPTConfig", dtype, return_kv: bool = False):
    heads = cfg.heads
    b, t, d = x.shape
    hd = d // heads
    qkv = x @ p["qkv"]["w"].astype(dtype) + p["qkv"]["b"].astype(dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def split_heads(t_):
        return t_.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if cfg.attention == "auto":
        # solver-visible composite: the auto-parallel ILP picks batch/head/
        # sequence (ring or Ulysses) sharding per mesh axis and emission
        # lowers accordingly (ops/attention_prim.py)
        from easydist_tpu.ops.attention_prim import attention as ed_attention

        out = ed_attention(q, k, v, causal=True)
    elif cfg.attention == "flash":
        from easydist_tpu.ops import flash_attention

        out = flash_attention(q, k, v, True)
    elif cfg.attention == "ring":
        from easydist_tpu.parallel import ring_attention

        out = ring_attention(q, k, v, cfg.attn_mesh, axis=cfg.attn_axis,
                             causal=True)
    else:
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        qi = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        att = jnp.where(ki <= qi, att, jnp.array(-1e9, dtype=att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
    out = out @ p["proj"]["w"].astype(dtype) + p["proj"]["b"].astype(dtype)
    if return_kv:
        return out, k, v  # k, v: [b, heads, t, hd], pre-projection
    return out


def gpt_apply(params, cfg: GPTConfig, tokens):
    """tokens: int32 [batch, seq] -> logits [batch, seq, vocab]."""
    dtype = jnp.dtype(cfg.dtype)
    x = params["wte"][tokens].astype(dtype) + params["wpe"].astype(dtype)[None, :tokens.shape[1]]
    def block_fn(blk, x):
        x = x + _attention(
            _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
            blk["attn"], cfg, dtype)
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        return x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                    + blk["mlp"]["proj"]["b"].astype(dtype))

    # per-block remat is driven ONLY by cfg.remat; the EASYDIST_REMAT_POLICY
    # env knob applies to compiled-function emission (jaxfront/api.py), a
    # separate mechanism — stacking both from one knob would double-remat
    remat = cfg.remat
    if remat not in ("none", "full", "dots"):
        raise ValueError(f"unknown GPTConfig.remat {cfg.remat!r}; "
                         f"expected none|full|dots")
    if remat == "full":
        block_fn = jax.checkpoint(block_fn)
    elif remat == "dots":
        block_fn = jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda h, blk: (block_fn(blk, h), None),
                            x, params["blocks"])
    else:
        for blk in params["blocks"]:
            x = block_fn(blk, x)
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return x.astype(jnp.float32) @ params["wte"].T


# --------------------------------------------------------- KV-cache decode
#
# Autoregressive serving forward: `gpt_prefill` runs the prompt once and
# fills a per-layer K/V cache; `gpt_decode_step` then attends ONE new token
# against the cache — O(layers * len) per token instead of the O(len^2)
# full re-forward.  Both are pure functions returning the updated cache, so
# a jit of the step with the cache input donated updates it in place
# (analyze rule SERVE001 audits exactly that).


def init_kv_cache(cfg: GPTConfig, batch: int, max_len: int, dtype=None):
    """Zeroed KV cache {"k", "v"}: [layers, batch, heads, max_len,
    head_dim].  Layer-stacked so the cache is two leaves regardless of
    depth (donation and sharding specs stay O(1)); the heads axis (dim 2)
    is the natural tensor-parallel shard dim, matching the solved qkv
    column-parallel strategy.  `dtype=None`/"auto" stores at the compute
    dtype; pass e.g. "bfloat16" to halve cache HBM."""
    if max_len > cfg.seq:
        raise ValueError(
            f"max_len {max_len} exceeds the learned position table "
            f"(cfg.seq={cfg.seq})")
    hd = cfg.dim // cfg.heads
    dt = jnp.dtype(cfg.dtype if dtype in (None, "auto") else dtype)
    shape = (cfg.layers, batch, cfg.heads, max_len, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _block_list(params, cfg):
    """Per-layer block pytrees whether `params["blocks"]` is a list or the
    scan_layers layer-stacked form."""
    blocks = params["blocks"]
    if cfg.scan_layers:
        return [jax.tree_util.tree_map(lambda p, i=i: p[i], blocks)
                for i in range(cfg.layers)]
    return list(blocks)


def _cache_write_row(cache_layer, new, pos):
    """Write one new K or V row per sequence: cache_layer [b, h, T, hd],
    new [b, h, hd], pos int32 [b] -> updated layer.  Per-row
    dynamic_update_slice touches only each sequence's own position."""
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n[:, None, :].astype(c.dtype), (0, p, 0)))(
        cache_layer, new, pos.astype(jnp.int32))


def gpt_prefill(params, cfg: GPTConfig, cache, tokens, lengths):
    """Prompt pass: run `tokens` (int32 [batch, t], padded) through the
    model, write every position's K/V into `cache`, and return
    (cache, logits) with logits [batch, vocab] taken at each row's last
    real position (`lengths` - 1).

    The attention is the standard causal forward, so positions < length
    compute exactly what `gpt_apply` computes; the padded tail writes
    garbage K/V that the decode-step length mask never attends."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"].astype(dtype)[None, :t]
    ks, vs = [], []
    for blk in _block_list(params, cfg):
        attn_out, k, v = _attention(
            _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
            blk["attn"], cfg, dtype, return_kv=True)
        x = x + attn_out
        ks.append(k)
        vs.append(v)
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    cache = {
        "k": cache["k"].at[:, :, :, :t, :].set(
            jnp.stack(ks).astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :, :t, :].set(
            jnp.stack(vs).astype(cache["v"].dtype)),
    }
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    last = jnp.take_along_axis(
        x, (lengths.astype(jnp.int32) - 1)[:, None, None], axis=1)[:, 0]
    return cache, last.astype(jnp.float32) @ params["wte"].T


def _cache_write_chunk(cache_layer, new, start):
    """Write a fixed-size chunk of K or V rows per sequence: cache_layer
    [b, h, T, hd], new [b, h, c, hd], start int32 [b] -> updated layer.
    Per-row dynamic_update_slice at a traced start keeps ONE compiled
    signature across every chunk position."""
    return jax.vmap(
        lambda cl, n, s: jax.lax.dynamic_update_slice(
            cl, n.astype(cl.dtype), (0, s, 0)))(
        cache_layer, new, start.astype(jnp.int32))


def gpt_prefill_chunk(params, cfg: GPTConfig, cache, tokens, start_pos,
                      lengths):
    """One fixed-size prefill chunk: run `tokens` (int32 [batch, chunk])
    at absolute positions `start_pos + [0..chunk)` (int32 [batch]), write
    the chunk's K/V into `cache` at those positions, and return
    (cache, logits [batch, vocab]) taken at each row's last real position
    — valid for rows whose chunk contains `lengths - 1` (the finishing
    chunk), garbage otherwise (the scheduler only reads finishing rows).

    Unlike `gpt_prefill` this attends the FULL cache window [0, T) with a
    `key_pos <= query_pos` mask, so the traced shape is independent of how
    much prompt is already cached: one compiled signature per bucket
    replaces the per-pow2-length set, and restored prefix chunks (written
    by a previous request via the prefix trie) are consumed exactly as if
    recomputed — softmax weights past a row's live positions underflow to
    exact 0, the stale-row-leakage property analyze SERVE002 audits."""
    from easydist_tpu.ops import chunk_attention

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b, c_len = tokens.shape
    hd = cfg.dim // heads
    start = start_pos.astype(jnp.int32)
    # absolute positions of this chunk's queries, per row: [b, chunk]
    abs_pos = start[:, None] + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"][abs_pos].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        ck = _cache_write_chunk(cache["k"][li], k, start)
        cv = _cache_write_chunk(cache["v"][li], v, start)
        new_k.append(ck)
        new_v.append(cv)
        att = chunk_attention(q, ck.astype(dtype), cv.astype(dtype),
                              abs_pos)
        att = att.transpose(0, 2, 1, 3).reshape(b, c_len, cfg.dim)
        x = x + (att @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    rel_last = jnp.clip(lengths.astype(jnp.int32) - 1 - start, 0, c_len - 1)
    last = jnp.take_along_axis(x, rel_last[:, None, None], axis=1)[:, 0]
    return cache, last.astype(jnp.float32) @ params["wte"].T


def gpt_verify_step(params, cfg: GPTConfig, cache, tokens, pos):
    """Speculative-decoding verify step: score `tokens` (int32
    [batch, s] — each row is [last committed token, draft_0, ...,
    draft_{s-2}]) at absolute positions `pos + [0..s)` in ONE forward,
    returning (cache, logits [batch, s, vocab]) for ALL s positions, so
    the host can accept the longest greedily-matching draft prefix.

    The trunk is `gpt_prefill_chunk` with s as the chunk length: K/V for
    all s positions is written at the traced start `pos` (one compiled
    signature per (bucket, s)) and attention over the full cache window
    is masked to `key_pos <= query_pos`, so position i's logits equal
    what `gpt_decode_step` would produce after sequentially feeding the
    first i tokens — rejected-draft rows written past the accept
    boundary are exactly the stale rows the mask keeps out of every
    later step (analyze rule SERVE003 audits this mask).  Callers must
    guarantee pos + s <= T (the write would otherwise be clamped onto
    committed rows)."""
    from easydist_tpu.ops import chunk_attention

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b, s = tokens.shape
    hd = cfg.dim // heads
    start = pos.astype(jnp.int32)
    abs_pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"][abs_pos].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        ck = _cache_write_chunk(cache["k"][li], k, start)
        cv = _cache_write_chunk(cache["v"][li], v, start)
        new_k.append(ck)
        new_v.append(cv)
        att = chunk_attention(q, ck.astype(dtype), cv.astype(dtype),
                              abs_pos)
        att = att.transpose(0, 2, 1, 3).reshape(b, s, cfg.dim)
        x = x + (att @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return cache, x.astype(jnp.float32) @ params["wte"].T


def gpt_decode_step(params, cfg: GPTConfig, cache, token, pos):
    """One cached decode step: feed `token` (int32 [batch]) at position
    `pos` (int32 [batch], == current sequence length per row) and return
    (cache, logits [batch, vocab]) for sampling the next token.

    Per-token work is O(layers * pos) attention reads plus the O(1)
    matmuls — independent of how many tokens were already generated.  The
    attention backend is `ops.decode_attention` (Pallas single-query flash
    kernel on TPU, masked dot_general elsewhere)."""
    from easydist_tpu.ops import decode_attention

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b = token.shape[0]
    hd = cfg.dim // heads
    pos = pos.astype(jnp.int32)
    x = params["wte"][token].astype(dtype) \
        + params["wpe"][pos].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, heads, hd)
        ck = _cache_write_row(cache["k"][li], k.reshape(b, heads, hd), pos)
        cv = _cache_write_row(cache["v"][li], v.reshape(b, heads, hd), pos)
        new_k.append(ck)
        new_v.append(cv)
        att = decode_attention(q, ck.astype(dtype), cv.astype(dtype),
                               pos + 1)
        x = x + (att.reshape(b, cfg.dim) @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return cache, x.astype(jnp.float32) @ params["wte"].T


# ------------------------------------------------------- paged KV decode
#
# Page-table variants of the serving forwards: K/V lives in one
# preallocated page arena ({"k","v"}: a tuple of one leaf per layer,
# [n_pages, heads, page_tokens, head_dim]; `kv/arena.py`) and each
# sequence's int32 page-table row says which arena page holds each
# `page_tokens`-token window.  The arena is threaded through and donated
# leaf by leaf: a layer's write lands in that layer's own input buffer and
# the written leaf is returned as it is, never sliced out of a stacked
# array and never stacked back.  The table is a few KiB of int32 pushed
# fresh each step.  Unmapped/dead
# entries hold the sentinel `n_pages`: writes through it scatter with
# mode="drop" (deterministically discarded), reads clip to a real page
# whose rows the length mask zeroes before softmax.


def init_kv_pages(cfg: GPTConfig, n_pages: int, page_tokens: int,
                  dtype=None, quant_dtype=None, quant_block: int = 0):
    """Zeroed page arena (`kv/arena.py`): {"k", "v"}, each a tuple of one
    leaf per layer, [n_pages, heads, page_tokens, head_dim] — a buffer of
    its own, donated and written in place leaf by leaf.

    `quant_dtype="int8"` stores the payload block-scaled int8 and adds
    parallel scale leaves {"k_scale", "v_scale"}: [n_pages, heads,
    page_tokens, head_dim // block] f32 (`quant_block` 0 = one block per
    row).  Presence of the scale keys is the quant signal every paged
    forward branches on — a {"k","v"}-only arena traces the exact
    pre-quant program."""
    dt = jnp.dtype(cfg.dtype if dtype in (None, "auto") else dtype)
    return init_page_arena(cfg.layers, n_pages, cfg.heads, page_tokens,
                           cfg.dim // cfg.heads, dt, quant_dtype,
                           quant_block)


def gpt_prefill_chunk_paged(params, cfg: GPTConfig, pages, table, tokens,
                            start_pos, lengths):
    """`gpt_prefill_chunk` with the cache indirected through a page table:
    `pages` is the arena, `table` int32 [batch, max_pages] maps each row's
    windows to arena pages (sentinel-padded), and the chunk's K/V is
    written INTO the row's own page for window `start_pos // page_tokens`
    — there is no staging cache and no migrate/restore copy on the paged
    path; a restored prefix is just table entries pointing at the trie's
    committed pages.  Attention gathers the virtual contiguous cache
    [batch, heads, max_pages * page_tokens, head_dim] through the table,
    so when that length equals the bucketed window the lowered program
    matches `gpt_prefill_chunk` shape-for-shape and the logits are
    bitwise identical.  Requires tokens.shape[1] == page_tokens."""
    from easydist_tpu.ops import (chunk_attention, gather_pages,
                                  kv_dequantize, kv_quantize)

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b, c_len = tokens.shape
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    if c_len != pt:
        raise ValueError(f"paged prefill chunk {c_len} != page_tokens {pt} "
                         f"(chunks must fill exactly one page)")
    hd = cfg.dim // heads
    start = start_pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    # the page receiving this chunk: the row's window start // page_tokens
    # (sentinel for inactive rows -> the writes drop)
    wp = jnp.take_along_axis(tbl, (start // pt)[:, None], axis=1)[:, 0]
    abs_pos = start[:, None] + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"][abs_pos].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, c_len, heads, hd).transpose(0, 2, 1, 3)
        if quant_nb:
            # quantize-on-commit: the page stores block-scaled int8, the
            # scale page rides the same write/gather indices
            k, sk = kv_quantize(k, quant_nb)
            v, sv = kv_quantize(v, quant_nb)
            psk = write_chunk(pages["k_scale"][li], sk, wp)
            psv = write_chunk(pages["v_scale"][li], sv, wp)
            new_ks.append(psk)
            new_vs.append(psv)
        pk = write_chunk(pages["k"][li], k, wp)
        pv = write_chunk(pages["v"][li], v, wp)
        new_k.append(pk)
        new_v.append(pv)
        # gather AFTER the write so the chunk attends its own fresh page
        if quant_nb:
            ck = kv_dequantize(gather_pages(pk, tbl),
                               gather_pages(psk, tbl), dtype)
            cv = kv_dequantize(gather_pages(pv, tbl),
                               gather_pages(psv, tbl), dtype)
        else:
            ck = gather_pages(pk, tbl)
            cv = gather_pages(pv, tbl)
        att = chunk_attention(q, ck.astype(dtype), cv.astype(dtype),
                              abs_pos)
        att = att.transpose(0, 2, 1, 3).reshape(b, c_len, cfg.dim)
        x = x + (att @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    rel_last = jnp.clip(lengths.astype(jnp.int32) - 1 - start, 0, c_len - 1)
    last = jnp.take_along_axis(x, rel_last[:, None, None], axis=1)[:, 0]
    return pages, last.astype(jnp.float32) @ params["wte"].T


def gpt_verify_step_paged(params, cfg: GPTConfig, pages, table, tokens,
                          pos):
    """`gpt_verify_step` against the page arena: the s positions'
    K/V rows land through the table per position (windows
    `(pos + i) // page_tokens`, offsets `(pos + i) % page_tokens` — a
    verify window may straddle a page boundary, unlike page-aligned
    prefill chunks), and attention gathers the virtual contiguous cache
    through the table as the paged prefill chunk does.  Returns
    (pages, logits [batch, s, vocab]) for all s positions.  Callers must
    have every touched window mapped (or the whole row sentinel — dead
    rows drop); rejected positions live in mapped pages until the host
    truncates the table tail past the reservation."""
    from easydist_tpu.ops import (chunk_attention, gather_pages,
                                  kv_dequantize, kv_quantize)

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b, s = tokens.shape
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    hd = cfg.dim // heads
    start = pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    abs_pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    # per-position page + offset: [b, s] each (sentinel rows stay
    # sentinel through the take -> every write drops)
    wp = jnp.take_along_axis(tbl, abs_pos // pt, axis=1)
    off = abs_pos % pt
    x = params["wte"][tokens].astype(dtype) \
        + params["wpe"][abs_pos].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
        if quant_nb:
            k, sk = kv_quantize(k, quant_nb)
            v, sv = kv_quantize(v, quant_nb)
            psk = write_rows(pages["k_scale"][li], sk, wp, off)
            psv = write_rows(pages["v_scale"][li], sv, wp, off)
            new_ks.append(psk)
            new_vs.append(psv)
        pk = write_rows(pages["k"][li], k, wp, off)
        pv = write_rows(pages["v"][li], v, wp, off)
        new_k.append(pk)
        new_v.append(pv)
        if quant_nb:
            ck = kv_dequantize(gather_pages(pk, tbl),
                               gather_pages(psk, tbl), dtype)
            cv = kv_dequantize(gather_pages(pv, tbl),
                               gather_pages(psv, tbl), dtype)
        else:
            ck = gather_pages(pk, tbl)
            cv = gather_pages(pv, tbl)
        att = chunk_attention(q, ck.astype(dtype), cv.astype(dtype),
                              abs_pos)
        att = att.transpose(0, 2, 1, 3).reshape(b, s, cfg.dim)
        x = x + (att @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return pages, x.astype(jnp.float32) @ params["wte"].T


def gpt_decode_step_paged(params, cfg: GPTConfig, pages, table, token, pos):
    """`gpt_decode_step` against the page arena: the new token's K/V row
    lands in the page holding window `pos // page_tokens` at offset
    `pos % page_tokens`, and attention runs through
    `ops.paged_decode_attention` (page-gathering Pallas kernel on TPU,
    gather + masked dot_general elsewhere).  The table's fixed
    [batch, max_pages] shape keeps ONE compiled signature across
    arbitrary per-row lengths — the whole point of the paged pool."""
    from easydist_tpu.ops import kv_quantize, paged_decode_attention

    dtype = jnp.dtype(cfg.dtype)
    heads = cfg.heads
    b = token.shape[0]
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    hd = cfg.dim // heads
    pos = pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    wp = jnp.take_along_axis(tbl, (pos // pt)[:, None], axis=1)[:, 0]
    off = pos % pt
    x = params["wte"][token].astype(dtype) \
        + params["wpe"][pos].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(_block_list(params, cfg)):
        p_at = blk["attn"]
        h_in = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype)
        qkv = h_in @ p_at["qkv"]["w"].astype(dtype) \
            + p_at["qkv"]["b"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, heads, hd)
        k = k.reshape(b, heads, hd)
        v = v.reshape(b, heads, hd)
        if quant_nb:
            k, sk = kv_quantize(k, quant_nb)
            v, sv = kv_quantize(v, quant_nb)
            psk = write_row(pages["k_scale"][li], sk, wp, off)
            psv = write_row(pages["v_scale"][li], sv, wp, off)
            new_ks.append(psk)
            new_vs.append(psv)
        pk = write_row(pages["k"][li], k, wp, off)
        pv = write_row(pages["v"][li], v, wp, off)
        new_k.append(pk)
        new_v.append(pv)
        if quant_nb:
            # int8 pages stream to the kernel as-is; dequantization
            # happens inside the online-softmax loop (or post-gather in
            # the XLA fallback)
            att = paged_decode_attention(q, pk, pv, tbl, pos + 1,
                                         k_scale=psk, v_scale=psv)
        else:
            att = paged_decode_attention(q, pk.astype(dtype),
                                         pv.astype(dtype), tbl, pos + 1)
        x = x + (att.reshape(b, cfg.dim) @ p_at["proj"]["w"].astype(dtype)
                 + p_at["proj"]["b"].astype(dtype))
        h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
        h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                        + blk["mlp"]["fc"]["b"].astype(dtype))
        x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                 + blk["mlp"]["proj"]["b"].astype(dtype))
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return pages, x.astype(jnp.float32) @ params["wte"].T


def gpt_loss(params, cfg: GPTConfig, tokens, targets):
    logits = gpt_apply(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return nll.mean()


def make_gpt_train_step(cfg: GPTConfig, lr=1e-4):
    """Returns (train_step, init_state): state = (params, opt_state);
    step(state, tokens, targets) -> (new_state, loss)."""

    def init_state(key):
        params = gpt_init(cfg, key)
        return (params, adam_init(params))

    def train_step(state, tokens, targets):
        params, opt = state
        loss, grads = jax.value_and_grad(gpt_loss)(params, cfg, tokens, targets)
        new_params, new_opt = adam_update(params, grads, opt, lr=lr)
        return (new_params, new_opt), loss

    return train_step, init_state


def make_gpt_pipeline_step(cfg: GPTConfig, mesh, n_microbatches: int,
                           lr: float = 1e-4, axis: str = "pp",
                           data_axis=None, schedule: str = "gpipe",
                           n_virtual: int = 1):
    """Pipeline-parallel GPT training: transformer blocks pipelined over the
    `pp` mesh axis (stage-stacked params), embedding/positional/head outside
    the pipelined middle (reference scenario: benchmark/torch/pp/gpt).

    schedule="gpipe"/"remat" differentiates through the forward pipeline;
    schedule="1f1b" runs the DAPPLE-class supertick schedule with
    O(n_stages) live microbatches, backpropagating into the embedding and
    head via the pipeline's aux input/head gradients.  n_virtual>1
    interleaves virtual stage chunks under ANY schedule.

    Requires cfg.layers % (n_stages * n_virtual) == 0.  Returns
    (train_step, init_state): state = (params, opt); train_step(state,
    tokens, targets) -> (state, loss); tokens [n_microbatches, mb, seq].
    """
    from easydist_tpu.parallel import (PipelineConfig, spmd_pipeline,
                                       spmd_pipeline_grad)

    n_stages = mesh.shape[axis]
    n_chunks = n_stages * max(1, n_virtual)
    if cfg.layers % n_chunks != 0:
        raise ValueError(f"layers {cfg.layers} not divisible by "
                         f"{n_chunks} pipeline stages x virtual chunks")
    per_stage = cfg.layers // n_chunks
    dtype = jnp.dtype(cfg.dtype)

    def stage_fn(stage_blocks, x):
        # stage_blocks: block pytree with leading dim per_stage
        for i in range(per_stage):
            blk = jax.tree_util.tree_map(lambda p: p[i], stage_blocks)
            x = x + _attention(
                _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"]).astype(dtype),
                blk["attn"], cfg, dtype)
            h = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"]).astype(dtype)
            h = jax.nn.gelu(h @ blk["mlp"]["fc"]["w"].astype(dtype)
                            + blk["mlp"]["fc"]["b"].astype(dtype))
            x = x + (h @ blk["mlp"]["proj"]["w"].astype(dtype)
                     + blk["mlp"]["proj"]["b"].astype(dtype))
        return x

    pipe_cfg = PipelineConfig(n_stages, n_microbatches, axis_name=axis,
                              schedule=schedule, data_axis=data_axis,
                              n_virtual=max(1, n_virtual))

    def stack_blocks(params):
        # list of layer pytrees -> [n_chunks, per_stage, ...] leading dims
        blocks = params["blocks"]
        stages = []
        for s in range(n_chunks):
            chunk = blocks[s * per_stage:(s + 1) * per_stage]
            stages.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunk))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stages)

    def embed(wte, wpe, tokens_mb):
        seq = tokens_mb.shape[-1]
        return wte[tokens_mb].astype(dtype) \
            + wpe.astype(dtype)[None, None, :seq]

    def head_loss(x_mb, targets_mb, hp):
        x = _layernorm(x_mb, hp["ln_f"]["g"], hp["ln_f"]["b"])
        logits = x.astype(jnp.float32) @ hp["wte"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets_mb[..., None],
                                    axis=-1).mean()

    if schedule == "1f1b":
        pipe_grad = spmd_pipeline_grad(stage_fn, head_loss, mesh, pipe_cfg,
                                       aux=True)

        def loss_and_grads(params, tokens_mb, targets_mb):
            x_mb, emb_vjp = jax.vjp(
                lambda wte, wpe: embed(wte, wpe, tokens_mb),
                params["wte"], params["wpe"])
            hp = {"ln_f": params["ln_f"], "wte": params["wte"]}
            loss, sgrads, dx_mb, dhp = pipe_grad(
                stack_blocks(params), x_mb, targets_mb, hp)
            dwte_emb, dwpe = emb_vjp(dx_mb)
            dblocks = [
                jax.tree_util.tree_map(lambda l: l[s][i], sgrads)
                for s in range(n_chunks) for i in range(per_stage)]
            grads = {"wte": dwte_emb + dhp["wte"], "wpe": dwpe,
                     "ln_f": dhp["ln_f"], "blocks": dblocks}
            return loss, grads
    else:
        pipe = spmd_pipeline(stage_fn, mesh, pipe_cfg)

        def forward(params, tokens_mb):
            # tokens_mb: [M, mb, seq]
            x = embed(params["wte"], params["wpe"], tokens_mb)
            x = pipe(stack_blocks(params), x)
            x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
            return x.astype(jnp.float32) @ params["wte"].T

        def loss_fn(params, tokens_mb, targets_mb):
            logits = forward(params, tokens_mb)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, targets_mb[..., None],
                                        axis=-1).mean()

        def loss_and_grads(params, tokens_mb, targets_mb):
            return jax.value_and_grad(loss_fn)(params, tokens_mb, targets_mb)

    def init_state(key):
        params = gpt_init(cfg, key)
        return (params, adam_init(params))

    def train_step(state, tokens_mb, targets_mb):
        params, opt = state
        loss, grads = loss_and_grads(params, tokens_mb, targets_mb)
        new_params, new_opt = adam_update(params, grads, opt, lr=lr)
        return (new_params, new_opt), loss

    return train_step, init_state
