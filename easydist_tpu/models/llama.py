"""Llama-style decoder: RMSNorm, rotary embeddings, SwiGLU, grouped-query
attention (BASELINE.json config: "Llama-2-7B pretrain, autoflow 2D (DPxTP)
plan").  Pure jax, bf16-ready, static shapes."""

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
class LlamaConfig:
    vocab: int = 32000
    seq: int = 2048
    dim: int = 4096
    heads: int = 32
    kv_heads: int = 32
    layers: int = 32
    ffn_dim: int = 11008
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=128, seq=32, dim=32, heads=4, kv_heads=2, layers=2,
                    ffn_dim=64, dtype="float32")
        base.update(kw)
        return LlamaConfig(**base)


def llama_init(cfg: LlamaConfig, key) -> Dict:
    keys = jax.random.split(key, 1 + cfg.layers)
    hd = cfg.dim // cfg.heads
    params = {
        "wte": jax.random.normal(keys[0], (cfg.vocab, cfg.dim)) * 0.02,
        "blocks": [],
        "norm_f": jnp.ones((cfg.dim,)),
    }
    scale = 1.0 / math.sqrt(cfg.dim)
    for i in range(cfg.layers):
        bk = jax.random.split(keys[1 + i], 7)
        params["blocks"].append({
            "attn_norm": jnp.ones((cfg.dim,)),
            "wq": jax.random.normal(bk[0], (cfg.dim, cfg.heads * hd)) * scale,
            "wk": jax.random.normal(bk[1], (cfg.dim, cfg.kv_heads * hd)) * scale,
            "wv": jax.random.normal(bk[2], (cfg.dim, cfg.kv_heads * hd)) * scale,
            "wo": jax.random.normal(bk[3], (cfg.heads * hd, cfg.dim)) * scale,
            "ffn_norm": jnp.ones((cfg.dim,)),
            "w_gate": jax.random.normal(bk[4], (cfg.dim, cfg.ffn_dim)) * scale,
            "w_up": jax.random.normal(bk[5], (cfg.dim, cfg.ffn_dim)) * scale,
            "w_down": jax.random.normal(bk[6], (cfg.ffn_dim, cfg.dim))
                      * (1.0 / math.sqrt(cfg.ffn_dim)),
        })
    return params


def _rmsnorm(x, g, eps=1e-5):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, theta):
    """x: [b, h, t, d]; rotate pairs along d with position-dependent angles."""
    b, h, t, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = jnp.arange(t, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]  # [t, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(b, h, t, d)


def _gqa_attention(x, blk, cfg: LlamaConfig, dtype):
    b, t, _ = x.shape
    hd = cfg.dim // cfg.heads
    rep = cfg.heads // cfg.kv_heads

    def heads(y, n):
        return y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q = heads(x @ blk["wq"].astype(dtype), cfg.heads)
    k = heads(x @ blk["wk"].astype(dtype), cfg.kv_heads)
    v = heads(x @ blk["wv"].astype(dtype), cfg.kv_heads)
    q = _rope(q.astype(jnp.float32), cfg.rope_theta).astype(dtype)
    k = _rope(k.astype(jnp.float32), cfg.rope_theta).astype(dtype)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    qi = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    att = jnp.where(ki <= qi, att, jnp.array(-1e9, att.dtype))
    att = jax.nn.softmax(att, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, cfg.heads * hd)
    return out @ blk["wo"].astype(dtype)


def llama_apply(params, cfg: LlamaConfig, tokens):
    dtype = jnp.dtype(cfg.dtype)
    x = params["wte"][tokens].astype(dtype)
    for blk in params["blocks"]:
        h = _rmsnorm(x, blk["attn_norm"]).astype(dtype)
        x = x + _gqa_attention(h, blk, cfg, dtype)
        h = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(h @ blk["w_gate"].astype(dtype)) \
            * (h @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    x = _rmsnorm(x, params["norm_f"])
    return x.astype(jnp.float32) @ params["wte"].T


# --------------------------------------------------------- KV-cache decode
#
# Same contract as models/gpt.py: `init_kv_cache` + `llama_prefill` +
# `llama_decode_step`, returning the updated cache functionally so the
# compiled step donates it.  The cache stores ROPED keys at kv_heads
# granularity (GQA: the repeat to full heads happens at attention time, so
# cache HBM scales with kv_heads, not heads).


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None):
    """Zeroed KV cache {"k", "v"}: [layers, batch, kv_heads, max_len,
    head_dim].  No position-table bound — RoPE extends to any max_len."""
    hd = cfg.dim // cfg.heads
    dt = jnp.dtype(cfg.dtype if dtype in (None, "auto") else dtype)
    shape = (cfg.layers, batch, cfg.kv_heads, max_len, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _rope_at(x, pos, theta):
    """x: [b, n, d] single-position heads rotated at absolute positions
    `pos` (int32 [b]) — the decode-time form of `_rope`."""
    b, n, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]     # [b, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(b, n, d)


def _cache_write_row(cache_layer, new, pos):
    """cache_layer [b, n, T, hd], new [b, n, hd], pos int32 [b]."""
    return jax.vmap(
        lambda c, n_, p: jax.lax.dynamic_update_slice(
            c, n_[:, None, :].astype(c.dtype), (0, p, 0)))(
        cache_layer, new, pos.astype(jnp.int32))


def llama_prefill(params, cfg: LlamaConfig, cache, tokens, lengths):
    """Prompt pass: fill `cache` with the prompt's roped K and V and
    return (cache, logits [batch, vocab]) at each row's last real
    position.  Positions < length compute exactly what `llama_apply`
    computes."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    hd = cfg.dim // cfg.heads
    rep = cfg.heads // cfg.kv_heads
    x = params["wte"][tokens].astype(dtype)
    ks, vs = [], []
    for blk in params["blocks"]:
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)

        def heads(y, n):
            return y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

        q = heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        q = _rope(q.astype(jnp.float32), cfg.rope_theta).astype(dtype)
        k = _rope(k.astype(jnp.float32), cfg.rope_theta).astype(dtype)
        ks.append(k)
        vs.append(v)
        kf, vf = k, v
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
        att = jnp.einsum("bhqd,bhkd->bhqk", q, kf) / math.sqrt(hd)
        qi = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        att = jnp.where(ki <= qi, att, jnp.array(-1e9, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", att, vf)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, cfg.heads * hd)
        x = x + out @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    cache = {
        "k": cache["k"].at[:, :, :, :t, :].set(
            jnp.stack(ks).astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :, :t, :].set(
            jnp.stack(vs).astype(cache["v"].dtype)),
    }
    x = _rmsnorm(x, params["norm_f"])
    last = jnp.take_along_axis(
        x, (lengths.astype(jnp.int32) - 1)[:, None, None], axis=1)[:, 0]
    return cache, last.astype(jnp.float32) @ params["wte"].T


def _rope_abs(x, pos, theta):
    """x: [b, n, c, d] chunk heads rotated at absolute positions `pos`
    (int32 [b, c]) — the chunked-prefill form of `_rope`/`_rope_at`."""
    b, n, c, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * freqs  # [b, c, d/2]
    cos = jnp.cos(ang)[:, None, :, :]
    sin = jnp.sin(ang)[:, None, :, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(b, n, c, d)


def _cache_write_chunk(cache_layer, new, start):
    """cache_layer [b, n, T, hd], new [b, n, c, hd], start int32 [b]."""
    return jax.vmap(
        lambda cl, n_, s: jax.lax.dynamic_update_slice(
            cl, n_.astype(cl.dtype), (0, s, 0)))(
        cache_layer, new, start.astype(jnp.int32))


def llama_prefill_chunk(params, cfg: LlamaConfig, cache, tokens, start_pos,
                        lengths):
    """One fixed-size prefill chunk (the llama mirror of
    `gpt.gpt_prefill_chunk`): `tokens` (int32 [batch, chunk]) at absolute
    positions `start_pos + [0..chunk)`, K/V roped at those absolute
    positions and written into `cache` at kv_heads granularity, attention
    over the FULL cache window masked to `key_pos <= query_pos`.  Returns
    (cache, logits [batch, vocab]) at each row's last real position —
    valid for rows whose chunk contains `lengths - 1`."""
    from easydist_tpu.ops import chunk_attention

    dtype = jnp.dtype(cfg.dtype)
    b, c_len = tokens.shape
    hd = cfg.dim // cfg.heads
    rep = cfg.heads // cfg.kv_heads
    start = start_pos.astype(jnp.int32)
    abs_pos = start[:, None] + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)

        def heads(y, n):
            return y.reshape(b, c_len, n, hd).transpose(0, 2, 1, 3)

        q = heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        q = _rope_abs(q.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        k = _rope_abs(k.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        ck = _cache_write_chunk(cache["k"][li], k, start)
        cv = _cache_write_chunk(cache["v"][li], v, start)
        new_k.append(ck)
        new_v.append(cv)
        kf, vf = ck.astype(dtype), cv.astype(dtype)
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
        att = chunk_attention(q, kf, vf, abs_pos)
        out = att.transpose(0, 2, 1, 3).reshape(b, c_len, cfg.heads * hd)
        x = x + out @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _rmsnorm(x, params["norm_f"])
    rel_last = jnp.clip(lengths.astype(jnp.int32) - 1 - start, 0, c_len - 1)
    last = jnp.take_along_axis(x, rel_last[:, None, None], axis=1)[:, 0]
    return cache, last.astype(jnp.float32) @ params["wte"].T


def llama_verify_step(params, cfg: LlamaConfig, cache, tokens, pos):
    """Speculative-decoding verify step (the llama mirror of
    `gpt.gpt_verify_step`): score `tokens` (int32 [batch, s] — last
    committed token + s-1 drafts) at absolute positions `pos + [0..s)`
    in one forward, K roped at those absolute positions and written at
    kv_heads granularity, attention over the full cache window masked to
    `key_pos <= query_pos`, GQA-repeated before attention exactly like
    the bucketed chunk path.  Returns (cache, logits [batch, s, vocab])
    for all s positions.  Callers must guarantee pos + s <= T."""
    from easydist_tpu.ops import chunk_attention

    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    hd = cfg.dim // cfg.heads
    rep = cfg.heads // cfg.kv_heads
    start = pos.astype(jnp.int32)
    abs_pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)

        def heads(y, n):
            return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

        q = heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        q = _rope_abs(q.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        k = _rope_abs(k.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        ck = _cache_write_chunk(cache["k"][li], k, start)
        cv = _cache_write_chunk(cache["v"][li], v, start)
        new_k.append(ck)
        new_v.append(cv)
        kf, vf = ck.astype(dtype), cv.astype(dtype)
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
        att = chunk_attention(q, kf, vf, abs_pos)
        out = att.transpose(0, 2, 1, 3).reshape(b, s, cfg.heads * hd)
        x = x + out @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _rmsnorm(x, params["norm_f"])
    return cache, x.astype(jnp.float32) @ params["wte"].T


def llama_decode_step(params, cfg: LlamaConfig, cache, token, pos):
    """One cached decode step: (cache, logits [batch, vocab]) for `token`
    (int32 [batch]) at absolute position `pos` (int32 [batch]).  Q and the
    new K are roped at `pos`; cached keys were roped at write time, so the
    cache is read back as-is (the relative-angle property of RoPE is paid
    at write time, once)."""
    from easydist_tpu.ops import decode_attention

    dtype = jnp.dtype(cfg.dtype)
    b = token.shape[0]
    hd = cfg.dim // cfg.heads
    rep = cfg.heads // cfg.kv_heads
    pos = pos.astype(jnp.int32)
    x = params["wte"][token].astype(dtype)
    new_k, new_v = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)
        q = (hx @ blk["wq"].astype(dtype)).reshape(b, cfg.heads, hd)
        k = (hx @ blk["wk"].astype(dtype)).reshape(b, cfg.kv_heads, hd)
        v = (hx @ blk["wv"].astype(dtype)).reshape(b, cfg.kv_heads, hd)
        q = _rope_at(q.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
        k = _rope_at(k.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
        ck = _cache_write_row(cache["k"][li], k, pos)
        cv = _cache_write_row(cache["v"][li], v, pos)
        new_k.append(ck)
        new_v.append(cv)
        kf, vf = ck.astype(dtype), cv.astype(dtype)
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
        att = decode_attention(q, kf, vf, pos + 1)
        x = x + att.reshape(b, cfg.heads * hd) @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    x = _rmsnorm(x, params["norm_f"])
    return cache, x.astype(jnp.float32) @ params["wte"].T


# ------------------------------------------------------- paged KV decode
#
# Page-table variants (the llama mirror of gpt.py's): the arena stores
# ROPED keys at kv_heads granularity — one leaf per layer, [n_pages,
# kv_heads, page_tokens, head_dim] — so page HBM scales with kv_heads and
# the GQA repeat happens at attention time, matching the bucketed path's
# repeat-then-attend order bitwise.


def init_kv_pages(cfg: LlamaConfig, n_pages: int, page_tokens: int,
                  dtype=None, quant_dtype=None, quant_block: int = 0):
    """Zeroed page arena (`kv/arena.py`): {"k", "v"}, each a tuple of one
    leaf per layer, [n_pages, kv_heads, page_tokens, head_dim] — a buffer
    of its own, donated and written in place leaf by leaf.
    `quant_dtype="int8"` stores the payload block-scaled int8 plus
    parallel {"k_scale", "v_scale"} f32 scale leaves ([..., head_dim //
    block] — `quant_block` 0 = one block per row); presence of the scale
    keys is the quant signal the paged forwards branch on."""
    dt = jnp.dtype(cfg.dtype if dtype in (None, "auto") else dtype)
    return init_page_arena(cfg.layers, n_pages, cfg.kv_heads, page_tokens,
                           cfg.dim // cfg.heads, dt, quant_dtype,
                           quant_block)


def llama_prefill_chunk_paged(params, cfg: LlamaConfig, pages, table,
                              tokens, start_pos, lengths):
    """`llama_prefill_chunk` through a page table: the chunk's roped K and
    V fill the row's own page for window `start_pos // page_tokens` (no
    staging cache, no restore copy), and attention gathers the virtual
    contiguous cache through the table, GQA-repeated after the gather.
    Requires tokens.shape[1] == page_tokens."""
    from easydist_tpu.ops import (chunk_attention, gather_pages,
                                  kv_dequantize, kv_quantize)

    dtype = jnp.dtype(cfg.dtype)
    b, c_len = tokens.shape
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    if c_len != pt:
        raise ValueError(f"paged prefill chunk {c_len} != page_tokens {pt} "
                         f"(chunks must fill exactly one page)")
    hd = cfg.dim // cfg.heads
    start = start_pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    wp = jnp.take_along_axis(tbl, (start // pt)[:, None], axis=1)[:, 0]
    abs_pos = start[:, None] + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    x = params["wte"][tokens].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)

        def heads(y, n):
            return y.reshape(b, c_len, n, hd).transpose(0, 2, 1, 3)

        q = heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        q = _rope_abs(q.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        k = _rope_abs(k.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        if quant_nb:
            # ROPED keys quantize (rope at write time, like the exact
            # path stores roped keys); the GQA repeat happens after the
            # gather on BOTH payload and scales, so dequant commutes
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
        if quant_nb:
            kf = kv_dequantize(gather_pages(pk, tbl, n_heads=cfg.heads),
                               gather_pages(psk, tbl, n_heads=cfg.heads),
                               dtype)
            vf = kv_dequantize(gather_pages(pv, tbl, n_heads=cfg.heads),
                               gather_pages(psv, tbl, n_heads=cfg.heads),
                               dtype)
        else:
            kf = gather_pages(pk, tbl, n_heads=cfg.heads).astype(dtype)
            vf = gather_pages(pv, tbl, n_heads=cfg.heads).astype(dtype)
        att = chunk_attention(q, kf, vf, abs_pos)
        out = att.transpose(0, 2, 1, 3).reshape(b, c_len, cfg.heads * hd)
        x = x + out @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _rmsnorm(x, params["norm_f"])
    rel_last = jnp.clip(lengths.astype(jnp.int32) - 1 - start, 0, c_len - 1)
    last = jnp.take_along_axis(x, rel_last[:, None, None], axis=1)[:, 0]
    return pages, last.astype(jnp.float32) @ params["wte"].T


def llama_verify_step_paged(params, cfg: LlamaConfig, pages, table, tokens,
                            pos):
    """`llama_verify_step` against the page arena (the llama mirror of
    `gpt.gpt_verify_step_paged`): roped K/V rows for the s positions land
    through the table per position, attention gathers the virtual
    contiguous cache with the GQA repeat applied after the gather —
    matching the bucketed repeat-then-attend order bitwise.  Returns
    (pages, logits [batch, s, vocab]) for all s positions."""
    from easydist_tpu.ops import (chunk_attention, gather_pages,
                                  kv_dequantize, kv_quantize)

    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    hd = cfg.dim // cfg.heads
    start = pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    abs_pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    wp = jnp.take_along_axis(tbl, abs_pos // pt, axis=1)
    off = abs_pos % pt
    x = params["wte"][tokens].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)

        def heads(y, n):
            return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

        q = heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        q = _rope_abs(q.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
        k = _rope_abs(k.astype(jnp.float32), abs_pos,
                      cfg.rope_theta).astype(dtype)
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
            kf = kv_dequantize(gather_pages(pk, tbl, n_heads=cfg.heads),
                               gather_pages(psk, tbl, n_heads=cfg.heads),
                               dtype)
            vf = kv_dequantize(gather_pages(pv, tbl, n_heads=cfg.heads),
                               gather_pages(psv, tbl, n_heads=cfg.heads),
                               dtype)
        else:
            kf = gather_pages(pk, tbl, n_heads=cfg.heads).astype(dtype)
            vf = gather_pages(pv, tbl, n_heads=cfg.heads).astype(dtype)
        att = chunk_attention(q, kf, vf, abs_pos)
        out = att.transpose(0, 2, 1, 3).reshape(b, s, cfg.heads * hd)
        x = x + out @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _rmsnorm(x, params["norm_f"])
    return pages, x.astype(jnp.float32) @ params["wte"].T


def llama_decode_step_paged(params, cfg: LlamaConfig, pages, table, token,
                            pos):
    """`llama_decode_step` against the page arena: the new roped K/V row
    lands at window `pos // page_tokens`, offset `pos % page_tokens`, and
    attention runs through `ops.paged_decode_attention` (the kernel holds
    whole pages and lets every query head of a GQA group attend its kv
    head's rows, read once; the fallback gathers then GQA-repeats,
    bitwise-matching the bucketed repeat-then-attend)."""
    from easydist_tpu.ops import kv_quantize, paged_decode_attention

    dtype = jnp.dtype(cfg.dtype)
    b = token.shape[0]
    pt = pages["k"][0].shape[2]
    quant_nb = pages["k_scale"][0].shape[-1] if "k_scale" in pages else 0
    hd = cfg.dim // cfg.heads
    pos = pos.astype(jnp.int32)
    tbl = table.astype(jnp.int32)
    wp = jnp.take_along_axis(tbl, (pos // pt)[:, None], axis=1)[:, 0]
    off = pos % pt
    x = params["wte"][token].astype(dtype)
    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for li, blk in enumerate(params["blocks"]):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)
        q = (hx @ blk["wq"].astype(dtype)).reshape(b, cfg.heads, hd)
        k = (hx @ blk["wk"].astype(dtype)).reshape(b, cfg.kv_heads, hd)
        v = (hx @ blk["wv"].astype(dtype)).reshape(b, cfg.kv_heads, hd)
        q = _rope_at(q.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
        k = _rope_at(k.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
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
            att = paged_decode_attention(q, pk, pv, tbl, pos + 1,
                                         k_scale=psk, v_scale=psv)
        else:
            att = paged_decode_attention(q, pk.astype(dtype),
                                         pv.astype(dtype), tbl, pos + 1)
        x = x + att.reshape(b, cfg.heads * hd) @ blk["wo"].astype(dtype)
        hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
        gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
            * (hx @ blk["w_up"].astype(dtype))
        x = x + gated @ blk["w_down"].astype(dtype)
    pages = {"k": tuple(new_k), "v": tuple(new_v)}
    if quant_nb:
        pages["k_scale"] = tuple(new_ks)
        pages["v_scale"] = tuple(new_vs)
    x = _rmsnorm(x, params["norm_f"])
    return pages, x.astype(jnp.float32) @ params["wte"].T


def llama_loss(params, cfg: LlamaConfig, tokens, targets):
    logits = llama_apply(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def make_llama_train_step(cfg: LlamaConfig, lr=1e-4):
    def init_state(key):
        params = llama_init(cfg, key)
        return (params, adam_init(params))

    def train_step(state, tokens, targets):
        params, opt = state
        loss, grads = jax.value_and_grad(llama_loss)(params, cfg, tokens,
                                                     targets)
        new_params, new_opt = adam_update(params, grads, opt, lr=lr)
        return (new_params, new_opt), loss

    return train_step, init_state
