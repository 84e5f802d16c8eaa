"""Llama-style decoder: RMSNorm, rotary embeddings, SwiGLU, grouped-query
attention (BASELINE.json config: "Llama-2-7B pretrain, autoflow 2D (DPxTP)
plan").  Pure jax, bf16-ready, static shapes."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from .decoder import (Contiguous, Decoder, Paged, chunk, decode, split_heads,
                      verify)
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


# ---------------------------------------------------------------- serving
#
# Same contract as models/gpt.py: the layer loop, both KV layouts and the
# step kinds live in `models/decoder.py`, and `decoder(cfg)` below is this
# model's arithmetic.  The cache stores ROPED keys at kv_heads granularity
# (the relative-angle property of RoPE is paid once, at write time; the GQA
# repeat to full heads happens at attention time, so cache HBM scales with
# kv_heads, not heads).


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


def _swiglu(blk, x, dtype):
    hx = _rmsnorm(x, blk["ffn_norm"]).astype(dtype)
    gated = jax.nn.silu(hx @ blk["w_gate"].astype(dtype)) \
        * (hx @ blk["w_up"].astype(dtype))
    return x + gated @ blk["w_down"].astype(dtype)


def decoder(cfg: LlamaConfig) -> Decoder:
    """The model as `models/decoder.py` serves it: RMSNorm, separate Q/K/V
    projections with RoPE at the rows' absolute positions (so no position
    bound), grouped-query attention, SwiGLU, head tied to the embedding."""
    dtype = jnp.dtype(cfg.dtype)

    def qkv(blk, x, pos):
        hx = _rmsnorm(x, blk["attn_norm"]).astype(dtype)
        q = split_heads(hx @ blk["wq"].astype(dtype), cfg.heads)
        k = split_heads(hx @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = split_heads(hx @ blk["wv"].astype(dtype), cfg.kv_heads)
        rope = _rope_at if x.ndim == 2 else _rope_abs
        q = rope(q.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
        k = rope(k.astype(jnp.float32), pos, cfg.rope_theta).astype(dtype)
        return q, k, v

    return Decoder(
        layers=cfg.layers, heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.dim // cfg.heads, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv,
        attn_out=lambda blk, x, att: x + att @ blk["wo"].astype(dtype),
        ffn=lambda blk, x: _swiglu(blk, x, dtype),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"]),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["wte"].T)


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None):
    """Zeroed contiguous cache (`decoder.Contiguous.init`).  No position
    bound: RoPE extends to any max_len."""
    return Contiguous.init(decoder(cfg), batch, max_len, dtype)


def init_kv_pages(cfg: LlamaConfig, n_pages: int, page_tokens: int,
                  dtype=None, quant_dtype=None, quant_block: int = 0):
    """Zeroed page arena (`decoder.Paged.init`); `quant_dtype="int8"`
    stores block-scaled int8 with scale leaves beside the payload."""
    return Paged.init(decoder(cfg), n_pages, page_tokens, dtype, quant_dtype,
                      quant_block)


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
        x = _swiglu(blk, x, dtype)
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


def llama_prefill_chunk(params, cfg: LlamaConfig, cache, tokens, start_pos,
                        lengths):
    """`decoder.chunk` on the contiguous cache: (cache, logits [b, vocab])."""
    return chunk(decoder(cfg), Contiguous(cache), params, tokens, start_pos,
                 lengths)


def llama_verify_step(params, cfg: LlamaConfig, cache, tokens, pos):
    """`decoder.verify` on the contiguous cache: logits [b, s, vocab]."""
    return verify(decoder(cfg), Contiguous(cache), params, tokens, pos)


def llama_decode_step(params, cfg: LlamaConfig, cache, token, pos):
    """`decoder.decode` on the contiguous cache: logits [b, vocab]."""
    return decode(decoder(cfg), Contiguous(cache), params, token, pos)


def llama_prefill_chunk_paged(params, cfg: LlamaConfig, pages, table,
                              tokens, start_pos, lengths):
    """`decoder.chunk` through a page table; chunk == page_tokens."""
    return chunk(decoder(cfg), Paged(pages, table), params, tokens,
                 start_pos, lengths)


def llama_verify_step_paged(params, cfg: LlamaConfig, pages, table, tokens,
                            pos):
    """`decoder.verify` through a page table."""
    return verify(decoder(cfg), Paged(pages, table), params, tokens, pos)


def llama_decode_step_paged(params, cfg: LlamaConfig, pages, table, token,
                            pos):
    """`decoder.decode` through a page table."""
    return decode(decoder(cfg), Paged(pages, table), params, token, pos)


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
