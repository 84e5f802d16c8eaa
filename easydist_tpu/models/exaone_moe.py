"""K-EXAONE (`exaone_moe`): grouped-query attention layers of two kinds in
one model — most see their last `sliding_window` positions, every fourth
sees everything — each followed by a sigmoid-routed expert FFN with one
shared expert (the leading layer by a dense SwiGLU instead), both
sublayers normed on their OUTPUT.  Serving only: `decoder(cfg)` is the
model as `models/decoder.py` serves it; there is no training step.

    q, k, v = h Wq, h Wk, h Wv;  q, k = rmsnorm_head(q), rmsnorm_head(k)
    q, k = rope(q), rope(k)                     on sliding layers only
    h = h + rmsnorm(attention(q, k, v) Wo)      window | full
    h = h + rmsnorm(ffn(h))                     dense | experts + shared
    logits = rmsnorm(h) @ head.T

The router scores every expert by a sigmoid in float32, chooses the top
`top_k` of score + bias (the bias steers the choice only), and weighs a
chosen expert by its score over the chosen scores' sum, times
`routed_scale`.  The expert FFN is told which experts it holds
(`experts_held`: first, how many) and computes their part
(`models/experts.py`); the head holds `vocab` rows, which may be a slice.

Parameters (`exaone_init`, `chipbench/weights_exaone.py`): {"wte" [vocab,
dim], "head" [vocab, dim], "blocks": [...], "norm_f"}; a block has "wq"
"wk" "wv" "wo", "q_norm" "k_norm" [head_dim], "norm_attn" "norm_ffn"
[dim], and either the dense "w1" [dim, 2 * ffn_dim] (gate | up), "w2"
[ffn_dim, dim] or "router" [dim, experts], "router_bias" [experts]
(float32), "w1" [held, dim, 2 * expert_dim], "w2" [held, expert_dim, dim],
"shared_w1" [dim, 2 * shared_dim], "shared_w2" [shared_dim, dim].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads
from .experts import expert_ffn, glu, sigmoid_route

__all__ = ["ExaoneMoeConfig", "exaone_init", "decoder", "route"]


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab: int = 153600
    dim: int = 6144
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 3
    sliding_window: int = 128
    heads: int = 64
    kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    ffn_dim: int = 18432
    experts: int = 128
    top_k: int = 8
    experts_held: Tuple[int, int] = (0, 128)     # first, how many
    expert_dim: int = 2048
    shared_dim: int = 2048
    routed_scale: float = 2.5
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32,
                    layer_types=("sliding_attention", "sliding_attention",
                                 "full_attention", "sliding_attention"),
                    mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                    sliding_window=8, heads=4, kv_heads=2, head_dim=8,
                    ffn_dim=48, experts=8, top_k=2, experts_held=(0, 4),
                    expert_dim=16, shared_dim=16, dtype="float32")
        base.update(kw)
        return ExaoneMoeConfig(**base)


def exaone_init(cfg: ExaoneMoeConfig, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in),
    the embedding normal (the residual stream must carry the token beside
    sublayer outputs that are normed to 1), gains 1 + 0.1 normal, the
    selection bias 0.01 normal."""
    dtype = jnp.dtype(cfg.dtype)
    dim, held = cfg.dim, cfg.experts_held[1]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, len(cfg.layer_types) + 3)
    blocks = []
    for mlp, bk in zip(cfg.mlp_layer_types, keys):
        k = jax.random.split(bk, 16)
        blk = {"wq": mat(k[0], dim, cfg.heads * cfg.head_dim),
               "wk": mat(k[1], dim, cfg.kv_heads * cfg.head_dim),
               "wv": mat(k[2], dim, cfg.kv_heads * cfg.head_dim),
               "wo": mat(k[3], cfg.heads * cfg.head_dim, dim),
               "q_norm": gain(k[4], cfg.head_dim),
               "k_norm": gain(k[5], cfg.head_dim),
               "norm_attn": gain(k[6]), "norm_ffn": gain(k[7])}
        if mlp == "dense":
            blk.update(w1=mat(k[8], dim, 2 * cfg.ffn_dim),
                       w2=mat(k[9], cfg.ffn_dim, dim))
        else:
            blk.update(router=mat(k[8], dim, cfg.experts),
                       router_bias=0.01 * jax.random.normal(
                           k[9], (cfg.experts,), jnp.float32),
                       w1=mat(k[10], held, dim, 2 * cfg.expert_dim),
                       w2=mat(k[11], held, cfg.expert_dim, dim),
                       shared_w1=mat(k[12], dim, 2 * cfg.shared_dim),
                       shared_w2=mat(k[13], cfg.shared_dim, dim))
        blocks.append(blk)
    return {"wte": jax.random.normal(keys[-3], (cfg.vocab, dim),
                                     jnp.float32).astype(dtype),
            "head": mat(keys[-2], cfg.vocab, dim), "blocks": blocks,
            "norm_f": gain(keys[-1])}


def _rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x float32 [b, n, hd] at pos [b], or [b, n, s, hd] at pos [b, s]:
    the whole head rotated, dim i paired with dim i + hd / 2."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(cfg: ExaoneMoeConfig, blk, u):
    """u [rows, dim] -> (idx int32 [rows, top_k], gate float32 [rows,
    top_k]): sigmoid scores in float32, the top `top_k` of score + bias,
    the chosen scores normalised and scaled.  The bias never gates."""
    return sigmoid_route(u, blk["router"], cfg.top_k, cfg.routed_scale,
                         blk["router_bias"])


def decoder(cfg: ExaoneMoeConfig) -> Decoder:
    """The model as `models/decoder.py` serves it.  A block is handed to
    the loop with its layer's kind beside its weights (`sliding`): rotary
    positions go on the sliding layers only."""
    dtype = jnp.dtype(cfg.dtype)
    sliding = tuple(t == "sliding_attention" for t in cfg.layer_types)

    def head_norm(y, g):          # y [b, n, (s,) hd]
        return _rmsnorm(y, g, cfg.eps)

    def qkv(blk, x, pos):
        q = head_norm(split_heads(x @ blk["wq"].astype(dtype), cfg.heads),
                      blk["q_norm"])
        k = head_norm(split_heads(x @ blk["wk"].astype(dtype), cfg.kv_heads),
                      blk["k_norm"])
        v = split_heads(x @ blk["wv"].astype(dtype), cfg.kv_heads)
        if blk["sliding"]:
            q, k = (_rope(y, pos, cfg.rope_theta) for y in (q, k))
        return q.astype(dtype), k.astype(dtype), v

    def attn_out(blk, x, att):
        return x + _rmsnorm(att @ blk["wo"].astype(dtype), blk["norm_attn"],
                            cfg.eps).astype(dtype)

    def ffn(blk, x, valid):
        counters = None
        if "router" in blk:
            flat = x.reshape(-1, cfg.dim)
            idx, gate = route(cfg, blk, flat)
            routed, counters = expert_ffn(
                flat, idx, gate, blk["w1"], blk["w2"], cfg.experts_held,
                dtype, valid.reshape(-1))
            out = routed.reshape(x.shape) \
                + glu(x, blk["shared_w1"], blk["shared_w2"], dtype)
        else:
            out = glu(x, blk["w1"], blk["w2"], dtype)
        return x + _rmsnorm(out, blk["norm_ffn"], cfg.eps).astype(dtype), \
            counters

    return Decoder(
        layers=len(cfg.layer_types), heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, dtype=dtype, max_positions=None,
        blocks=lambda params: [dict(blk, sliding=s) for blk, s in
                               zip(params["blocks"], sliding)],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv, attn_out=attn_out, ffn=ffn, counts=True,
        pair_slots=cfg.top_k * cfg.mlp_layer_types.count("sparse"),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["head"].T,
        windows=tuple(cfg.sliding_window if s else None for s in sliding))
