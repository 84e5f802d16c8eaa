"""A.X-K1 (`axk1`): multi-head latent attention in every layer — queries
through a low-rank bottleneck, keys and values re-expanded from ONE cached
row a position that all the heads share — each layer followed by a
sigmoid-routed expert FFN with one shared expert (the leading layer by a
dense SwiGLU), pre-norm, untied head.  Serving only: `decoder(cfg)` is the
model as `models/decoder.py` serves it; there is no training step.

    u   = rmsnorm(h)
    c_q = rmsnorm(u W_dq);  q = c_q W_uq -> heads x (nope | rope)
    [c | k_r] = u W_dkv;  c = rmsnorm(c);  q_r, k_r = rope(q_r), rope(k_r)
    CACHED a position a layer: [c | k_r]          (kv_rank + rope_dim values)
    [k_n | v] = c W_ukv -> heads x (nope | v_dim)
    s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) * scale, j <= i
    h = h + (softmax(s) v) W_o;   h = h + ffn(rmsnorm(h))

What is computed is the ABSORBED form: with W_ukv split a head into W_uk
[nope, kv_rank] and W_uv [kv_rank, v_dim], q' = [q_n W_uk | q_r] * scale,
s_ij = q'_i . [c_j | k_r,j], o' = softmax(s) c, o = o' W_uv — the same
numbers, and the cache is read as it is, once for all heads, by the latent
kernels (`ops/flash_attention.py`) through `decoder.Latent`; no key or value
a head is ever formed.  `scale` is (nope + rope)^-0.5 * m^2 with m the YaRN
magnitude 0.1 * mscale_all_dim * ln(factor) + 1; the rotary frequencies are
YaRN's blend (`rope_frequencies`), a pair being dim i and dim i + rope / 2.

The router scores every expert by a sigmoid in float32, takes the top
`top_k` scores (`topk_method` "none": no group limit, no selection bias),
and weighs a chosen expert by its score over the chosen scores' sum, times
`routed_scale` (`models/experts.py::sigmoid_route`).  The expert FFN is told
which experts it holds (`experts_held`) and computes their part; the head
holds `vocab` rows, which may be a slice.

Parameters (`axk1_init`, `chipbench/weights_axk1.py`): {"wte" [vocab, dim],
"head" [vocab, dim], "blocks": [...], "norm_f"}; a block has "w_dq" [dim,
q_rank], "q_norm" [q_rank], "w_uq" [q_rank, heads * (nope + rope)], "w_dkv"
[dim, kv_rank + rope], "kv_norm" [kv_rank], "w_ukv" [kv_rank, heads * (nope
+ v_dim)], "wo" [heads * v_dim, dim], "norm_attn" "norm_ffn" [dim], and
either the dense "w1" [dim, 2 * ffn_dim] (gate | up), "w2" [ffn_dim, dim] or
"router" [dim, experts], "w1" [held, dim, 2 * expert_dim], "w2" [held,
expert_dim, dim], "shared_w1" [dim, 2 * shared_dim], "shared_w2"
[shared_dim, dim].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads
from .experts import expert_ffn, glu, sigmoid_route

__all__ = ["AxK1Config", "axk1_init", "decoder", "attention_scale",
           "rope_frequencies"]


@dataclass(frozen=True)
class AxK1Config:
    vocab: int = 163840
    dim: int = 7168
    layers: int = 61
    dense_layers: int = 1            # first_k_dense_replace
    heads: int = 64
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    # rope_scaling, type "yarn"
    yarn_factor: float = 32.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_original: int = 4096
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    ffn_dim: int = 18432
    experts: int = 192
    top_k: int = 8
    experts_held: Tuple[int, int] = (0, 192)     # first, how many
    expert_dim: int = 2048
    shared_dim: int = 2048
    routed_scale: float = 2.5
    eps: float = 1e-6
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32, layers=3, heads=4, q_rank=24,
                    kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
                    yarn_original=16, ffn_dim=48, experts=8, top_k=2,
                    experts_held=(0, 4), expert_dim=16, shared_dim=16,
                    dtype="float32")
        base.update(kw)
        return AxK1Config(**base)


def _yarn_magnitude(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def attention_scale(cfg: AxK1Config) -> float:
    """(nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1."""
    m = _yarn_magnitude(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.nope_dim + cfg.rope_dim) ** -0.5 * m * m


def rope_frequencies(cfg: AxK1Config):
    """(inv_freq float32 [rope_dim / 2], the factor cos and sin carry):
    YaRN's blend of the plain frequencies f_i and f_i / factor — the first
    below dim `low`, the second above `high`, a linear ramp between, `low`
    and `high` the dims whose wavelength fits `beta_fast` / `beta_slow`
    turns into the original context."""
    half = cfg.rope_dim // 2
    f = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)

    def dim_of(turns):      # the (fractional) dim that makes `turns` turns
        return cfg.rope_dim * math.log(
            cfg.yarn_original / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), cfg.rope_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / cfg.yarn_factor * (1.0 - keep) + f * keep, \
        _yarn_magnitude(cfg.yarn_factor, cfg.yarn_mscale) \
        / _yarn_magnitude(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


def axk1_init(cfg: AxK1Config, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in),
    the embedding normal, gains 1 + 0.1 normal."""
    dtype = jnp.dtype(cfg.dtype)
    dim, held = cfg.dim, cfg.experts_held[1]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, cfg.layers + 3)
    blocks = []
    for li in range(cfg.layers):
        k = jax.random.split(keys[li], 16)
        blk = {"w_dq": mat(k[0], dim, cfg.q_rank),
               "q_norm": gain(k[1], cfg.q_rank),
               "w_uq": mat(k[2], cfg.q_rank,
                           cfg.heads * (cfg.nope_dim + cfg.rope_dim)),
               "w_dkv": mat(k[3], dim, cfg.kv_rank + cfg.rope_dim),
               "kv_norm": gain(k[4], cfg.kv_rank),
               "w_ukv": mat(k[5], cfg.kv_rank,
                            cfg.heads * (cfg.nope_dim + cfg.v_dim)),
               "wo": mat(k[6], cfg.heads * cfg.v_dim, dim),
               "norm_attn": gain(k[7]), "norm_ffn": gain(k[8])}
        if li < cfg.dense_layers:
            blk.update(w1=mat(k[9], dim, 2 * cfg.ffn_dim),
                       w2=mat(k[10], cfg.ffn_dim, dim))
        else:
            blk.update(router=mat(k[9], dim, cfg.experts),
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


def _rope(x, pos, inv_freq, factor):
    """x float32 [..., rope_dim] at pos (the leading shape of x but for an
    optional heads axis at 1): dim i paired with dim i + rope_dim / 2."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    if x.ndim == ang.ndim + 1:      # x has heads at axis 1, pos has none
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decoder(cfg: AxK1Config) -> Decoder:
    """The model as `models/decoder.py` serves it: `qkv` gives the absorbed,
    scaled queries and the row to cache, `attn_out` up-projects what the
    heads read back."""
    dtype = jnp.dtype(cfg.dtype)
    scale = attention_scale(cfg)
    h, nope, rope, vd = cfg.heads, cfg.nope_dim, cfg.rope_dim, cfg.v_dim

    def norm(x, g):
        return _rmsnorm(x, g, cfg.eps).astype(dtype)

    def up_kv(blk):      # W_ukv a head: [kv_rank, heads, nope | v_dim]
        return blk["w_ukv"].astype(dtype).reshape(cfg.kv_rank, h, nope + vd)

    def qkv(blk, x, pos):
        inv_freq, factor = rope_frequencies(cfg)
        u = norm(x, blk["norm_attn"])
        c_q = norm(u @ blk["w_dq"].astype(dtype), blk["q_norm"])
        q = split_heads(c_q @ blk["w_uq"].astype(dtype), h)
        q_r = _rope(q[..., nope:].astype(jnp.float32), pos, inv_freq, factor)
        ckr = u @ blk["w_dkv"].astype(dtype)
        c = norm(ckr[..., :cfg.kv_rank], blk["kv_norm"])
        k_r = _rope(ckr[..., cfg.kv_rank:].astype(jnp.float32), pos,
                    inv_freq, factor)
        # the keys' up-projection, absorbed: q_n W_uk, a head
        q_c = jnp.einsum("bh...n,chn->bh...c", q[..., :nope],
                         up_kv(blk)[..., :nope],
                         preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate([q_c, q_r], -1) * scale
        return q_abs.astype(dtype), \
            jnp.concatenate([c, k_r.astype(dtype)], -1), None

    def attn_out(blk, x, att):
        # att [..., heads * kv_rank]: each head's weighted sum of latents
        o = jnp.einsum("...hc,chv->...hv",
                       att.reshape(att.shape[:-1] + (h, cfg.kv_rank)),
                       up_kv(blk)[..., nope:])
        return x + o.reshape(o.shape[:-2] + (h * vd,)) \
            @ blk["wo"].astype(dtype)

    def ffn(blk, x, valid):
        u = norm(x, blk["norm_ffn"])
        if "router" not in blk:
            return x + glu(u, blk["w1"], blk["w2"], dtype), None
        flat = u.reshape(-1, cfg.dim)
        idx, gate = sigmoid_route(flat, blk["router"], cfg.top_k,
                                  cfg.routed_scale)
        routed, counters = expert_ffn(
            flat, idx, gate, blk["w1"], blk["w2"], cfg.experts_held, dtype,
            valid.reshape(-1))
        return x + routed.reshape(x.shape) \
            + glu(u, blk["shared_w1"], blk["shared_w2"], dtype), counters

    return Decoder(
        layers=cfg.layers, heads=h, kv_heads=1,
        head_dim=cfg.kv_rank + rope, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv, attn_out=attn_out, ffn=ffn, counts=True,
        pair_slots=cfg.top_k * (cfg.layers - cfg.dense_layers),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["head"].T,
        latent=cfg.kv_rank)
