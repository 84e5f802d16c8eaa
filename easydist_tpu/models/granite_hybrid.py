"""Granite 4.0-H (`granitemoehybrid`): Mamba-2 state layers beside a few
grouped-query attention layers with no positional encoding, every layer
followed by a routed expert FFN plus one shared MLP, four scalar
multipliers.  Serving only: `decoder(cfg)` is the model as
`models/decoder.py` serves it; there is no training step.

    h = embed[token] * embedding_multiplier
    h = h + residual_multiplier * mixer(rmsnorm(h))        mamba | attention
    h = h + residual_multiplier * (moe + shared)(rmsnorm(h))
    logits = rmsnorm(h) @ embed.T / logits_scaling

The expert FFN is told which experts it holds (`experts_held`: first, how
many): it routes over ALL `experts` (softmax over the chosen `top_k`) and
computes its own experts' part (`models/experts.py`, the body every expert
model shares); what the absent experts would add is left out.

Parameters (`granite_init`, `chipbench/weights_granite.py`): {"wte",
"blocks": [...], "norm_f"}; a block has "norm_in", "norm_post", "router"
[dim, experts], "w1" [held, dim, 2 * expert_dim], "w2" [held, expert_dim,
dim], "shared_w1" [dim, 2 * shared_dim], "shared_w2" [shared_dim, dim] and
either {"wq", "wk", "wv", "wo"} or the mixer's {"w_in" [dim, 2 * d_inner
+ 2 * d_state + heads], "conv_w" [d_conv, d_inner + 2 * d_state], "conv_b",
"dt_bias", "a_log", "d_skip" [heads], "norm_gate" [d_inner], "w_out"
[d_inner, dim]}.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads

__all__ = ["GraniteHybridConfig", "granite_init", "decoder", "expert_ffn",
           "shared_mlp", "mamba_mixer"]


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab: int = 100352
    dim: int = 4096
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 128
    attention_multiplier: float = 0.0078125
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    mamba_chunk: int = 256
    experts: int = 72
    top_k: int = 10
    experts_held: Tuple[int, int] = (0, 72)     # first, how many
    expert_dim: int = 768
    shared_dim: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32, layer_types=("mamba", "attention",
                                                   "mamba"),
                    heads=4, kv_heads=2, head_dim=8,
                    attention_multiplier=0.125, mamba_heads=4,
                    mamba_head_dim=8, d_state=16, mamba_chunk=8, experts=8,
                    top_k=2, experts_held=(0, 4), expert_dim=16,
                    shared_dim=24, dtype="float32")
        base.update(kw)
        return GraniteHybridConfig(**base)


def granite_init(cfg: GraniteHybridConfig, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in),
    the embedding normal * 0.02 / `embedding_multiplier`, gains 1 + 0.1
    normal, `a_log` = log(uniform(1, 16)), `dt_bias` such
    that softplus lands in 1e-3..1e-1, `d_skip` 1."""
    dtype = jnp.dtype(cfg.dtype)
    dim, held = cfg.dim, cfg.experts_held[1]

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, len(cfg.layer_types) + 2)
    blocks = []
    for kind, bk in zip(cfg.layer_types, keys):
        k = jax.random.split(bk, 16)
        blk = {"norm_in": gain(k[0]), "norm_post": gain(k[1]),
               "router": mat(k[2], dim, cfg.experts),
               "w1": mat(k[3], held, dim, 2 * cfg.expert_dim),
               "w2": mat(k[4], held, cfg.expert_dim, dim),
               "shared_w1": mat(k[5], dim, 2 * cfg.shared_dim),
               "shared_w2": mat(k[6], cfg.shared_dim, dim)}
        if kind == "attention":
            blk.update(wq=mat(k[7], dim, cfg.heads * cfg.head_dim),
                       wk=mat(k[8], dim, cfg.kv_heads * cfg.head_dim),
                       wv=mat(k[9], dim, cfg.kv_heads * cfg.head_dim),
                       wo=mat(k[10], cfg.heads * cfg.head_dim, dim))
        else:
            dt = jnp.exp(jax.random.uniform(
                k[11], (cfg.mamba_heads,), jnp.float32,
                math.log(1e-3), math.log(1e-1)))
            blk.update(
                w_in=mat(k[7], dim, 2 * cfg.d_inner + 2 * cfg.d_state
                         + cfg.mamba_heads),
                conv_w=mat(k[8], cfg.d_conv, cfg.conv_dim),
                conv_b=(0.1 * jax.random.normal(k[9], (cfg.conv_dim,),
                                                jnp.float32)).astype(dtype),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                a_log=jnp.log(jax.random.uniform(
                    k[10], (cfg.mamba_heads,), jnp.float32, 1.0, 16.0)),
                d_skip=jnp.ones((cfg.mamba_heads,), jnp.float32),
                norm_gate=gain(k[12], cfg.d_inner),
                w_out=mat(k[13], cfg.d_inner, dim))
        blocks.append(blk)
    # 0.02 AFTER the multiplier: at 0.02 before it the tied head's self
    # term makes every stream one repeated token
    return {"wte": (jax.random.normal(keys[-2], (cfg.vocab, dim), jnp.float32)
                    * (0.02 / cfg.embedding_multiplier)).astype(dtype),
            "blocks": blocks, "norm_f": gain(keys[-1])}


def _rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def shared_mlp(cfg: GraniteHybridConfig, blk, u):
    from .experts import glu

    return glu(u, blk["shared_w1"], blk["shared_w2"], jnp.dtype(cfg.dtype))


def expert_ffn(cfg: GraniteHybridConfig, blk, u, valid=None):
    """This model's router in front of `models/experts.py::expert_ffn` (the
    held experts' part of the routed FFN, shared by every expert model):
    the top `top_k` of the router's logits over ALL `experts`, softmax over
    the chosen.  u [rows, dim] (already normed), valid bool [rows] or None
    -> (out [rows, dim], counters int32 [3])."""
    from . import experts

    dtype = jnp.dtype(cfg.dtype)
    scores = (u @ blk["router"].astype(dtype)).astype(jnp.float32)
    top, idx = jax.lax.top_k(scores, cfg.top_k)
    return experts.expert_ffn(u, idx, jax.nn.softmax(top, axis=-1),
                              blk["w1"], blk["w2"], cfg.experts_held, dtype,
                              valid)


def mamba_mixer(cfg: GraniteHybridConfig, blk, u, carry, valid):
    """The Mamba-2 mixer over normed activations u ([b, s, dim] a window,
    [b, dim] one position) from `carry` = {"conv": [b, (d_conv - 1) *
    conv_dim] (the last pre-activation conv inputs, flat: input j is the
    lanes [j * conv_dim, (j + 1) * conv_dim),
    `ops/ssm.py::causal_conv_tail`), "ssm": [b, heads, head_dim,
    d_state]}, both float32 -> (out like u, carry after the
    positions that are `valid` (bool [b, s] / [b]); the others leave the
    carry as it was)."""
    from easydist_tpu.ops.ssm import (causal_conv_tail, ssd_chunk_scan,
                                      ssm_decode_update)

    dtype = jnp.dtype(cfg.dtype)
    h, p, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state
    d_in = cfg.d_inner
    window = u.ndim == 3
    if not window:
        u, valid = u[:, None, :], valid[:, None]
    b, s, _ = u.shape
    zxbcdt = u @ blk["w_in"].astype(dtype)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + cfg.conv_dim].astype(jnp.float32)
    dt = zxbcdt[..., d_in + cfg.conv_dim:].astype(jnp.float32)

    xbc, new_conv = causal_conv_tail(carry["conv"], xbc, blk["conv_w"],
                                     blk["conv_b"], valid)

    x = xbc[..., :d_in].reshape(b, s, h, p)
    b_mat, c_mat = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    dt = jnp.where(valid[..., None], _softplus(dt + blk["dt_bias"]), 0.0)
    a = -jnp.exp(blk["a_log"].astype(jnp.float32))
    d_skip = blk["d_skip"].astype(jnp.float32)
    if window:
        y, ssm = ssd_chunk_scan(x, dt, a, b_mat, c_mat, d_skip,
                                carry["ssm"], block=cfg.mamba_chunk)
    else:
        ssm, y = ssm_decode_update(carry["ssm"], x[:, 0], dt[:, 0], a,
                                   b_mat[:, 0], c_mat[:, 0], d_skip,
                                   live=valid[:, 0])
        y = y[:, None]
    y = y.reshape(b, s, d_in) * jax.nn.silu(z.astype(jnp.float32))
    out = _rmsnorm(y, blk["norm_gate"], cfg.eps).astype(dtype) \
        @ blk["w_out"].astype(dtype)
    return (out if window else out[:, 0]), {"conv": new_conv, "ssm": ssm}


def decoder(cfg: GraniteHybridConfig) -> Decoder:
    """The model as `models/decoder.py` serves it.  Attention has no
    positional term, so `qkv` ignores `pos`; its scale is
    `attention_multiplier`, which `qkv` folds into q because the attention
    kernels scale by 1 / sqrt(head_dim)."""
    dtype = jnp.dtype(cfg.dtype)
    res = cfg.residual_multiplier
    q_scale = cfg.attention_multiplier * math.sqrt(cfg.head_dim)

    def norm(x, g):
        return _rmsnorm(x, g, cfg.eps).astype(dtype)

    def qkv(blk, x, pos):
        u = norm(x, blk["norm_in"])
        q = split_heads((u @ blk["wq"].astype(dtype)) * q_scale, cfg.heads)
        k = split_heads(u @ blk["wk"].astype(dtype), cfg.kv_heads)
        v = split_heads(u @ blk["wv"].astype(dtype), cfg.kv_heads)
        return q.astype(dtype), k, v

    def state(blk, x, carry, valid):
        out, carry = mamba_mixer(cfg, blk, norm(x, blk["norm_in"]), carry,
                                 valid)
        return x + res * out, carry

    def ffn(blk, x, valid):
        u = norm(x, blk["norm_post"])
        flat = u.reshape(-1, cfg.dim)
        routed, counters = expert_ffn(cfg, blk, flat, valid.reshape(-1))
        out = routed.reshape(u.shape) + shared_mlp(cfg, blk, u)
        return x + res * out, counters

    return Decoder(
        layers=len(cfg.layer_types), heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: (
            params["wte"][tokens] * cfg.embedding_multiplier).astype(dtype),
        qkv=qkv,
        attn_out=lambda blk, x, att: x + res * (att @ blk["wo"].astype(dtype)),
        ffn=ffn, counts=True, pair_slots=cfg.top_k * len(cfg.layer_types),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: (x.astype(jnp.float32) @ params["wte"].T)
        / cfg.logits_scaling,
        kinds=tuple("state" if t == "mamba" else "attention"
                    for t in cfg.layer_types),
        state=state,
        state_shapes={"conv": (((cfg.d_conv - 1) * cfg.conv_dim,),
                               jnp.float32),
                      "ssm": ((cfg.mamba_heads, cfg.mamba_head_dim,
                               cfg.d_state), jnp.float32)})
