"""Jamba (`jamba`, the dense Jamba2-3B reading: `num_experts` 1): Mamba-1
selective state-space layers beside a few multi-query attention layers with
no positional term, thirteen to one, every layer followed by a dense SwiGLU,
pre-normed, the head tied to the embedding.  Serving only: `decoder(cfg)` is
the model as `models/decoder.py` serves it; there is no training step.

    h = h + mixer(rmsnorm(h))              selective state | attention
    h = h + swiglu(rmsnorm(h))
    logits = rmsnorm(h) @ wte.T

An attention layer (layer i with i % attn_period == attn_offset): `heads`
query heads on `kv_heads` (ONE) KV head, causal softmax, no positions.

A selective layer (`selective_mixer`), with the state h a [d_state, d_inner]
float32 matrix a sequence keeps (`ops/ssm.py`: the state index on the
sublanes, the channels on the lanes) and u the normed input:

    [x~ | z]      = u W_in
    x             = silu(conv4(x~) + b_conv)     causal, depthwise, x~ ALONE
    [dt~ | B | C] = x W_x;  each RMS-normed with its own gain
    dt            = softplus(dt~ W_dt + b_dt);   A = -exp(A_log)
    h             = exp(dt A) h + dt x B^T       a decay a channel AND an index
    y             = h . C + D x
    out           = (y * silu(z)) W_out

Parameters (`jamba_init`, `chipbench/weights_jamba.py`): {"wte" [vocab, dim],
"blocks": [...], "norm_f"}; a block has "norm_in" "norm_ff" [dim], "w1" [dim,
2 * ffn_dim] (gate | up), "w2" [ffn_dim, dim] and either "wq" "wo" [dim,
heads * head_dim] / its transpose's shape, "wk" "wv" [dim, kv_heads *
head_dim] or the mixer's "w_in" [dim, 2 * d_inner] (columns x~ | z),
"conv_w" [d_conv, d_inner] (row j multiplies the input d_conv - 1 - j
positions back), "conv_b" [d_inner], "w_x" [d_inner, dt_rank + 2 * d_state]
(columns dt~ | B | C), "norm_dt" [dt_rank], "norm_b" "norm_c" [d_state],
"w_dt" [dt_rank, d_inner], "dt_bias" [d_inner], "a_log" [d_state, d_inner],
"d_skip" [d_inner] (the last three float32), "w_out" [d_inner, dim].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads
from .experts import glu

__all__ = ["JambaConfig", "jamba_init", "decoder", "selective_mixer"]


@dataclass(frozen=True)
class JambaConfig:
    vocab: int = 65536
    dim: int = 2560
    layers: int = 28
    attn_period: int = 14
    attn_offset: int = 7
    heads: int = 20
    kv_heads: int = 1
    head_dim: int = 128
    ffn_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    eps: float = 1e-6
    dtype: str = "bfloat16"

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("attention" if i % self.attn_period == self.attn_offset
                     else "mamba" for i in range(self.layers))

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32, layers=4, attn_period=4, attn_offset=2,
                    heads=4, kv_heads=1, head_dim=8, ffn_dim=48, d_state=16,
                    dt_rank=8, dtype="float32")
        base.update(kw)
        return JambaConfig(**base)


def jamba_init(cfg: JambaConfig, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in), the
    tied embedding normal * 0.02 (logits spread about 0.02 sqrt(dim), and
    the token just read is a small part of the stream the head sees), gains
    1 + 0.1 normal, `a_log[n]` = log(n + 1) (S4D-real), `dt_bias` such that
    softplus lands log-uniformly in 1e-3..1e-1, `d_skip` 1."""
    dtype = jnp.dtype(cfg.dtype)
    dim, e, n, r = cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, cfg.layers + 2)
    blocks = []
    for kind, bk in zip(cfg.layer_types, keys):
        k = jax.random.split(bk, 16)
        blk = {"norm_in": gain(k[0]), "norm_ff": gain(k[1]),
               "w1": mat(k[2], dim, 2 * cfg.ffn_dim),
               "w2": mat(k[3], cfg.ffn_dim, dim)}
        if kind == "attention":
            blk.update(wq=mat(k[4], dim, cfg.heads * cfg.head_dim),
                       wk=mat(k[5], dim, cfg.kv_heads * cfg.head_dim),
                       wv=mat(k[6], dim, cfg.kv_heads * cfg.head_dim),
                       wo=mat(k[7], cfg.heads * cfg.head_dim, dim))
        else:
            dt = jnp.exp(jax.random.uniform(
                k[8], (e,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            blk.update(
                w_in=mat(k[4], dim, 2 * e),
                conv_w=mat(k[5], cfg.d_conv, e),
                conv_b=(0.1 * jax.random.normal(k[6], (e,), jnp.float32)
                        ).astype(dtype),
                w_x=mat(k[7], e, r + 2 * n),
                norm_dt=gain(k[9], r), norm_b=gain(k[10], n),
                norm_c=gain(k[11], n),
                w_dt=mat(k[12], r, e),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                a_log=jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32))[:, None], (n, e)),
                d_skip=jnp.ones((e,), jnp.float32),
                w_out=mat(k[13], e, dim))
        blocks.append(blk)
    return {"wte": (0.02 * jax.random.normal(keys[-2], (cfg.vocab, dim),
                                             jnp.float32)).astype(dtype),
            "blocks": blocks, "norm_f": gain(keys[-1])}


def _rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def selective_mixer(cfg: JambaConfig, blk, u, carry, valid):
    """The Mamba-1 mixer over normed activations u ([b, s, dim] a window,
    [b, dim] one position) from `carry` = {"conv": [b, (d_conv - 1) *
    d_inner] (the last pre-activation conv inputs, flat: input j is the
    lanes [j * d_inner, (j + 1) * d_inner), `ops/ssm.py::causal_conv_tail`),
    "selective": [b, d_state, d_inner]}, both float32 -> (out like u, carry
    after the positions that are `valid` (bool [b, s] / [b]); the others
    leave the carry as it was).  The matrix products take `cfg.dtype`
    operands and sum in float32; the conv, the norms, dt, the decay and the
    recurrence are float32."""
    from easydist_tpu.ops.ssm import (causal_conv_tail, selective_chunk_scan,
                                      selective_decode_update)

    dtype, f32 = jnp.dtype(cfg.dtype), jnp.float32
    e, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    window = u.ndim == 3
    if not window:
        u, valid = u[:, None, :], valid[:, None]

    def product(a, w):
        return jnp.dot(a.astype(dtype), w.astype(dtype),
                       preferred_element_type=f32)

    xz = product(u, blk["w_in"])
    x, new_conv = causal_conv_tail(carry["conv"], xz[..., :e], blk["conv_w"],
                                   blk["conv_b"], valid)
    proj = product(x, blk["w_x"])
    dt = _rmsnorm(proj[..., :r], blk["norm_dt"], cfg.eps)
    b_mat = _rmsnorm(proj[..., r:r + n], blk["norm_b"], cfg.eps)
    c_mat = _rmsnorm(proj[..., r + n:], blk["norm_c"], cfg.eps)
    dt = _softplus(product(dt, blk["w_dt"]) + blk["dt_bias"].astype(f32))
    # a position that does not count neither decays nor adds
    dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(blk["a_log"].astype(f32))
    d_skip = blk["d_skip"].astype(f32)
    if window:
        y, state = selective_chunk_scan(x, dt, a, b_mat, c_mat, d_skip,
                                        carry["selective"])
    else:
        state, y = selective_decode_update(
            carry["selective"], x[:, 0], dt[:, 0], a, b_mat[:, 0],
            c_mat[:, 0], d_skip, live=valid[:, 0])
        y = y[:, None]
    out = product(y * jax.nn.silu(xz[..., e:]), blk["w_out"]).astype(dtype)
    return (out if window else out[:, 0]), \
        {"conv": new_conv, "selective": state}


def decoder(cfg: JambaConfig) -> Decoder:
    """The model as `models/decoder.py` serves it.  Attention has no
    positional term, so `qkv` ignores `pos`."""
    dtype = jnp.dtype(cfg.dtype)

    def norm(x, g):
        return _rmsnorm(x, g, cfg.eps).astype(dtype)

    def qkv(blk, x, pos):
        u = norm(x, blk["norm_in"])
        return split_heads(u @ blk["wq"].astype(dtype), cfg.heads), \
            split_heads(u @ blk["wk"].astype(dtype), cfg.kv_heads), \
            split_heads(u @ blk["wv"].astype(dtype), cfg.kv_heads)

    def state(blk, x, carry, valid):
        out, carry = selective_mixer(cfg, blk, norm(x, blk["norm_in"]), carry,
                                     valid)
        return x + out, carry

    return Decoder(
        layers=cfg.layers, heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv,
        attn_out=lambda blk, x, att: x + att @ blk["wo"].astype(dtype),
        ffn=lambda blk, x: x + glu(norm(x, blk["norm_ff"]), blk["w1"],
                                   blk["w2"], dtype),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["wte"].T,
        kinds=tuple("state" if t == "mamba" else "attention"
                    for t in cfg.layer_types),
        state=state,
        state_shapes={
            "conv": (((cfg.d_conv - 1) * cfg.d_inner,), jnp.float32),
            "selective": ((cfg.d_state, cfg.d_inner), jnp.float32)})
