"""Olmo Hybrid (`olmo_hybrid`): gated delta-rule linear-attention layers
(Gated DeltaNet) beside full multi-head attention layers with no positional
term, three to one, every layer followed by a dense SwiGLU, both sublayers
normed on their OUTPUT.  Serving only: `decoder(cfg)` is the model as
`models/decoder.py` serves it; there is no training step.

    h = h + rmsnorm(mixer(h))              delta rule | attention
    h = h + rmsnorm(swiglu(h))
    logits = rmsnorm(h) @ head.T

A full layer: q, k = rmsnorm(h Wq), rmsnorm(h Wk) over the WHOLE projection,
then heads; causal softmax attention, `heads` query heads over `kv_heads`.

A delta-rule layer (`gated_delta_mixer`), a head, with the state S a
[value_dim, key_dim] float32 matrix a sequence keeps (`ops/delta_rule.py`):

    c     = silu(conv4([q~ | k~ | v~]))         causal, depthwise, no bias
    q, k  = c_q / |c_q| * key_dim^-1/2,  c_k / |c_k|;      v = c_v
    beta  = 2 sigmoid(h W_b)          (`allow_neg_eigval`: else 1 sigmoid)
    g     = -exp(A_log) softplus(h W_a + dt_bias)          the decay's log
    S     = exp(g) S (I - beta k k^T) + beta v k^T ;       o = S q
    out   = concat_heads(rmsnorm(o) * silu(h W_g)) W_o

Parameters (`olmo_hybrid_init`, `chipbench/weights_olmo.py`): {"wte" [vocab,
dim], "head" [vocab, dim], "blocks": [...], "norm_f"}; a block has
"norm_attn" "norm_ffn" [dim], "w1" [dim, 2 * ffn_dim] (gate | up), "w2"
[ffn_dim, dim] and either "wq" "wk" "wv" "wo", "q_norm" [heads * head_dim],
"k_norm" [kv_heads * head_dim] or the mixer's "w_qkv" [dim, 2 * heads *
key_dim + heads * value_dim] (columns q~ | k~ | v~), "conv_w" [conv_kernel,
the same width] (row j multiplies the input conv_kernel - 1 - j positions
back), "w_ab" [dim, 2 * heads] (columns a | b), "a_log" "dt_bias" [heads]
(float32), "w_gate" [dim, heads * value_dim], "norm_gate" [value_dim],
"w_out" [heads * value_dim, dim].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads
from .experts import glu

__all__ = ["OlmoHybridConfig", "olmo_hybrid_init", "decoder",
           "gated_delta_mixer"]


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab: int = 100352
    dim: int = 3840
    layer_types: Tuple[str, ...] = (("linear_attention",) * 3
                                    + ("full_attention",)) * 8
    heads: int = 30
    kv_heads: int = 30
    ffn_dim: int = 11008
    linear_heads: int = 30          # key heads = value heads
    key_dim: int = 96
    value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @property
    def conv_dim(self) -> int:
        return self.linear_heads * (2 * self.key_dim + self.value_dim)

    @property
    def state_pack(self) -> int:
        from easydist_tpu.ops.delta_rule import state_pack

        return state_pack(self.linear_heads, self.value_dim)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32,
                    layer_types=("linear_attention", "linear_attention",
                                 "full_attention", "linear_attention"),
                    heads=4, kv_heads=4, ffn_dim=48, linear_heads=4,
                    key_dim=8, value_dim=64, dtype="float32")
        base.update(kw)
        return OlmoHybridConfig(**base)


def olmo_hybrid_init(cfg: OlmoHybridConfig, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in), the
    embedding normal (the residual stream must carry the token beside
    sublayer outputs that are normed to 1), gains 1 + 0.1 normal, `a_log` =
    log(uniform(1, 16)), `dt_bias` such that softplus lands log-uniformly
    in 1e-3..1e-1."""
    dtype = jnp.dtype(cfg.dtype)
    dim, h = cfg.dim, cfg.linear_heads

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, len(cfg.layer_types) + 3)
    blocks = []
    for kind, bk in zip(cfg.layer_types, keys):
        k = jax.random.split(bk, 16)
        blk = {"norm_attn": gain(k[0]), "norm_ffn": gain(k[1]),
               "w1": mat(k[2], dim, 2 * cfg.ffn_dim),
               "w2": mat(k[3], cfg.ffn_dim, dim)}
        if kind == "full_attention":
            blk.update(wq=mat(k[4], dim, cfg.heads * cfg.head_dim),
                       wk=mat(k[5], dim, cfg.kv_heads * cfg.head_dim),
                       wv=mat(k[6], dim, cfg.kv_heads * cfg.head_dim),
                       wo=mat(k[7], cfg.heads * cfg.head_dim, dim),
                       q_norm=gain(k[8], cfg.heads * cfg.head_dim),
                       k_norm=gain(k[9], cfg.kv_heads * cfg.head_dim))
        else:
            dt = jnp.exp(jax.random.uniform(
                k[10], (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            blk.update(
                w_qkv=mat(k[4], dim, cfg.conv_dim),
                conv_w=mat(k[5], cfg.conv_kernel, cfg.conv_dim),
                w_ab=mat(k[6], dim, 2 * h),
                a_log=jnp.log(jax.random.uniform(k[7], (h,), jnp.float32,
                                                 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                w_gate=mat(k[8], dim, h * cfg.value_dim),
                norm_gate=gain(k[9], cfg.value_dim),
                w_out=mat(k[11], h * cfg.value_dim, dim))
        blocks.append(blk)
    return {"wte": jax.random.normal(keys[-3], (cfg.vocab, dim),
                                     jnp.float32).astype(dtype),
            "head": mat(keys[-2], cfg.vocab, dim), "blocks": blocks,
            "norm_f": gain(keys[-1])}


def _rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def gated_delta_mixer(cfg: OlmoHybridConfig, blk, x, carry, valid):
    """The gated delta-rule mixer over activations x ([b, s, dim] a window,
    [b, dim] one position) from `carry` = {"conv": [b, (conv_kernel - 1) *
    conv_dim] (the last pre-activation conv inputs, flat: input j is the
    lanes [j * conv_dim, (j + 1) * conv_dim),
    `ops/ssm.py::causal_conv_tail`), "delta": the heads'
    states as `ops/delta_rule.py` stores them, [b, heads / pack, key_dim,
    pack * value_dim]}, both float32 -> (out like x, carry after the
    positions that are `valid` (bool [b, s] / [b]); the others leave the
    carry as it was)."""
    from easydist_tpu.ops.delta_rule import (delta_chunk_scan,
                                             delta_decode_update)
    from easydist_tpu.ops.ssm import causal_conv_tail

    dtype = jnp.dtype(cfg.dtype)
    h, d_k, d_v = cfg.linear_heads, cfg.key_dim, cfg.value_dim
    window = x.ndim == 3
    if not window:
        x, valid = x[:, None, :], valid[:, None]
    b, s, _ = x.shape
    qkv = (x @ blk["w_qkv"].astype(dtype)).astype(jnp.float32)
    qkv, new_conv = causal_conv_tail(carry["conv"], qkv, blk["conv_w"], None,
                                     valid)
    q = _unit(qkv[..., :h * d_k].reshape(b, s, h, d_k), cfg.eps) * d_k ** -0.5
    k = _unit(qkv[..., h * d_k:2 * h * d_k].reshape(b, s, h, d_k), cfg.eps)
    v = qkv[..., 2 * h * d_k:].reshape(b, s, h, d_v)
    ab = (x @ blk["w_ab"].astype(dtype)).astype(jnp.float32)
    beta = jax.nn.sigmoid(ab[..., h:]) * (2.0 if cfg.allow_neg_eigval else 1.0)
    g = -jnp.exp(blk["a_log"].astype(jnp.float32)) \
        * _softplus(ab[..., :h] + blk["dt_bias"].astype(jnp.float32))
    # a position that does not count neither decays nor corrects
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    if window:
        o, delta = delta_chunk_scan(q, k, v, g, beta, carry["delta"])
    else:
        delta, o = delta_decode_update(carry["delta"], q[:, 0], k[:, 0],
                                       v[:, 0], g[:, 0], beta[:, 0],
                                       live=valid[:, 0])
        o = o[:, None]
    gate = jax.nn.silu((x @ blk["w_gate"].astype(dtype)).astype(jnp.float32))
    y = _rmsnorm(o, blk["norm_gate"], cfg.eps) * gate.reshape(b, s, h, d_v)
    out = y.reshape(b, s, h * d_v).astype(dtype) @ blk["w_out"].astype(dtype)
    return (out if window else out[:, 0]), {"conv": new_conv, "delta": delta}


def decoder(cfg: OlmoHybridConfig) -> Decoder:
    """The model as `models/decoder.py` serves it.  The full layers have no
    positional term (`rope_theta: null`), so `qkv` ignores `pos`."""
    dtype = jnp.dtype(cfg.dtype)
    pack = cfg.state_pack

    def normed(y, g):
        return _rmsnorm(y, g, cfg.eps).astype(dtype)

    def qkv(blk, x, pos):
        q = normed(x @ blk["wq"].astype(dtype), blk["q_norm"])
        k = normed(x @ blk["wk"].astype(dtype), blk["k_norm"])
        v = x @ blk["wv"].astype(dtype)
        return split_heads(q, cfg.heads), split_heads(k, cfg.kv_heads), \
            split_heads(v, cfg.kv_heads)

    def state(blk, x, carry, valid):
        out, carry = gated_delta_mixer(cfg, blk, x, carry, valid)
        return x + normed(out, blk["norm_attn"]), carry

    return Decoder(
        layers=len(cfg.layer_types), heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv,
        attn_out=lambda blk, x, att: x + normed(att @ blk["wo"].astype(dtype),
                                                blk["norm_attn"]),
        ffn=lambda blk, x: x + normed(glu(x, blk["w1"], blk["w2"], dtype),
                                      blk["norm_ffn"]),
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["head"].T,
        kinds=tuple("state" if t == "linear_attention" else "attention"
                    for t in cfg.layer_types),
        state=state,
        state_shapes={
            "conv": (((cfg.conv_kernel - 1) * cfg.conv_dim,), jnp.float32),
            "delta": ((cfg.linear_heads // pack, cfg.key_dim,
                       pack * cfg.value_dim), jnp.float32)})
