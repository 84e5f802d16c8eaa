"""LFM2-MoE (`lfm2_moe`, LiquidAI's LFM2-8B-A1B): gated short convolutions on
most layers, grouped-query attention with narrow heads on a few — which
ones is a LIST, not a period — each followed by a dense SwiGLU (the leading
`dense_layers`) or a sigmoid-routed expert FFN without a shared expert,
pre-normed, the head tied to the embedding.  Serving only: `decoder(cfg)`
is the model as `models/decoder.py` serves it; there is no training step.

    u = rmsnorm(h; operator_norm)
    conv:       [B | C | x~] = u W_in
                h = h + (C * conv3(B * x~)) W_out     causal, depthwise,
                                                      no bias, NO activation
    attention:  q, k = rmsnorm_head(u Wq), rmsnorm_head(u Wk);  v = u Wv
                q, k = rope(q), rope(k);  h = h + attention(q, k, v) Wo
    f = rmsnorm(h; ffn_norm)
    h = h + swiglu(f)   |   h + sum_j g_j expert_j(f)
    logits = rmsnorm(h) @ wte.T

A conv layer's WHOLE carry is the conv's tail: the last `conv_taps - 1`
rows of B * x~, float32, flat (`ops/ssm.py::causal_conv_tail`) — one leaf
a layer, `shortconv`, and nothing recurrent beside it.  The router scores
every expert by a sigmoid in float32, chooses the top `top_k` of score +
bias (the bias steers the choice only) and weighs a chosen expert by its
score over the chosen scores' sum + 1e-6, times `routed_scale`
(`models/experts.py::sigmoid_route`).  The expert FFN is told which experts
it holds (`experts_held`: first, how many) and computes their part.

Parameters (`lfm2_init`, `chipbench/weights_lfm2.py`): {"wte" [vocab, dim]
(the head too), "blocks": [...], "norm_f"}; a block has "norm_op" "norm_ffn"
[dim]; the mixer's "w_in" [dim, 3 * dim] (columns B | C | x~), "conv_w"
[taps, dim] (row j multiplies the input taps - 1 - j positions back),
"w_out" [dim, dim], or "wq" [dim, heads * head_dim], "wk" "wv" [dim,
kv_heads * head_dim], "wo", "q_norm" "k_norm" [head_dim]; and the dense
"w1" [dim, 2 * ffn_dim] (gate | up), "w2" [ffn_dim, dim] or "router" [dim,
experts], "router_bias" [experts] (float32), "w1" [held, dim, 2 *
expert_dim], "w2" [held, expert_dim, dim].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .decoder import Decoder, split_heads
from .exaone_moe import _rmsnorm, _rope
from .experts import expert_ffn, glu, sigmoid_route

__all__ = ["Lfm2MoeConfig", "lfm2_init", "decoder", "shortconv_mixer"]

ROUTER_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab: int = 65536
    dim: int = 2048
    layer_types: Tuple[str, ...] = tuple(
        "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
        for i in range(24))
    dense_layers: int = 2
    heads: int = 32
    kv_heads: int = 8
    rope_theta: float = 1e6
    conv_taps: int = 3
    ffn_dim: int = 7168
    experts: int = 32
    top_k: int = 4
    experts_held: Tuple[int, int] = (0, 32)      # first, how many
    expert_dim: int = 1792
    routed_scale: float = 1.0
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @staticmethod
    def tiny(**kw):
        base = dict(vocab=96, dim=32,
                    layer_types=("conv", "conv", "full_attention", "conv",
                                 "full_attention", "conv"),
                    dense_layers=2, heads=4, kv_heads=2, ffn_dim=48,
                    experts=8, top_k=2, experts_held=(0, 4), expert_dim=16,
                    dtype="float32")
        base.update(kw)
        return Lfm2MoeConfig(**base)


def lfm2_init(cfg: Lfm2MoeConfig, key) -> Dict:
    """Random parameters at `cfg.dtype`: matrices normal / sqrt(fan_in), the
    tied embedding normal * 0.02 (`models/jamba.py` says why), gains 1 + 0.1
    normal, the selection bias 0.02 normal."""
    dtype = jnp.dtype(cfg.dtype)
    dim, held, hd = cfg.dim, cfg.experts_held[1], cfg.head_dim

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def gain(k, n=dim):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dtype)

    keys = jax.random.split(key, len(cfg.layer_types) + 2)
    blocks = []
    for i, (kind, bk) in enumerate(zip(cfg.layer_types, keys)):
        k = jax.random.split(bk, 16)
        blk = {"norm_op": gain(k[0]), "norm_ffn": gain(k[1])}
        if kind == "conv":
            blk.update(w_in=mat(k[2], dim, 3 * dim),
                       conv_w=mat(k[3], cfg.conv_taps, dim),
                       w_out=mat(k[4], dim, dim))
        else:
            blk.update(wq=mat(k[2], dim, cfg.heads * hd),
                       wk=mat(k[3], dim, cfg.kv_heads * hd),
                       wv=mat(k[4], dim, cfg.kv_heads * hd),
                       wo=mat(k[5], cfg.heads * hd, dim),
                       q_norm=gain(k[6], hd), k_norm=gain(k[7], hd))
        if i < cfg.dense_layers:
            blk.update(w1=mat(k[8], dim, 2 * cfg.ffn_dim),
                       w2=mat(k[9], cfg.ffn_dim, dim))
        else:
            blk.update(router=mat(k[8], dim, cfg.experts),
                       router_bias=0.02 * jax.random.normal(
                           k[9], (cfg.experts,), jnp.float32),
                       w1=mat(k[10], held, dim, 2 * cfg.expert_dim),
                       w2=mat(k[11], held, cfg.expert_dim, dim))
        blocks.append(blk)
    return {"wte": (0.02 * jax.random.normal(keys[-2], (cfg.vocab, dim),
                                             jnp.float32)).astype(dtype),
            "blocks": blocks, "norm_f": gain(keys[-1])}


def shortconv_mixer(cfg: Lfm2MoeConfig, blk, u, carry, valid):
    """The gated short convolution over normed activations u ([b, s, dim] a
    window, [b, dim] one position) from `carry` = {"shortconv": [b,
    (conv_taps - 1) * dim]} (the last inputs B * x~ of the conv, float32,
    flat: `ops/ssm.py::causal_conv_tail`) -> (out like u, carry after the
    positions that are `valid` (bool [b, s] / [b]); the others leave it as
    it was).  The two products take `cfg.dtype` operands and sum in float32;
    the gates and the conv are float32."""
    from easydist_tpu.ops.ssm import causal_conv_tail

    dtype, f32, dim = jnp.dtype(cfg.dtype), jnp.float32, cfg.dim
    window = u.ndim == 3
    if not window:
        u, valid = u[:, None, :], valid[:, None]
    bcx = jnp.dot(u.astype(dtype), blk["w_in"].astype(dtype),
                  preferred_element_type=f32)
    gate_in, gate_out, x = (bcx[..., i * dim:(i + 1) * dim]
                            for i in range(3))
    conv, tail = causal_conv_tail(carry["shortconv"], gate_in * x,
                                  blk["conv_w"], None, valid,
                                  activation=None)
    out = jnp.dot((gate_out * conv).astype(dtype),
                  blk["w_out"].astype(dtype),
                  preferred_element_type=f32).astype(dtype)
    return (out if window else out[:, 0]), {"shortconv": tail}


def decoder(cfg: Lfm2MoeConfig) -> Decoder:
    """The model as `models/decoder.py` serves it."""
    dtype = jnp.dtype(cfg.dtype)

    def norm(x, g):
        return _rmsnorm(x, g, cfg.eps).astype(dtype)

    def qkv(blk, x, pos):
        u = norm(x, blk["norm_op"])
        q = _rmsnorm(split_heads(u @ blk["wq"].astype(dtype), cfg.heads),
                     blk["q_norm"], cfg.eps)
        k = _rmsnorm(split_heads(u @ blk["wk"].astype(dtype), cfg.kv_heads),
                     blk["k_norm"], cfg.eps)
        v = split_heads(u @ blk["wv"].astype(dtype), cfg.kv_heads)
        q, k = (_rope(y, pos, cfg.rope_theta) for y in (q, k))
        return q.astype(dtype), k.astype(dtype), v

    def state(blk, x, carry, valid):
        out, carry = shortconv_mixer(cfg, blk, norm(x, blk["norm_op"]),
                                     carry, valid)
        return x + out, carry

    def ffn(blk, x, valid):
        f = norm(x, blk["norm_ffn"])
        if "router" not in blk:
            return x + glu(f, blk["w1"], blk["w2"], dtype), None
        flat = f.reshape(-1, cfg.dim)
        idx, gate = sigmoid_route(flat, blk["router"], cfg.top_k,
                                  cfg.routed_scale, blk["router_bias"],
                                  ROUTER_EPS)
        routed, counters = expert_ffn(
            flat, idx, gate, blk["w1"], blk["w2"], cfg.experts_held, dtype,
            valid.reshape(-1))
        return x + routed.reshape(x.shape).astype(dtype), counters

    expert_layers = len(cfg.layer_types) - cfg.dense_layers
    return Decoder(
        layers=len(cfg.layer_types), heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, dtype=dtype, max_positions=None,
        blocks=lambda params: params["blocks"],
        embed=lambda params, tokens, pos: params["wte"][tokens].astype(dtype),
        qkv=qkv,
        attn_out=lambda blk, x, att: x + att @ blk["wo"].astype(dtype),
        ffn=ffn, counts=True, pair_slots=cfg.top_k * expert_layers,
        final_norm=lambda params, x: _rmsnorm(x, params["norm_f"], cfg.eps),
        unembed=lambda params, x: x.astype(jnp.float32) @ params["wte"].T,
        kinds=tuple("state" if t == "conv" else "attention"
                    for t in cfg.layer_types),
        state=state,
        state_shapes={
            "shortconv": (((cfg.conv_taps - 1) * cfg.dim,), jnp.float32)})
