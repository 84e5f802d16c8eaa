"""Plain reference for the Mistral-7B decoder: RMSNorm, grouped-query
attention with rotary embeddings, SwiGLU.  float32 `jax.numpy` under
`default_matmul_precision("highest")`; no kernels, no cache, no batching;
imports nothing of the program.

Departures from the published model, both the program's (config `assumed`):
the output head is tied to the embedding, and the rotary pairs are the
interleaved (2i, 2i+1) pairs of the RoFormer paper rather than the
half-split layout of the Hugging Face checkpoint (the same function up to a
fixed permutation of the q/k projection columns).

One full forward over prompt + served tokens, a layer at a time, the
(bf16) weights upcast inside each layer's program so that float32 copies
never sit beside them.  `quant` is the control's lower precision: every
matmul operand is rounded to fp8 (e4m3) with a per-row scale first."""

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def fake_fp8(x, axis=-1):
    """Round to float8_e4m3 with a per-row absmax scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, quant):
    if quant:
        a, w = fake_fp8(a, -1), fake_fp8(w, 0)
    return a @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [t, heads, hd]; interleaved pairs, positions 0..t-1."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "hd", "theta",
                                             "eps", "quant"))
def _layer(x, blk, *, n_q, n_kv, hd, theta, eps, quant):
    with jax.default_matmul_precision("highest"):
        blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
        t = x.shape[0]
        h = _rmsnorm(x, blk["attn_norm"], eps)
        q = _rope(_mm(h, blk["wq"], quant).reshape(t, n_q, hd), theta)
        k = _rope(_mm(h, blk["wk"], quant).reshape(t, n_kv, hd), theta)
        v = _mm(h, blk["wv"], quant).reshape(t, n_kv, hd)
        rep = n_q // n_kv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, n_q * hd)
        x = x + _mm(att, blk["wo"], quant)
        h = _rmsnorm(x, blk["ffn_norm"], eps)
        gated = jax.nn.silu(_mm(h, blk["w_gate"], quant)) \
            * _mm(h, blk["w_up"], quant)
        return x + _mm(gated, blk["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, wte, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_f.astype(jnp.float32), eps)
        return _mm(x, wte.astype(jnp.float32).T, quant)


def logits(params, sizes: dict, tokens, rows=None, quant: bool = False):
    """tokens: int32 [t] -> float32 logits [len(rows) or t, vocab]."""
    kw = dict(n_q=sizes["num_attention_heads"],
              n_kv=sizes["num_key_value_heads"], hd=sizes["head_dim"],
              theta=float(sizes["rope_theta"]),
              eps=float(sizes["rms_norm_eps"]), quant=quant)
    x = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    for blk in params["blocks"]:
        x = _layer(x, blk, **kw)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm_f"], params["wte"], eps=kw["eps"],
                 quant=quant)
