"""Ring attention: exact attention over sequence-sharded Q/K/V.

Absent from the reference (SURVEY.md §2.9: context parallel / ring attention
"Absent") and required here as a first-class long-context capability.  Each
device holds a sequence chunk of Q, K, V; K/V blocks rotate around the ICI
ring with `lax.ppermute` while a flash-style online softmax accumulates the
exact result — memory per device is O(seq/n), communication overlaps with
the block computation, and the whole thing is one compiled XLA program.

Layout: [batch, heads, seq_shard, head_dim] inside `shard_map` over the
sequence mesh axis.  Causal masking uses global positions derived from the
device's ring index.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _block_attn(q, k, v, mask, scale):
    """One block: returns (unnormalized out, running max, running denom)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(mask, s, jnp.array(-1e30, s.dtype))
    m = jnp.max(s, axis=-1)  # [b,h,q]
    # rows with no visible keys: keep m finite so exp() is well-defined
    m_safe = jnp.maximum(m, -1e30 / 2)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m_safe, l


def _ring_attention_local(q, k, v, axis: str, causal: bool, scale: float,
                          block_impl: str = "einsum"):
    n = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    t_local = q.shape[2]

    q_pos = idx * t_local + jnp.arange(t_local)

    def flash_block(q_, k_blk, v_blk, src):
        """Pallas flash kernel as the per-block compute: its normalized
        output + logsumexp form a valid (o, m, l=1) triple for the online
        merge (o_norm = o_raw/l and lse = m + log l).  flash_attention_lse
        is a custom_vjp in both outputs, so the ring stays differentiable."""
        from easydist_tpu.ops.flash_attention import flash_attention_lse

        b, h, t, _ = q_.shape

        def run(block_causal):
            out, lse = flash_attention_lse(q_, k_blk, v_blk, block_causal,
                                           scale)
            return out.astype(jnp.float32), lse.reshape(b, h, t)

        if causal:
            out_b, lse_b = jax.lax.cond(
                src == idx, lambda _: run(True), lambda _: run(False), None)
            visible = src <= idx  # src > idx: block fully in the future
            m_b = jnp.where(visible, lse_b, -1e30 / 2)
            l_b = jnp.where(visible, 1.0, 0.0) * jnp.ones_like(lse_b)
            o_b = jnp.where(visible, out_b, 0.0)
        else:
            o_b, m_b = run(False)
            l_b = jnp.ones_like(m_b)
        return o_b, m_b, l_b

    def step(carry, r):
        o_acc, m_acc, l_acc, k_blk, v_blk = carry
        # block r came from device (idx - r) mod n
        src = jnp.mod(idx - r, n)
        if block_impl == "flash":
            o_b, m_b, l_b = flash_block(q, k_blk, v_blk, src)
        else:
            k_pos = src * t_local + jnp.arange(t_local)
            if causal:
                mask = k_pos[None, None, None, :] <= q_pos[None, None, :,
                                                           None]
            else:
                mask = jnp.ones((1, 1, t_local, t_local), bool)
            # rotate k/v in their input dtype (half the ICI bytes for
            # bf16); accumulate in f32 per block
            o_b, m_b, l_b = _block_attn(q, k_blk.astype(jnp.float32),
                                        v_blk.astype(jnp.float32), mask,
                                        scale)

        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        o_acc = o_acc * alpha[..., None] + o_b * beta[..., None]
        l_acc = l_acc * alpha + l_b * beta

        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (o_acc, m_new, l_acc, k_blk, v_blk), None

    b, h, t, d = q.shape
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t), -1e30 / 2, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v),
                                      jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   block_impl: Optional[str] = None):
    """Exact attention with q/k/v sequence-sharded over mesh axis `axis`.

    q, k, v: [batch, heads, seq, head_dim] global arrays (seq divisible by
    the axis size).  Returns [batch, heads, seq, head_dim] sharded the same.

    block_impl: per-device block compute — "flash" (Pallas kernel, O(t/n)
    block memory) or "einsum".  None auto-selects flash on TPU.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_impl is None:
        block_impl = "flash" if jax.default_backend() == "tpu" else "einsum"
    fn = functools.partial(_ring_attention_local, axis=axis, causal=causal,
                           scale=scale, block_impl=block_impl)
    spec = P(None, None, axis, None)
    # manual ONLY over `axis`: other mesh axes stay GSPMD-auto, so a batch
    # or head sharding chosen on a sibling axis (hybrid dp x sp) survives
    # into the block compute instead of being forced replicated
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names=frozenset({axis}),
                     check_vma=False)(q, k, v)
