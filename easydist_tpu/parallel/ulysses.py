"""Ulysses-style sequence parallelism: head<->sequence all_to_all.

Absent from the reference (SURVEY.md §2.9).  Inputs arrive sequence-sharded;
an `all_to_all` regroups to head-sharded full-sequence tensors so each device
runs ordinary full attention on heads/n heads, then a second all_to_all
returns to sequence sharding.  Two all_to_alls per attention vs ring's n-1
ppermutes — better for moderate sequence lengths on fat ICI.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _full_attention(q, k, v, causal: bool, scale: float):
    # single source of truth for the reference attention math
    from easydist_tpu.ops.attention_prim import _einsum_attention

    return _einsum_attention(q, k, v, causal, scale)


def ulysses_attention(q, k, v, mesh, axis: str = "sp", causal: bool = True,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None):
    """q,k,v: [batch, heads, seq, head_dim] sequence-sharded over `axis`.
    heads must be divisible by the axis size."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if attn_fn is None:
        def attn_fn(q_, k_, v_):
            return _full_attention(q_, k_, v_, causal, scale)

    def local(q_, k_, v_):
        # [b, h, t/n, d] -> all_to_all -> [b, h/n, t, d]
        def seq2head(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def head2seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = seq2head(q_), seq2head(k_), seq2head(v_)
        out = attn_fn(qh, kh, vh)
        return head2seq(out)

    spec = P(None, None, axis, None)
    # manual ONLY over `axis` (sibling mesh axes stay GSPMD-auto; see
    # ring_attention)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names=frozenset({axis}),
                     check_vma=False)(q, k, v)
