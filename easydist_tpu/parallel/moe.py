"""Mixture-of-Experts with expert parallelism (EP).

Absent from the reference (SURVEY.md §2.9: "Expert parallel (EP / MoE) —
Absent") and first-class here.  Switch-style top-1 routing with capacity
buffers, GShard-style dense dispatch (einsum with one-hot masks — MXU
friendly, no dynamic shapes), experts sharded over the `ep` mesh axis, and
token exchange via `lax.all_to_all` inside one compiled program.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


@dataclass
class MoEConfig:
    n_experts: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    # experts per token: 1 = Switch routing, 2 = GShard-style top-2 (gates
    # renormalized over the selected experts)
    top_k: int = 1


def moe_init(cfg: MoEConfig, key) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": jax.random.normal(k1, (cfg.d_model, cfg.n_experts)) * 0.02,
        "w_in": jax.random.normal(k2, (cfg.n_experts, cfg.d_model, cfg.d_ff))
                / math.sqrt(cfg.d_model),
        "w_out": jax.random.normal(k3, (cfg.n_experts, cfg.d_ff, cfg.d_model))
                 / math.sqrt(cfg.d_ff),
    }


def _routing(probs, n_experts: int, capacity: int, top_k: int, dtype):
    """Top-k routing with per-expert capacity shared across slots.

    Returns (dispatch [n, E, C] summed over slots, per-slot combine
    weights as a list of ([n, E, C] dispatch_s, gate_s [n]) pairs,
    onehot_all [n, E] for the aux loss).
    """
    topk_probs, topk_idx = jax.lax.top_k(probs, top_k)  # [n, k]
    if top_k == 1:
        gates = topk_probs  # Switch: gate by the raw router probability
    else:
        gates = topk_probs / jnp.maximum(
            jnp.sum(topk_probs, axis=-1, keepdims=True), 1e-9)

    counts = jnp.zeros((probs.shape[1],), probs.dtype)  # filled per expert
    slot_dispatch = []
    onehot_all = jnp.zeros_like(probs)
    for s in range(top_k):
        onehot = jax.nn.one_hot(topk_idx[:, s], n_experts, dtype=dtype)
        pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - 1.0
        pos_tok = jnp.sum(pos * onehot, axis=-1)
        keep = pos_tok < capacity
        pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                                dtype=dtype)
        disp = onehot[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
        slot_dispatch.append((disp, gates[:, s] * keep))
        counts = counts + jnp.sum(onehot * keep[:, None], axis=0)
        onehot_all = onehot_all + onehot
    dispatch = sum(d for d, _ in slot_dispatch)
    return dispatch, slot_dispatch, onehot_all


def _moe_local(x, router, w_in, w_out, *, axis: str, n_experts: int,
               capacity: int, top_k: int = 1):
    """x: [n_local, d]; w_in/w_out: [E/n, ...] local expert shards."""
    n_local, d = x.shape
    ep = jax.lax.psum(1, axis)

    logits = x @ router  # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, slot_dispatch, onehot = _routing(probs, n_experts, capacity,
                                               top_k, x.dtype)
    # [n, E, C] -> buffers [E, C, d]
    buffers = jnp.einsum("nec,nd->ecd", dispatch, x)

    # exchange: every device sends its per-expert buffers to the expert
    # owner; E splits across devices, capacity concatenates
    buffers = jax.lax.all_to_all(buffers, axis, split_axis=0, concat_axis=1,
                                 tiled=True)  # [E/ep, C*ep, d]

    h = jnp.einsum("ecd,edf->ecf", buffers, w_in)
    h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, w_out)  # [E/ep, C*ep, d]

    out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                             tiled=True)  # [E, C, d]
    y = sum(jnp.einsum("nec,ecd->nd", disp, out) * gate_s[:, None]
            for disp, gate_s in slot_dispatch)

    # Switch load-balancing loss: E * sum_e frac_tokens_e * mean_prob_e,
    # averaged over devices (assignment fractions normalized by top_k)
    frac = jnp.mean(onehot, axis=0) / max(top_k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob)
    aux = jax.lax.pmean(aux, axis)
    return y, aux


def moe_layer(params: Dict, x, mesh, cfg: MoEConfig,
              axis: str = "ep") -> Tuple[jax.Array, jax.Array]:
    """x: [tokens, d_model] (token dim sharded over `axis`); experts sharded
    over `axis`.  Returns (output [tokens, d_model], aux_loss scalar)."""
    ep = mesh.shape[axis]
    if cfg.n_experts % ep != 0:
        raise ValueError(f"n_experts {cfg.n_experts} not divisible by "
                         f"ep axis size {ep}")
    n_tokens = x.shape[0]
    n_local = n_tokens // ep
    capacity = max(1, int(math.ceil(n_local * cfg.top_k
                                    * cfg.capacity_factor / cfg.n_experts)))

    fn = shard_map(
        lambda xl, r, wi, wo: _moe_local(
            xl, r, wi, wo, axis=axis, n_experts=cfg.n_experts,
            capacity=capacity, top_k=cfg.top_k),
        mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False)
    return fn(x, params["router"], params["w_in"], params["w_out"])


def moe_reference(params: Dict, x, cfg: MoEConfig, n_devices: int = 1):
    """Single-device semantics-equivalent reference (per-token python loop,
    same slot-major capacity accounting as `_routing`) used by tests."""
    import numpy as np

    n = x.shape[0]
    n_local = n // n_devices
    capacity = max(1, int(math.ceil(n_local * cfg.top_k
                                    * cfg.capacity_factor / cfg.n_experts)))
    ys = []
    auxes = []
    for s in range(n_devices):
        xs = x[s * n_local:(s + 1) * n_local]
        probs = np.asarray(jax.nn.softmax(xs @ params["router"], axis=-1))
        order = np.argsort(-probs, axis=-1)[:, :cfg.top_k]  # [n, k]
        topk = np.take_along_axis(probs, order, axis=-1)
        if cfg.top_k == 1:
            gates = topk
        else:
            gates = topk / np.maximum(topk.sum(-1, keepdims=True), 1e-9)

        counts = np.zeros(cfg.n_experts, np.int64)
        out = jnp.zeros_like(xs)
        onehot_frac = np.zeros(cfg.n_experts)
        for k in range(cfg.top_k):
            for i in range(xs.shape[0]):
                e = int(order[i, k])
                onehot_frac[e] += 1
                if counts[e] >= capacity:
                    continue
                counts[e] += 1
                h = jax.nn.gelu(xs[i] @ params["w_in"][e])
                out = out.at[i].add((h @ params["w_out"][e])
                                    * gates[i, k])
        ys.append(out)
        frac = onehot_frac / xs.shape[0] / max(cfg.top_k, 1)
        auxes.append(cfg.n_experts * jnp.sum(jnp.asarray(frac)
                                             * jnp.mean(probs, axis=0)))
    return jnp.concatenate(ys), jnp.mean(jnp.stack(auxes))
