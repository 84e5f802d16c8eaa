"""The operations and bytes an algorithm needs, from shapes alone.  A
roofline share divides these by a measured time, so only a wrong time can
push it over 100%: nothing here counts recomputation, padding, masked-out
blocks or bytes a kernel happens to re-read."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"chipbench/peaks.json has no row for device_kind "
            f"{device_kind!r}; known: "
            f"{[k for k in table if not k.startswith('_')]}")
    return table[device_kind]


def causal_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                           backward: bool = False) -> float:
    """Causal self-attention over `seq` positions: the lower triangle,
    diagonal included, is seq * (seq + 1) / 2 score entries per head.
    Forward: QK^T and PV, 2 * head_dim multiply-adds each per entry = 4 *
    head_dim FLOPs.  Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK =
    dS^T Q — four products, 8 * head_dim FLOPs per entry (the forward's
    scores recomputed inside a flash backward are not needed work)."""
    entries = batch * heads * seq * (seq + 1) / 2
    return entries * head_dim * (8.0 if backward else 4.0)


def paged_decode_bytes(live_tokens: int, slots: int, q_heads: int,
                       kv_heads: int, head_dim: int, itemsize: int) -> float:
    """One decode-attention call over `slots` sequences holding
    `live_tokens` cached tokens in all: every live K and V row read once
    per KV head (grouped query heads share it), q read and o written."""
    kv = 2 * live_tokens * kv_heads * head_dim * itemsize
    qo = 2 * slots * q_heads * head_dim * itemsize
    return float(kv + qo)


def paged_decode_flops(live_tokens: int, q_heads: int,
                       head_dim: int) -> float:
    """q . K^T and p . V over every live token, per query head."""
    return 4.0 * live_tokens * q_heads * head_dim


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds the chip could take, which peak binds)."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def gpt2_train_flops_per_token(sizes: dict) -> float:
    """Forward + backward FLOPs one trained token needs: 6 per matmul
    parameter (the tied head counted once, as a matmul; embeddings' lookup
    is free) plus causal attention at the context length."""
    d, L, t = sizes["n_embd"], sizes["n_layer"], sizes["n_positions"]
    vocab = sizes.get("padded_vocab_size", sizes["vocab_size"])
    matmul_params = L * 12 * d * d + vocab * d
    attn = L * (causal_attention_flops(1, sizes["n_head"], t,
                                       d // sizes["n_head"])
                + causal_attention_flops(1, sizes["n_head"], t,
                                         d // sizes["n_head"], True)) / t
    return 6.0 * matmul_params + attn
