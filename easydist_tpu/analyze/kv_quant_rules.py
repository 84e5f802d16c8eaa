"""Layer 13: quantized/tiered-KV sanitizer.

The block-scaled int8 KV arena (ops/flash_attention.py::kv_quantize /
kv_dequantize, models gpt+llama paged forwards) and the host memory tier
(kv/tier.py) both fail the same way the paged layout does: silently.
A payload page whose scales went missing dequantizes into garbage; a
decode program that forgot the dequant computes logits on raw int8
codes, off by exactly the per-block scale; a host-tier entry whose
bytes rotted serves a corrupt prefix to every request sharing it.  None
of these crash — they emit plausible wrong tokens.  Three audits:

  * KVQ001 `audit_quant_arena` — structural payload/scale consistency
    over a live arena pytree: int8 payload implies a float32 scale leaf
    whose shape is the payload's with the feature axis divided into
    blocks; scale leaves over a non-int8 payload are equally a desync
    (the exact path must stay scale-free so its programs stay
    jaxpr-identical to pre-quant builds);
  * KVQ002 `audit_quant_program` — jaxpr lint over a compiled paged
    step: no `dot_general` may consume an int8-typed operand.  A
    correct quant program dequantizes (convert + scale multiply) before
    attention, so int8 reaching a dot IS the missing-dequant bug;
  * KVQ003 `audit_tier_roundtrip` — wraps `HostTier.check_invariants`
    (per-entry sha256 manifest re-verification + byte accounting) into
    findings, the same shape KV001 gives the page-table audit.

Wired as session hooks next to KV001: the paged first-decode audit runs
KVQ001/KVQ002 when the arena is quantized, and KVQ003 whenever a host
tier is attached.
"""

from __future__ import annotations

from typing import List

from .findings import Finding, make_finding


def _pair_problem(name: str, payload, scales):
    """What is wrong between one layer's payload leaf and its scale leaf
    (None where the arena carries no scales), or None."""
    import numpy as np

    quantized = np.dtype(payload.dtype) == np.int8
    if quantized and scales is None:
        return (f"{name!r} payload is int8 but the arena carries no "
                f"{name}_scale leaf — pages cannot be dequantized")
    if not quantized and scales is not None:
        return (f"arena carries {name}_scale over a "
                f"{np.dtype(payload.dtype).name} payload — the exact path "
                f"must stay scale-free (jaxpr-identical contract)")
    if not quantized:
        return None
    if np.dtype(scales.dtype) != np.float32:
        return (f"{name}_scale dtype is {np.dtype(scales.dtype).name}, "
                f"expected float32")
    d = int(payload.shape[-1])
    nb = int(scales.shape[-1])
    if tuple(scales.shape[:-1]) != tuple(payload.shape[:-1]) \
            or nb < 1 or d % nb != 0:
        return (f"{name}_scale shape {tuple(scales.shape)} does not "
                f"block-partition payload shape {tuple(payload.shape)} "
                f"(leading dims must match; head_dim {d} must divide into "
                f"{nb} blocks) — dequant would broadcast scales onto the "
                f"wrong pages")
    return None


def audit_quant_arena(arena, node: str = "kv.quant") -> List[Finding]:
    """KVQ001 over an arena pytree ({"k","v"[,"k_scale","v_scale"]}, each
    a tuple of one leaf per layer): every layer's payload leaf against the
    scale leaf at the same position; one finding a key, naming the first
    layer at fault."""
    findings: List[Finding] = []
    for name in ("k", "v"):
        payloads = arena.get(name)
        scales = arena.get(f"{name}_scale")
        if not payloads:
            problem = f"arena has no {name!r} payload leaf"
        elif scales is not None and len(scales) != len(payloads):
            problem = (f"{name}_scale has {len(scales)} layer leaves over "
                       f"{len(payloads)} payload leaves")
        else:
            problem = None
            for li, payload in enumerate(payloads):
                problem = _pair_problem(
                    name, payload, None if scales is None else scales[li])
                if problem:
                    problem = f"layer {li}: {problem}"
                    break
        if problem:
            findings.append(make_finding("KVQ001", node, problem))
    return findings


def _int8_dot_operands(jaxpr) -> List[str]:
    """Descriptions of every dot_general consuming an int8 operand,
    recursing into sub-jaxprs (pjit/cond/scan/remat)."""
    import numpy as np

    hits: List[str] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            for i, iv in enumerate(eqn.invars):
                aval = getattr(iv, "aval", None)
                if aval is not None and \
                        np.dtype(aval.dtype) == np.int8:
                    hits.append(
                        f"dot_general operand {i} has dtype int8 "
                        f"(shape {tuple(aval.shape)})")
        for param in eqn.params.values():
            sub = []
            if hasattr(param, "jaxpr"):
                sub = [param.jaxpr]
            elif isinstance(param, (list, tuple)):
                sub = [p.jaxpr for p in param if hasattr(p, "jaxpr")]
            for s in sub:
                hits.extend(_int8_dot_operands(s))
    return hits


def audit_quant_program(result, node: str = "decode.quant") -> List[Finding]:
    """KVQ002 over a compiled paged step (`get_compiled` result): retrace
    `result.jitted` on its input avals and lint the jaxpr for int8
    operands reaching a `dot_general`.  When the retrace is unavailable
    the audit skips (same policy as SERVE002's mask walk)."""
    try:
        import jax

        traced = jax.make_jaxpr(result.jitted)(*result.in_avals)
    except Exception:
        return []
    return [make_finding(
        "KVQ002", node,
        f"{hit} — int8 K/V reached attention without dequantization "
        f"(kv_dequantize / the quant kernel's in-loop scale multiply "
        f"must run before the score matmul)")
        for hit in _int8_dot_operands(traced.jaxpr)]


def audit_tier_roundtrip(tier, node: str = "kv.tier") -> List[Finding]:
    """KVQ003 over a live `HostTier`: re-verify every entry's sha256
    manifest and the byte accounting."""
    return [make_finding("KVQ003", node, problem)
            for problem in tier.check_invariants()]
