"""Layer 3a: static verifier over the graph memory plan and remat rewrite.

Runs on the `(MetaGraph, MemoryPlan)` pair the planner produced
(`schedule/memory_planner.py`) plus the remat rewrite plan
(`schedule/remat.py`) — the whole memory pipeline whose errors otherwise
surface only as OOMs on real TPUs.  DistIR-style: everything here is pure
Python over already-built structures, no device execution.

  MEM001  independent liveness recomputation: every interval's
          (start, end) must match a producer/last-consumer audit done by a
          DIFFERENT traversal (operand scan vs the planner's edge lists),
          graph outputs pinned live to the program end;
  MEM002  sharded-bytes sizing: every interval's bytes must equal the
          placement-divided tensor size, element-aligned and rounded UP on
          non-divisible shard dims (the widest device's share);
  MEM003  skyline soundness: `offsets` overlap-free while live
          (`native.check_plan`), `peak_bytes` >= the sum-of-live lower
          bound, and `peak_bytes` == the packed extent max(offset+size);
  MEM004  HBM budget gate: the predicted per-device peak must fit the
          platform capacity (`edconfig.analyze_hbm_budget`, v5e default) —
          the finding carries a structured remat advisory naming which
          candidates, in `schedule/remat.py`'s largest-bytes-per-
          recompute-second order, would bring the program under budget;
  MEM005  remat-rewrite audit: every recomputed chain is pure flat
          primitives preceding its consumer, the post-rewrite planned peak
          is strictly lower, and the emitted program reads chain sources
          through `optimization_barrier` (no CSE fold-back).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from easydist_tpu import native
from easydist_tpu.metashard.metair import _DTYPE_BYTES, MetaGraph

from .findings import Finding, make_finding

# ops whose MetaIR node hides a sub-graph: not remat-chain material (the
# same exclusion as remat.py's _BANNED_PARAM_KEYS, in op_key vocabulary)
_COMPOSITE_OPS = frozenset((
    "scan", "while", "cond", "custom_jvp_call", "custom_vjp_call",
    "checkpoint", "remat", "remat2", "pjit", "closed_call",
))

# cap repeated-findings floods: each seeded fixture fires exactly once, and
# a systematically-broken plan does not drown the report
_MAX_PER_CHECK = 8


# ------------------------------------------------------ MEM001: lifetimes

def recompute_liveness(graph: MetaGraph
                       ) -> Dict[str, Tuple[int, int]]:
    """Producer/last-consumer intervals recomputed independently of
    `plan_graph_memory`: last uses come from a REVERSE operand scan over
    `node.invars` (the planner walks the forward `var.consumers` edge
    lists), so a corrupted edge list and a corrupted plan cannot agree by
    construction.  Graph outputs (op- or input-produced) are pinned live
    to the final op."""
    n_ops = len(graph.ops)
    out_names = {v.name for v in graph.outputs}
    last_use: Dict[str, int] = {}
    for i in range(n_ops - 1, -1, -1):
        for v in graph.ops[i].invars:
            if v is not None and v.name not in last_use:
                last_use[v.name] = i
    intervals: Dict[str, Tuple[int, int]] = {}
    for i, node in enumerate(graph.ops):
        for v in node.outvars:
            if v is None or v.name in intervals:
                continue
            end = max(i, last_use.get(v.name, i))
            if v.name in out_names:
                end = n_ops - 1
            intervals[v.name] = (i, end)
    for node in graph.inputs:
        for v in node.outvars:
            if v is None or v.name in intervals:
                continue
            end = last_use.get(v.name, 0)
            if v.name in out_names:
                end = n_ops - 1
            intervals[v.name] = (0, end)
    return intervals


def _vars_by_name(graph: MetaGraph) -> Dict[str, object]:
    out: Dict[str, object] = {}
    for node in graph.ops + graph.inputs:
        for v in node.outvars:
            if v is not None and v.name not in out:
                out[v.name] = v
    return out


def _plan_placements(var, per_axis: Sequence[Dict]):
    """The placement slots `plan_graph_memory` sizes a var by (its
    producer's out placement per axis)."""
    node = var.producer
    out = []
    for chosen in per_axis:
        s = chosen.get(node.name) if node is not None else None
        if s is None or var.producer_idx >= len(s.out_placements):
            out.append(None)
        else:
            out.append(s.out_placements[var.producer_idx])
    return out


def _expected_sharded_bytes(var, per_axis, axis_sizes) -> int:
    """Independent re-derivation of the interval's per-device bytes:
    element-aligned, shard dims rounded up (ceil) per axis."""
    shape = list(var.shape)
    for p, n in zip(_plan_placements(var, per_axis), axis_sizes):
        if p is not None and p.is_shard() and n > 0 and p.dim < len(shape):
            shape[p.dim] = -(-int(shape[p.dim]) // int(n))
    elems = 1
    for d in shape:
        elems *= int(d)
    return max(elems * _DTYPE_BYTES.get(var.dtype, 4), 1)


def verify_memory_plan(graph: MetaGraph, plan, per_axis: Sequence[Dict],
                       axis_sizes: Sequence[int]) -> List[Finding]:
    """MEM001 + MEM002 + MEM003 over one (graph, MemoryPlan) pair."""
    findings: List[Finding] = []

    # ---- MEM001: interval audit
    expected = recompute_liveness(graph)
    plan_iv = {name: (int(plan.starts[i]), int(plan.ends[i]))
               for i, name in enumerate(plan.var_names)}
    missing = sorted(set(expected) - set(plan_iv))
    extra = sorted(set(plan_iv) - set(expected))
    if missing:
        findings.append(make_finding(
            "MEM001", "memory-plan",
            f"{len(missing)} graph var(s) have no plan interval: "
            f"{missing[:6]}{'...' if len(missing) > 6 else ''}"))
    if extra:
        findings.append(make_finding(
            "MEM001", "memory-plan",
            f"{len(extra)} plan interval(s) name no graph var: "
            f"{extra[:6]}{'...' if len(extra) > 6 else ''}"))
    n_drift = 0
    for name in plan_iv:
        if name not in expected or n_drift >= _MAX_PER_CHECK:
            continue
        if plan_iv[name] != expected[name]:
            n_drift += 1
            findings.append(make_finding(
                "MEM001", f"memory-plan/{name}",
                f"interval {plan_iv[name]} but the independent "
                f"producer/last-consumer audit gives {expected[name]}"))

    # ---- MEM002: sizing audit
    vars_by_name = _vars_by_name(graph)
    n_size = 0
    for i, name in enumerate(plan.var_names):
        v = vars_by_name.get(name)
        if v is None or n_size >= _MAX_PER_CHECK:
            continue
        want = _expected_sharded_bytes(v, per_axis, axis_sizes)
        got = int(plan.sizes[i])
        if got != want:
            n_size += 1
            findings.append(make_finding(
                "MEM002", f"memory-plan/{name}",
                f"interval sized {got} bytes but the placement-divided "
                f"size of {v!r} is {want} (shard dims rounded up to whole "
                f"elements)"))

    # ---- MEM003: skyline soundness
    for i, j in plan.validate()[:_MAX_PER_CHECK]:
        findings.append(make_finding(
            "MEM003", f"memory-plan/{plan.var_names[i]}",
            f"offset range overlaps {plan.var_names[j]} while both are "
            f"live (offsets {int(plan.offsets[i])}+{int(plan.sizes[i])} "
            f"vs {int(plan.offsets[j])}+{int(plan.sizes[j])})"))
    if plan.peak_bytes < plan.peak_live_bytes:
        findings.append(make_finding(
            "MEM003", "memory-plan/peak",
            f"skyline peak {plan.peak_bytes} below the sum-of-live lower "
            f"bound {plan.peak_live_bytes} — a packing cannot beat "
            f"simultaneous liveness"))
    if len(plan.sizes):
        extent = int(np.max(plan.offsets + plan.sizes))
        if plan.peak_bytes != extent:
            findings.append(make_finding(
                "MEM003", "memory-plan/peak",
                f"declared peak {plan.peak_bytes} != packed extent "
                f"{extent} (max offset+size)"))
    return findings


# ------------------------------------------------- MEM004: HBM budget gate

def resolve_hbm_budget(mesh=None) -> int:
    """Per-device HBM capacity the MEM004 gate verifies against.
    `edconfig.analyze_hbm_budget` wins when set (>0); 0 disables; the
    default (-1) asks the real device
    (`runtime.calibrate.device_memory_limit`) and falls back to
    `hbm_capacity_default` only on backends that report none (CPU virtual
    meshes)."""
    from easydist_tpu import config as edconfig
    from easydist_tpu.runtime.calibrate import device_memory_limit

    cap = edconfig.analyze_hbm_budget
    if cap >= 0:
        return int(cap)
    limit = device_memory_limit(mesh) if mesh is not None else None
    return int(limit or edconfig.hbm_capacity_default)


def _node_recompute_seconds(node) -> float:
    """FLOP-proxy recompute cost of re-executing one producer node —
    the same cost dimension remat.py prices chains in (exact bridge-
    recorded MACs when available, output elements otherwise, at
    `peak_flops`)."""
    from easydist_tpu import config as edconfig

    flops = node.flops
    if flops is None:
        flops = 0.0
        for v in node.outvars:
            if v is not None:
                n = 1
                for d in v.shape:
                    n *= int(d)
                flops += float(n)
    return float(flops) / max(edconfig.peak_flops, 1.0)


def remat_advisory(graph: MetaGraph, plan, budget: int,
                   predicted: Optional[int] = None,
                   max_names: int = 6) -> str:
    """Which vars, taken in `schedule/remat.py`'s largest-bytes-per-
    recompute-second order, would bring the predicted peak under `budget`.
    Candidates must span the peak step strictly (their eviction moves the
    peak) and have a flat, re-executable producer."""
    from easydist_tpu.schedule.remat import candidate_score

    predicted = plan.peak_bytes if predicted is None else int(predicted)
    overshoot = predicted - budget
    profile = native.live_profile(plan.starts, plan.ends, plan.sizes)
    if profile.size == 0:
        return "no live intervals to rematerialize"
    t_star = int(profile.argmax())
    vars_by_name = _vars_by_name(graph)
    cands: List[Tuple[float, str, int]] = []
    for i, name in enumerate(plan.var_names):
        if not (int(plan.starts[i]) < t_star < int(plan.ends[i])):
            continue
        v = vars_by_name.get(name)
        node = v.producer if v is not None else None
        if node is None or node.is_input or node.op_key in _COMPOSITE_OPS:
            continue
        nbytes = int(plan.sizes[i])
        cands.append((candidate_score(nbytes,
                                      _node_recompute_seconds(node)),
                      name, nbytes))
    cands.sort(key=lambda c: (-c[0], c[1]))
    picked, cum = [], 0
    for _, name, nbytes in cands:
        if cum >= overshoot:
            break
        picked.append(f"{name}({nbytes}B)")
        cum += nbytes
    if not picked:
        return (f"over budget by {overshoot} bytes with no "
                f"rematerializable candidate spanning peak step {t_star}")
    shown = ", ".join(picked[:max_names])
    if len(picked) > max_names:
        shown += f", ... +{len(picked) - max_names} more"
    verdict = ("sufficient to fit" if cum >= overshoot else
               f"covers only {cum} of the {overshoot}-byte overshoot")
    return (f"remat advisory (largest bytes-per-recompute-second first): "
            f"recompute {shown} — {verdict}")


def check_hbm_budget(graph: Optional[MetaGraph], plan, budget: int,
                     remat_plan=None) -> List[Finding]:
    """MEM004: the predicted per-device peak of the program that ships
    (the remat plan's post-rewrite peak when a rewrite was applied, the
    graph skyline otherwise) must fit `budget`."""
    if budget <= 0 or plan is None:
        return []
    predicted = (int(remat_plan.predicted_peak) if remat_plan
                 else int(plan.peak_bytes))
    if predicted <= budget:
        return []
    advisory = (remat_advisory(graph, plan, budget, predicted=predicted)
                if graph is not None else "no MetaGraph for an advisory")
    return [make_finding(
        "MEM004", "memory-plan/budget",
        f"predicted per-device peak {predicted} bytes "
        f"({predicted / 2**20:.2f} MiB) exceeds the HBM budget {budget} "
        f"bytes ({budget / 2**20:.2f} MiB); {advisory}")]


# -------------------------------------------------- MEM005: remat rewrite

def _jaxpr_contains(jaxpr, prim_name: str) -> bool:
    from .jaxpr_rules import _sub_jaxprs

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == prim_name:
            return True
        for _, sub in _sub_jaxprs(eqn):
            if _jaxpr_contains(sub, prim_name):
                return True
    return False


def audit_remat_plan(closed_jaxpr, remat_plan,
                     traced=None) -> List[Finding]:
    """MEM005 over one (traced jaxpr, RematPlan) pair.  `traced` is the
    retraced EMITTED program (when available): it must carry the
    `optimization_barrier` reads that keep XLA CSE from folding the
    recomputed chains back into the originals."""
    from easydist_tpu.schedule.remat import _BANNED_PARAM_KEYS

    findings: List[Finding] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    n = len(jaxpr.eqns)
    chain_eqns = sorted({e for ch in remat_plan.recompute.values()
                         for e in ch})
    n_flat = 0
    for e in chain_eqns:
        if not (0 <= e < n):
            findings.append(make_finding(
                "MEM005", f"remat/eqn{e}",
                f"chain equation index {e} outside the program "
                f"(0..{n - 1})"))
            continue
        eqn = jaxpr.eqns[e]
        bad = [k for k in _BANNED_PARAM_KEYS if k in eqn.params]
        if bad and n_flat < _MAX_PER_CHECK:
            n_flat += 1
            findings.append(make_finding(
                "MEM005", f"remat/eqn{e}:{eqn.primitive.name}",
                f"recompute chain re-executes non-flat primitive "
                f"{eqn.primitive.name!r} (carries sub-jaxpr params {bad}) "
                f"— chains must be pure flat equations"))
    for consumer in sorted(remat_plan.recompute):
        late = [e for e in remat_plan.recompute[consumer]
                if 0 <= e < n and e >= consumer]
        if late:
            findings.append(make_finding(
                "MEM005", f"remat/consumer{consumer}",
                f"chain equation(s) {late[:4]} do not precede their "
                f"consumer eqn {consumer} — not a topological recompute"))
    if remat_plan.recompute and \
            remat_plan.predicted_peak >= remat_plan.base_peak:
        findings.append(make_finding(
            "MEM005", "remat/peak",
            f"rewrite does not lower the planned peak "
            f"({remat_plan.base_peak} -> {remat_plan.predicted_peak} "
            f"bytes) — recompute cost with no memory win"))
    if traced is not None and remat_plan.recompute and \
            not _jaxpr_contains(traced, "optimization_barrier"):
        findings.append(make_finding(
            "MEM005", "remat/emission",
            "emitted program carries no optimization_barrier: XLA CSE "
            "can fold every recomputed chain back into the original "
            "values, silently undoing the rewrite"))
    return findings
