"""jaxpr -> MetaGraph bridge (reference: easydist/jax/bridge.py:21-111).

Each jaxpr equation becomes one MetaNode named `op{i}`; every invar/constvar
becomes a placeholder node whose sharding space comes from the analytic view
rule on its own shape (any dim shardable, concat recombination).  Non-Var
(literal) equation inputs are skipped in graph edges but accounted for in the
`arg_rows` mapping so strategy in-placements line up with discovery rows.

The `var_shapes` override lets the frontend pre-shrink shapes already sharded
on previously-solved mesh axes (reference bridge.py:62-83).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.extend import core as jex_core

from easydist_tpu import config as edconfig
from easydist_tpu.metashard import view_rule
from easydist_tpu.metashard.metair import MetaGraph, MetaNode, MetaVar
from .interpreter import VarNames, eqn_signature


def _eqn_flops(eqn) -> float:
    """Rough FLOP estimate for replication accounting: exact-ish for
    dot_general/conv, a Pallas kernel's own `cost_estimate`, length x body
    for scan, output numel otherwise."""
    import math

    prim = eqn.primitive.name
    if prim == "dot_general":
        (lhs_c, _), (lhs_b, _) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        out = eqn.outvars[0].aval
        k = math.prod(lhs.shape[d] for d in lhs_c) if lhs_c else 1
        return 2.0 * math.prod(out.shape) * k
    if prim in ("conv_general_dilated",):
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        return 2.0 * math.prod(out.shape) * math.prod(rhs.shape[2:]) \
            * rhs.shape[1]
    if eqn.params.get("cost_estimate") is not None:  # a pallas_call's
        return float(eqn.params["cost_estimate"].flops)
    if prim == "scan":
        inner = eqn.params.get("jaxpr")
        length = eqn.params.get("length", 1)
        if inner is not None and hasattr(inner, "jaxpr"):
            return length * sum(_eqn_flops(e) for e in inner.jaxpr.eqns)
    if prim == "cond":
        branch_flops = [sum(_eqn_flops(e) for e in br.jaxpr.eqns)
                        for br in eqn.params.get("branches", ())
                        if hasattr(br, "jaxpr")]
        if branch_flops:
            return max(branch_flops)
    if prim == "while":
        per_trip = sum(
            _eqn_flops(e)
            for part in (eqn.params.get("body_jaxpr"),
                         eqn.params.get("cond_jaxpr"))
            if part is not None and hasattr(part, "jaxpr")
            for e in part.jaxpr.eqns)
        if per_trip:
            return edconfig.while_trip_estimate * per_trip
    if prim in ("remat2", "remat", "checkpoint", "pjit", "custom_vjp_call",
                "custom_jvp_call"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is not None:
            body = inner.jaxpr if hasattr(inner, "jaxpr") else inner
            return sum(_eqn_flops(e) for e in getattr(body, "eqns", []))
    return float(sum(math.prod(v.aval.shape) for v in eqn.outvars
                     if hasattr(v.aval, "shape")))


def jaxpr_to_metagraph(closed_jaxpr, rules: Dict[str, dict],
                       shape_info: Dict[str, Tuple],
                       world_size: int,
                       names: Optional[VarNames] = None,
                       var_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                       state_io: Optional[Dict[str, str]] = None) -> MetaGraph:
    """Build the MetaGraph.  `state_io` maps output var name -> input var name
    for train-state threading (new params should land where old params live)."""
    jaxpr = closed_jaxpr.jaxpr
    names = names or VarNames()
    var_shapes = var_shapes or {}
    graph = MetaGraph()
    mvars: Dict[str, MetaVar] = {}

    def get_shape(var) -> Tuple[Tuple[int, ...], str]:
        name = names.name(var)
        if name in shape_info:
            shape, dtype = shape_info[name]
        else:
            shape, dtype = tuple(var.aval.shape), var.aval.dtype.name
        return var_shapes.get(name, shape), dtype

    for var in jaxpr.invars + jaxpr.constvars:
        name = names.name(var)
        shape, dtype = get_shape(var)
        mv = MetaVar(name, shape, dtype)
        mvars[name] = mv
        rule = view_rule(list(shape), list(shape), world_size=world_size)
        node = MetaNode(name=name, op_key="placeholder", invars=[],
                        outvars=[mv], space=rule["space"],
                        recombines=rule["recombines"], is_input=True)
        graph.add_input(node)

    for idx, eqn in enumerate(jaxpr.eqns):
        sig = eqn_signature(eqn, names)
        rule = rules.get(sig, {"space": None, "recombines": {}})

        invars, arg_rows = [], []
        row = 0
        for v in eqn.invars:
            if isinstance(v, jex_core.Literal):
                # literal scalars occupy no discovery row and no graph edge
                continue
            invars.append(mvars[names.name(v)])
            arg_rows.append(row)
            row += 1

        outvars = []
        for v in eqn.outvars:
            name = names.name(v)
            shape, dtype = get_shape(v)
            mv = MetaVar(name, shape, dtype)
            mvars[name] = mv
            outvars.append(mv)

        node = MetaNode(name=f"op{idx}", op_key=eqn.primitive.name,
                        invars=invars, outvars=outvars,
                        space=rule["space"], recombines=rule["recombines"],
                        arg_rows=arg_rows, sig=sig)
        if eqn.primitive.name in ("dot_general", "conv_general_dilated") \
                or eqn.params.get("cost_estimate") is not None:
            # exact MACs from dimension_numbers (a kernel's from its own
            # cost estimate), recorded while we still have the eqn:
            # shape-only recovery of the contraction length is ambiguous
            # (square matmuls vs batched dots, r5 review #3)
            node.flops = _eqn_flops(eqn)
        node.shard_where_valid = bool(rule.get("shard_where_valid"))
        if rule.get("compute") is not None:
            node.compute_proxy = float(rule["compute"])
        if rule.get("strategies") is not None:
            from easydist_tpu.metashard.metair import NodeStrategy

            explicit = []
            for ins, outs, cost, *rest in rule["strategies"]:
                s = NodeStrategy(ins, outs)
                s.intrinsic_cost = float(cost)
                if rest and rest[0] is not None:
                    s.compute_cost = float(rest[0])
                if len(rest) > 1 and rest[1]:
                    # emission metadata (e.g. attention variant ring/ulysses
                    # — same boundary placements, different lowering)
                    s.meta = dict(rest[1])
                explicit.append(s)
            node.explicit_strategies = explicit
        graph.add_op(node)

    for v in jaxpr.outvars:
        if isinstance(v, jex_core.Literal):
            continue
        graph.outputs.append(mvars[names.name(v)])

    if state_io:
        placeholder_by_name = {n.name: n for n in graph.inputs}
        for out_name, in_name in state_io.items():
            if out_name in mvars and in_name in placeholder_by_name:
                graph.state_io[out_name] = placeholder_by_name[in_name]

    return graph
