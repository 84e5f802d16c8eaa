"""Solver-chosen rematerialization on the traced (already-differentiated)
jaxpr — the TPU re-expression of the reference's memory-opt subsystem
(profile -> plan -> replay, easydist/torch/compile_auto.py:353-453 and the
ILP address model, torch/schedule/ilp_memory_scheduler.py:25).

On TPU, XLA owns addresses, so the decision surface is *what to keep live*:
when the planned per-device peak exceeds the HBM cap, this pass picks
long-lived activations (live across the forward->backward boundary) and
rewrites the program so their far consumers RECOMPUTE them from values that
are alive anyway — the block-boundary residual stream, parameters — instead
of keeping them resident.  That is exactly `jax.checkpoint`-per-block
semantics, but chosen by the compiler from the liveness profile, after
autodiff, with no user annotation (`jax.checkpoint` itself cannot be
applied post-hoc: the user's step already contains its own value_and_grad).

Recomputed chains read their sources through `jax.lax.optimization_barrier`
so XLA's CSE cannot fold the duplicate back into the original (the same
mechanism jax.remat lowering uses).

The cost dimension is recompute-seconds vs liveness-bytes: chains are
capped in length and priced by a FLOP/HBM proxy; candidates are taken
largest-resident-bytes-per-recompute-second first.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from easydist_tpu import config as edconfig

logger = logging.getLogger(__name__)

# primitives whose equations may be re-executed: anything flat and pure.
# Equations carrying sub-jaxprs (control flow, remat regions, sharded
# calls) are not chain material — recomputing them wholesale would nest
# arbitrarily deep.
_BANNED_PARAM_KEYS = ("jaxpr", "call_jaxpr", "branches", "cond_jaxpr",
                      "body_jaxpr", "fun_jaxpr")

# XLA fusion model for liveness sizing (validated against memory_analysis
# on v5e — charging every intermediate overstated GPT-2's peak 3.4x):
# - compute-pointwise outputs whose consumers are all fusable/reduce ops
#   stay inside one fusion (softmax's exp feeding reduce+div) — never in HBM
# - layout/convert outputs with a single consumer fold into the consumer's
#   operand read (bf16 converts and transposes feeding the MXU)
_POINTWISE_PRIMS = frozenset((
    "tanh", "exp", "log", "logistic", "rsqrt", "sqrt", "neg", "abs", "sign",
    "floor", "ceil", "round", "erf", "erf_inv", "erfc", "sin", "cos",
    "integer_pow", "pow", "add", "sub", "mul", "div", "max", "min", "rem",
    "and", "or", "xor", "not", "select_n", "eq", "ne", "lt", "le", "gt",
    "ge", "iota", "copy", "stop_gradient", "is_finite", "clamp", "add_any",
    "real", "imag", "logaddexp",
))
_LAYOUT_PRIMS = frozenset((
    "convert_element_type", "broadcast_in_dim", "transpose", "reshape",
    "expand_dims", "squeeze", "rev",
))
_REDUCE_PRIMS = frozenset((
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "argmax",
    "argmin", "reduce_and", "reduce_or",
))
_FUSABLE_PRIMS = _POINTWISE_PRIMS | _LAYOUT_PRIMS  # remat-chain material


def _eqn_recomputable(eqn) -> bool:
    if any(k in eqn.params for k in _BANNED_PARAM_KEYS):
        return False
    return True


def candidate_score(nbytes: float, recompute_s: float) -> float:
    """The remat ranking metric: resident bytes reclaimed per second of
    recompute — candidates are taken largest-first.  Shared with the
    analyzer's MEM004 budget advisory (analyze/memory_rules.py) so the
    advisory names exactly the candidates this planner would pick."""
    return nbytes / (1e-6 + recompute_s)


def _eqn_flops(eqn) -> float:
    """Crude per-equation recompute cost proxy (seconds are derived by the
    caller).  dot_general: 2*M*N*K; conv: treated as expensive; everything
    else: output elements (elementwise on the VPU)."""
    out_elems = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
    name = eqn.primitive.name
    if name == "dot_general":
        dims = eqn.params.get("dimension_numbers")
        lhs = eqn.invars[0].aval.shape
        contract = 1
        if dims:
            for d in dims[0][0]:
                contract *= lhs[d]
        return 2.0 * out_elems * max(contract, 1)
    if "conv" in name:
        return 50.0 * out_elems
    return float(out_elems)


@dataclass
class RematPlan:
    """recompute: consumer eqn idx -> ordered chain eqn idxs to (re)execute.
    overlay_last_use: chain eqn idx -> last consumer idx that reads its
    outputs (emission shares recomputed values between consumers and evicts
    after this point).  redirected: consumer idx -> var names read from the
    overlay instead of the original environment."""
    recompute: Dict[int, List[int]] = field(default_factory=dict)
    overlay_last_use: Dict[int, int] = field(default_factory=dict)
    n_remat_vars: int = 0
    base_peak: int = 0
    predicted_peak: int = 0
    recompute_seconds: float = 0.0

    def __bool__(self):
        return bool(self.recompute)


class _Liveness:
    """Mutable interval model over the jaxpr's vars (one interval per var,
    op-index granularity, sizes honoring the solved per-axis shardings)."""

    def __init__(self, jaxpr, names, per_axis, axis_sizes, state_io_names):
        from jax.extend import core as jex_core

        self.jaxpr = jaxpr
        self.n_ops = max(len(jaxpr.eqns), 1)
        self.producer: Dict[object, int] = {}
        self.consumers: Dict[object, List[int]] = {}
        self.size: Dict[object, int] = {}
        self.start: Dict[object, int] = {}
        self.end: Dict[object, int] = {}
        self.is_invar: Set[object] = set()

        def sharded_bytes(var, strategy, out_idx) -> int:
            aval = var.aval
            if not hasattr(aval, "shape"):
                return 0
            size = float(np.prod(aval.shape, dtype=np.float64)
                         ) * aval.dtype.itemsize
            for chosen, n in zip(per_axis, axis_sizes):
                s = chosen.get(strategy)
                if s is None or out_idx >= len(s.out_placements):
                    continue
                p = s.out_placements[out_idx]
                if p is not None and p.is_shard():
                    size /= n
            return max(int(size), 1)

        for var in list(jaxpr.invars) + list(jaxpr.constvars):
            self.producer[var] = -1
            self.is_invar.add(var)
            self.size[var] = sharded_bytes(var, names.name(var), 0)
            self.start[var] = 0
            self.end[var] = 0
        for idx, eqn in enumerate(jaxpr.eqns):
            for k, v in enumerate(eqn.outvars):
                self.producer[v] = idx
                self.size[v] = sharded_bytes(v, f"op{idx}", k)
                self.start[v] = idx
                self.end[v] = idx
            for v in eqn.invars:
                if isinstance(v, jex_core.Literal):
                    continue
                self.consumers.setdefault(v, []).append(idx)
                if v in self.end:
                    self.end[v] = max(self.end[v], idx)

        # XLA-fusion-aware sizing (see _POINTWISE/_LAYOUT_PRIMS above): an
        # output is fusion-internal — never materialized in HBM — when its
        # consumers sit in the same fusion neighborhood (temporally near)
        # and, for compute-pointwise ops, are themselves fusable/reduce ops.
        # A far consumer is a saved-for-backward residual: always charged.
        # The model still overestimates XLA's scheduler somewhat (duplicated
        # cheap ops, multi-output fusions) — the safe direction for an OOM
        # guard.
        out_set = {v for v in jaxpr.outvars
                   if not isinstance(v, jex_core.Literal)}
        transparent = _POINTWISE_PRIMS | _LAYOUT_PRIMS | _REDUCE_PRIMS
        window = 24
        for idx, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            if name not in _POINTWISE_PRIMS and name not in _LAYOUT_PRIMS:
                continue
            for v in eqn.outvars:
                if v in out_set:
                    continue
                cons = self.consumers.get(v, ())
                if not cons:
                    self.size[v] = 0
                    continue
                if max(cons) - idx > window:
                    continue  # saved for backward: materialized
                if name in _LAYOUT_PRIMS:
                    if len(cons) <= 1:
                        self.size[v] = 0
                elif all(jaxpr.eqns[j].primitive.name in transparent
                         for j in cons):
                    self.size[v] = 0

        # jaxpr outputs live to the end; donated state outputs alias their
        # paired input buffer (size 0) and pin the input to program end
        donated_in = {in_name for in_name in state_io_names.values()}
        out_names = {}
        for v in jaxpr.outvars:
            if not isinstance(v, jex_core.Literal) and v in self.end:
                self.end[v] = self.n_ops - 1
                out_names[names.name(v)] = v
        for out_name, in_name in state_io_names.items():
            v = out_names.get(out_name)
            if v is not None:
                self.size[v] = 0
        for var in self.is_invar:
            if names.name(var) in donated_in:
                self.end[var] = self.n_ops - 1

    def live_profile(self) -> np.ndarray:
        delta = np.zeros(self.n_ops + 1, dtype=np.int64)
        for v, s in self.start.items():
            e = self.end[v]
            if e < s:
                continue
            delta[s] += self.size[v]
            delta[e + 1] -= self.size[v]
        return np.cumsum(delta[:-1])


def plan_remat(closed_jaxpr, names, per_axis: Sequence[Dict],
               axis_sizes: Sequence[int], cap_bytes: int,
               state_io_names: Optional[Dict[str, str]] = None,
               banned_eqns: Optional[Set[int]] = None
               ) -> Optional[RematPlan]:
    """Greedy liveness-driven remat planning.  Returns None when the
    program already fits (or nothing rematerializable helps).
    `banned_eqns` (e.g. deferred-reduction region members, which are
    emitted inside one shard_map) may neither join recompute chains nor
    host recompute sites."""
    from jax.extend import core as jex_core

    banned_eqns = banned_eqns or set()
    jaxpr = closed_jaxpr.jaxpr
    if not jaxpr.eqns or cap_bytes <= 0:
        return None
    lv = _Liveness(jaxpr, names, per_axis, axis_sizes, state_io_names or {})
    profile = lv.live_profile()
    base_peak = int(profile.max())
    if base_peak <= cap_bytes:
        return None

    plan = RematPlan(base_peak=base_peak, predicted_peak=base_peak)
    max_chain = edconfig.remat_max_chain_len
    # seconds proxy for chain pricing; measured per-op times (PerfDB,
    # runtime/op_profile.py — ROADMAP #5) replace the FLOP proxy per eqn
    # when a profile exists for the op's signature
    flops_per_s = max(edconfig.peak_flops, 1.0)
    op_times: Dict[str, float] = {}
    if edconfig.use_op_cost_db:
        try:
            from easydist_tpu.runtime.op_profile import load_op_times

            op_times = load_op_times()
        except Exception:
            op_times = {}
    sig_cache: Dict[int, Optional[str]] = {}

    def eqn_seconds(e: int) -> float:
        if op_times:
            sig = sig_cache.get(e)
            if sig is None and e not in sig_cache:
                from easydist_tpu.jaxfront.interpreter import eqn_signature

                try:
                    sig = eqn_signature(jaxpr.eqns[e], names)
                except Exception:
                    sig = None
                sig_cache[e] = sig
            measured = op_times.get(sig) if sig else None
            if measured is not None:
                return measured
        return _eqn_flops(jaxpr.eqns[e]) / flops_per_s

    # vars whose far consumers have been redirected (no longer readable
    # past their shortened end)
    rematted: Set[object] = set()
    accounted_eqns: Set[int] = set()  # chain eqns already priced (unique)

    def build_chain(target, at: int) -> Optional[List[int]]:
        """Eqn indices (ascending = topological) whose re-execution at op
        `at` reproduces `target` from values alive at `at`."""
        chain: Set[int] = set()
        stack = [target]
        while stack:
            u = stack.pop()
            if isinstance(u, jex_core.Literal):
                continue
            if u is not target:
                if u in lv.is_invar:
                    continue
                if lv.end.get(u, -1) >= at and u not in rematted:
                    continue  # alive at the consumer: read, don't recompute
            e = lv.producer.get(u)
            if e is None or e < 0:
                continue
            if e in chain:
                continue
            if e in banned_eqns:
                return None
            eqn = jaxpr.eqns[e]
            if not _eqn_recomputable(eqn):
                return None
            chain.add(e)
            if len(chain) > max_chain:
                return None
            stack.extend(eqn.invars)
        return sorted(chain)

    def metric(profile) -> Tuple[int, int]:
        """(peak, bytes-x-ops area above cap): a commit that shaves a
        plateau point without moving the max is still progress."""
        return (int(profile.max()),
                int(np.maximum(profile - cap_bytes, 0).sum()))

    for _round in range(2048):
        profile = lv.live_profile()
        peak = int(profile.max())
        cur_metric = metric(profile)
        plan.predicted_peak = peak
        if peak <= cap_bytes:
            break
        t_star = int(profile.argmax())

        # candidates: eqn-produced vars resident across the peak whose far
        # consumers can recompute them
        cands: List[Tuple[float, object, int, List[int]]] = []
        for v, s in lv.start.items():
            if v in lv.is_invar or v in rematted or lv.size[v] == 0:
                continue
            if not (s < t_star < lv.end[v]):
                continue
            far = [j for j in lv.consumers.get(v, []) if j > t_star]
            if not far or len(far) > 4 \
                    or any(j in banned_eqns for j in far):
                continue
            chain = build_chain(v, min(far))
            if not chain:
                continue
            cost_s = sum(eqn_seconds(e) for e in chain)
            score = candidate_score(lv.size[v], cost_s)
            cands.append((score, v, t_star, chain))
            if len(cands) >= 256:
                break
        if not cands:
            logger.warning(
                "[remat] peak %.2f GiB still over cap %.2f GiB and no "
                "rematerializable candidates remain",
                peak / 2**30, cap_bytes / 2**30)
            break
        cands.sort(key=lambda c: -c[0])

        # try candidates best-first until one genuinely improves the
        # metric; rejections are per-round (a candidate useless at this
        # peak point may help after the peak moves)
        committed = False
        for _, v, t_cut, chain in cands:
            # snapshot for rollback: a remat whose recompute-span residency
            # outweighs the saving must not be committed
            saved_end = dict(lv.end)
            saved_recompute = {k: list(vv)
                               for k, vv in plan.recompute.items()}
            saved_last_use = dict(plan.overlay_last_use)
            saved_seconds = plan.recompute_seconds
            saved_accounted = set(accounted_eqns)

            far = [j for j in lv.consumers[v] if j > t_cut]
            near = [j for j in lv.consumers[v] if j <= t_cut]
            first_far, last_far = min(far), max(far)
            for j in far:
                merged = set(plan.recompute.get(j, ())) | set(chain)
                plan.recompute[j] = sorted(merged)
            for e in chain:
                plan.overlay_last_use[e] = max(
                    plan.overlay_last_use.get(e, -1), last_far)
                # overlay sharing executes a chain equation once even when
                # several committed vars' chains contain it — count unique
                if e not in accounted_eqns:
                    accounted_eqns.add(e)
                    plan.recompute_seconds += eqn_seconds(e)
            # model: original interval ends at the last near consumer; the
            # recomputed copy lives [first_far, last_far]; chain sources
            # read at first_far stay resident through last_far.  Chain
            # intermediates are transient inside the consumer's slot (XLA
            # frees them within the fused region) and are not charged.
            lv.end[v] = max(near) if near else lv.start[v]
            chain_set = set(chain)
            key = ("remat", v, first_far)
            lv.producer[key] = first_far
            lv.size[key] = lv.size.get(v, 0)
            lv.start[key] = first_far
            lv.end[key] = last_far
            for e in chain:
                for u in jaxpr.eqns[e].invars:
                    if isinstance(u, jex_core.Literal):
                        continue
                    if lv.producer.get(u, -1) in chain_set:
                        continue  # overlay-internal
                    if u in lv.end:
                        lv.end[u] = max(lv.end[u], last_far)

            new_metric = metric(lv.live_profile())
            logger.debug("[remat] round %d t*=%d chain=%d metric %s -> %s",
                         _round, t_star, len(chain), cur_metric, new_metric)
            if new_metric < cur_metric:
                rematted.add(v)
                committed = True
                break
            # roll back
            lv.end = saved_end
            lv.producer.pop(key, None)
            lv.size.pop(key, None)
            lv.start.pop(key, None)
            plan.recompute = saved_recompute
            plan.overlay_last_use = saved_last_use
            plan.recompute_seconds = saved_seconds
            accounted_eqns = saved_accounted
        if not committed:
            logger.info(
                "[remat] no candidate improves the profile at peak %.2f "
                "GiB (cap %.2f GiB); stopping with %d vars",
                peak / 2**30, cap_bytes / 2**30, len(rematted))
            break

    plan.n_remat_vars = len(rematted)
    if not plan.recompute:
        return None
    logger.info(
        "[remat] %d vars rematerialized across %d consumers: planned peak "
        "%.2f -> %.2f GiB (cap %.2f), est. recompute %.1f ms/step",
        plan.n_remat_vars, len(plan.recompute), plan.base_peak / 2**30,
        plan.predicted_peak / 2**30, cap_bytes / 2**30,
        plan.recompute_seconds * 1e3)
    return plan


def resolve_memory_cap(mesh) -> int:
    """Per-device HBM budget in bytes, with `memory_ratio` headroom
    applied uniformly (the solver's liveness constraint scales the same
    way — an explicit cap without the ratio would ship programs with none
    of the allocator headroom the ratio exists to provide).  Config wins
    when set (>0); 0 disables; the default (-1) asks the real device
    (`runtime.calibrate.device_memory_limit`).  A backend that reports no
    limit (CPU virtual meshes) -> uncapped."""
    from easydist_tpu.runtime.calibrate import device_memory_limit

    cap = edconfig.per_device_memory_cap
    if cap >= 0:
        return int(cap * edconfig.memory_ratio) if cap > 0 else 0
    limit = device_memory_limit(mesh)
    return int(limit * edconfig.memory_ratio) if limit else 0
