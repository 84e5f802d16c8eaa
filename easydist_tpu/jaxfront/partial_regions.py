"""Deferred-reduction emission for solver-chosen PARTIAL chains.

GSPMD has no user-visible "partial" annotation: when the solver defers an
all-reduce across a linear chain (dot -> scale -> dot -> sum), plain
constraint emission cannot express it — XLA reduces right after the first
dot (measured: an 8 KiB all-reduce where a 4-byte one suffices).  This pass
finds maximal runs of consecutive equations whose chosen strategy carries
PARTIAL on one mesh axis and emits each run as a `shard_map` region:
sharded sources enter per their solved placement, the chain computes on
local shards (values inside are partial-by-construction), and ONE
`jax.lax.psum` at the region fence realizes the deferred reduction —
exactly the reference's global-partial deferral (metair.py:376-481)
re-expressed with XLA collectives.

Scope: one deferred axis per region, flat primitives only.  Other mesh
axes may carry SHARD placements (hybrid dp x tp): the region is emitted
with EVERY axis manual, using the solved placements as in/out specs, so
GSPMD gets no freedom to re-layout inside (an `auto`-axes variant measured
2 MiB of involuntary-rematerialization all-gathers).  That requires the
run to be sync-free on the other axes — each in-run consumer's placement
must equal its producer's — and excludes runs carrying another axis's
PARTIAL (two simultaneous deferred reductions would need coupled fences).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Set

logger = logging.getLogger(__name__)

# primitives that may live inside a region: P-creators (contracted dot /
# sharded-dim reduce) + P-linear chain ops (match the pool injection,
# interpreter._PARTIAL_LINEAR_*)
_REGION_PRIMS = frozenset((
    "dot_general", "reduce_sum", "reshape", "transpose",
    "convert_element_type", "squeeze", "expand_dims", "broadcast_in_dim",
    "neg", "rev", "slice", "copy", "mul", "div", "add_any",
))

# primitives whose params bake in GLOBAL shapes/indices (reshape new_sizes,
# slice start/limit, broadcast target shape) or whose local execution does
# not commute with sharding (rev flips shard order).  Safe when every region
# tensor is full-shape (P on the deferred axis, R elsewhere) — the v1
# situation — but wrong on local blocks, so a run carrying another axis's
# SHARD placements must not contain them.
_GLOBAL_SHAPE_PRIMS = frozenset((
    "reshape", "broadcast_in_dim", "slice", "rev",
))


@dataclass
class PartialRegion:
    """One consecutive run [start, end] of P-carrying equations."""
    start: int
    end: int
    axis_idx: int
    axis_name: str
    # source var -> {tensor dim: axis name} (its solved S placements, the
    # deferred axis AND any other sharded axes)
    source_specs: Dict[object, Dict[int, str]] = field(default_factory=dict)
    # fence var -> {tensor dim: axis name} on the NON-deferred axes (the
    # deferred axis exits replicated, or sharded via fence_scatter)
    out_specs_map: Dict[object, Dict[int, str]] = field(default_factory=dict)
    # region-produced vars read outside the region that are P at region
    # exit (need the psum fence)
    fence_partial: Set[object] = field(default_factory=set)
    # fence vars whose every outside consumer wants S(dim) on the deferred
    # axis: the fence pays psum_scatter (half the all_reduce wire bytes)
    # and exits sharded
    fence_scatter: Dict[object, int] = field(default_factory=dict)


def find_partial_regions(jaxpr, per_axis: Sequence[Dict], axis_names,
                         axis_sizes: Sequence[int]) -> List[PartialRegion]:
    from jax.extend import core as jex_core

    regions: List[PartialRegion] = []
    n_axes = len(per_axis)
    if n_axes == 0:
        return regions

    def strat(a, idx):
        return per_axis[a].get(f"op{idx}")

    def placement_in(a, idx, pos):
        s = strat(a, idx)
        if s is None or pos >= len(s.in_placements):
            return None
        return s.in_placements[pos]

    def placement_out(a, idx, k):
        s = strat(a, idx)
        if s is None or k >= len(s.out_placements):
            return None
        return s.out_placements[k]

    def carries_p(a, idx):
        s = strat(a, idx)
        if s is None:
            return False
        return any(p is not None and p.is_partial()
                   for p in s.out_placements)

    def clean_other_axes(a, idx):
        # other-axis SHARD is fine (emitted manual with the solved specs);
        # other-axis PARTIAL would need a second fence
        for b in range(n_axes):
            if b == a:
                continue
            s = strat(b, idx)
            if s is None:
                continue
            if any(p is not None and p.is_partial()
                   for p in list(s.out_placements) + list(s.in_placements)):
                return False
        return True

    def divisible(v, dim, axis):
        shape = getattr(v.aval, "shape", ())
        return dim < len(shape) and shape[dim] % axis_sizes[axis] == 0

    eqns = jaxpr.eqns
    out_set = {v for v in jaxpr.outvars
               if not isinstance(v, jex_core.Literal)}
    for a in range(n_axes):
        idx = 0
        while idx < len(eqns):
            if not (carries_p(a, idx)
                    and eqns[idx].primitive.name in _REGION_PRIMS
                    and clean_other_axes(a, idx)):
                idx += 1
                continue
            start = idx
            while idx + 1 < len(eqns) and carries_p(a, idx + 1) \
                    and eqns[idx + 1].primitive.name in _REGION_PRIMS \
                    and clean_other_axes(a, idx + 1):
                idx += 1
            end = idx
            idx += 1
            if end == start:
                # a lone P producer gains nothing from a region; XLA's
                # immediate reduction is already optimal
                continue

            region = PartialRegion(start, end, a, str(axis_names[a]))
            produced: Set[object] = set()
            producer_out: Dict[object, Dict[int, object]] = {}
            source_placements: Dict[object, tuple] = {}
            ok = True
            for j in range(start, end + 1):
                eqn = eqns[j]
                pos = 0
                for v in eqn.invars:
                    if isinstance(v, jex_core.Literal):
                        continue
                    if v in produced:
                        # sync-free requirement on EVERY axis (including
                        # the deferred one): the consumer must take the
                        # producer's placement as-is.  On axis `a` this
                        # rejects runs where the solver priced a mid-chain
                        # psum (producer P, consumer expecting R/S) — a
                        # region would silently skip that reduction.
                        for b in range(n_axes):
                            pin = placement_in(b, j, pos)
                            pout = producer_out.get(v, {}).get(b)
                            pin_r = pin is None or pin.is_replicate()
                            pout_r = pout is None or pout.is_replicate()
                            if pin_r != pout_r or (
                                    not pin_r and (pin.kind != pout.kind
                                                   or pin.dim != pout.dim)):
                                ok = False
                    else:
                        spec = region.source_specs.setdefault(v, {})
                        # every consuming eqn must read this source with
                        # the SAME per-axis placement: the shard_map slices
                        # the source once, so S-here-R-there (a reshard
                        # edge the solver prices between consumers) cannot
                        # be honored inside one region
                        placements = tuple(placement_in(b, j, pos)
                                           for b in range(n_axes))
                        prev_pl = source_placements.get(v)
                        if prev_pl is None:
                            source_placements[v] = placements
                        elif prev_pl != placements:
                            ok = False
                        for b, p in enumerate(placements):
                            if p is None:
                                continue
                            if p.is_partial():
                                ok = False  # P flowing in from outside
                            elif p.is_shard():
                                prev = spec.get(p.dim)
                                if prev is not None \
                                        and prev != str(axis_names[b]):
                                    ok = False  # two axes on one dim
                                elif not divisible(v, p.dim, b):
                                    ok = False
                                else:
                                    spec[p.dim] = str(axis_names[b])
                        # conflicting sharding of the same source between
                        # two consuming eqns (same axis, different dim)
                        for d1, n1 in list(spec.items()):
                            for d2, n2 in spec.items():
                                if n1 == n2 and d1 != d2:
                                    ok = False
                    pos += 1
                for k, v in enumerate(eqn.outvars):
                    produced.add(v)
                    producer_out[v] = {b: placement_out(b, j, k)
                                       for b in range(n_axes)}
            if not ok:
                continue

            # fences: region-produced vars read after the region (or
            # returned); record whether they exit as P on the deferred axis
            # and their S dims on the other axes
            consumed_later: Set[object] = set()
            consumer_placements: Dict[object, List] = {}
            for j in range(end + 1, len(eqns)):
                pos = 0
                for v in eqns[j].invars:
                    if isinstance(v, jex_core.Literal):
                        continue
                    consumed_later.add(v)
                    if v in produced:
                        consumer_placements.setdefault(v, []).append(
                            placement_in(a, j, pos))
                    pos += 1
            for v in list(produced):
                if v not in consumed_later and v not in out_set:
                    continue
                pa = producer_out.get(v, {}).get(a)
                spec = {}
                for b in range(n_axes):
                    if b == a:
                        continue
                    p = producer_out.get(v, {}).get(b)
                    if p is not None and p.is_shard():
                        if not divisible(v, p.dim, b):
                            ok = False
                        spec[p.dim] = str(axis_names[b])
                region.out_specs_map[v] = spec
                if pa is not None and pa.is_partial():
                    region.fence_partial.add(v)
                    ps = consumer_placements.get(v, [])
                    if ps and v not in out_set and all(
                            p is not None and p.is_shard() for p in ps) \
                            and len({p.dim for p in ps}) == 1 \
                            and ps[0].dim not in spec \
                            and divisible(v, ps[0].dim, a):
                        # divisibility decided HERE so the byte gate below
                        # never credits a scatter emit_region would refuse
                        region.fence_scatter[v] = ps[0].dim
            if not ok:
                continue
            # with other-axis SHARD placements anywhere in the run, region
            # tensors are local blocks — global-shape-param prims break
            other_sharded = any(
                b != a and p is not None and p.is_shard()
                for v in produced
                for b, p in producer_out.get(v, {}).items()) or any(
                name != region.axis_name
                for spec in region.source_specs.values()
                for name in spec.values())
            if other_sharded and any(
                    eqns[j].primitive.name in _GLOBAL_SHAPE_PRIMS
                    for j in range(start, end + 1)):
                continue
            # the region must STRICTLY beat immediate reduction: psum-ing
            # every P-creator output (what GSPMD emits with no region)
            # vs psum/psum_scatter at the fence.  A byte-neutral region
            # (e.g. P riding an optimizer update: psum(p - lr*g) costs
            # what psum(g) did) buys nothing and hurts elsewhere — its
            # full-size partials inflate liveness and its eqns are banned
            # from remat chains.
            immediate = 0
            for j in range(start, end + 1):
                s = strat(a, j)
                if s is None:
                    continue
                creates = any(p is not None and p.is_partial()
                              for p in s.out_placements) and not any(
                    p is not None and p.is_partial()
                    for p in s.in_placements)
                if creates:
                    for k, v in enumerate(eqns[j].outvars):
                        p = (s.out_placements[k]
                             if k < len(s.out_placements) else None)
                        if p is not None and p.is_partial():
                            immediate += v.aval.size * v.aval.dtype.itemsize
            fence = sum(
                (v.aval.size * v.aval.dtype.itemsize)
                // (2 if v in region.fence_scatter else 1)
                for v in region.fence_partial)
            if fence >= immediate:
                continue
            regions.append(region)
    # keep non-overlapping regions only (one axis per run; first wins)
    taken: Set[int] = set()
    final = []
    for r in sorted(regions, key=lambda r: (r.start, -(r.end - r.start))):
        span = set(range(r.start, r.end + 1))
        if span & taken:
            continue
        taken |= span
        final.append(r)
    if final:
        logger.info("[partial] %d deferred-reduction region(s): %s",
                    len(final),
                    [(r.start, r.end, r.axis_name) for r in final])
    return final


def emit_region(region: PartialRegion, jaxpr, env, mesh):
    """Execute one region under shard_map: local chain + one psum fence.
    Reads sources from `env`, writes region outputs (post-fence, global
    semantics) back into `env`.  Every mesh axis is manual — in/out specs
    come from the solved placements, so GSPMD cannot re-layout inside."""
    import jax
    from jax import shard_map
    from jax.extend import core as jex_core
    from jax.sharding import PartitionSpec

    eqns = jaxpr.eqns[region.start:region.end + 1]
    produced = {v for eqn in eqns for v in eqn.outvars}
    sources = []
    seen = set()
    for eqn in eqns:
        for v in eqn.invars:
            if isinstance(v, jex_core.Literal) or v in produced or v in seen:
                continue
            seen.add(v)
            sources.append(v)
    # region outputs = produced vars needed outside (production order)
    consumed_later: Set[object] = set()
    for e in jaxpr.eqns[region.end + 1:]:
        for v in e.invars:
            if not isinstance(v, jex_core.Literal):
                consumed_later.add(v)
    out_set = {v for v in jaxpr.outvars
               if not isinstance(v, jex_core.Literal)}
    outs = []
    for eqn in eqns:
        for v in eqn.outvars:
            if v in consumed_later or v in out_set:
                outs.append(v)

    axis = region.axis_name
    axis_count = mesh.shape[axis]
    # P->S fence eligibility, decided once (body and out_specs must agree)
    scatter_dim = {}
    for v in outs:
        d = region.fence_scatter.get(v)
        if v in region.fence_partial and d is not None \
                and d < len(v.aval.shape) \
                and v.aval.shape[d] % axis_count == 0:
            scatter_dim[v] = d

    def body(*src_vals):
        local = dict(zip(sources, src_vals))

        def read(v):
            return v.val if isinstance(v, jex_core.Literal) else local[v]

        for eqn in eqns:
            sub, params = eqn.primitive.get_bind_params(eqn.params)
            vals = eqn.primitive.bind(*sub, *[read(v) for v in eqn.invars],
                                      **params)
            if not eqn.primitive.multiple_results:
                vals = [vals]
            for var, val in zip(eqn.outvars, vals):
                local[var] = val
        from easydist_tpu.comm import fence_psum, fence_psum_scatter

        result = []
        for v in outs:
            val = local[v]
            if v in scatter_dim:
                # P -> S fence: half the wire bytes of the all_reduce,
                # and the consumer wanted the shard anyway.  The comm
                # wrapper block-quantizes the wire when enabled and is the
                # exact jax.lax collective when not (docs/COMM.md).
                val = fence_psum_scatter(val, axis, axis_count,
                                         scatter_dim=scatter_dim[v])
            elif v in region.fence_partial:
                # THE deferred reduction
                val = fence_psum(val, axis, axis_count)
            result.append(val)
        return tuple(result)

    def spec_for(v):
        nd = len(v.aval.shape)
        entries = [None] * nd
        for d, name in region.source_specs.get(v, {}).items():
            if d < nd:
                entries[d] = name
        return PartitionSpec(*entries)

    def out_spec_for(v):
        entries = [None] * len(v.aval.shape)
        for d, name in region.out_specs_map.get(v, {}).items():
            if d < len(entries):
                entries[d] = name
        d = scatter_dim.get(v)
        if d is not None:
            entries[d] = axis
        return PartitionSpec(*entries)

    in_specs = tuple(spec_for(v) for v in sources)
    out_specs = tuple(out_spec_for(v) for v in outs)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    results = fn(*[env[v] for v in sources])
    for v, val in zip(outs, results):
        env[v] = val
