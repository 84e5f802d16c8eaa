"""`easydist_compile`: one decorator from an unmodified step function to a
sharded, jitted TPU program.

Pipeline (reference jax/api.py:173-323, redesigned for ND meshes):

  1. trace to jaxpr
  2. ShardingAnalyzer: ShardCombine discovery per unique op signature
  3. per-mesh-axis sequential solve (reference compile_auto.py:128-173):
     bridge -> coarsen (sync-free cone clusters) -> SpmdSolver ILP; shapes
     are pre-shrunk by earlier axes and already-chosen strategies excluded
  4. emit: replay the jaxpr inserting `jax.lax.with_sharding_constraint`
     with the combined ND `PartitionSpec` per tensor, then `jax.jit` with
     sharded `in_shardings` and state buffers donated

XLA's GSPMD partitioner turns the constraints into ICI/DCN collectives —
the TPU equivalent of the reference's sharding_transform + NCCL pass
(torch/passes/sharding.py).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from jax.extend import core as jex_core
from jax.sharding import NamedSharding, PartitionSpec

from easydist_tpu import config as edconfig
from easydist_tpu.autoflow import SpmdSolver
from easydist_tpu.metashard.metair import NodeStrategy, Placement
from easydist_tpu.runtime import spans
from .bridge import _eqn_flops, jaxpr_to_metagraph
from .interpreter import ShardingAnalyzer, VarNames
from .mesh import get_axis_specs, get_device_mesh, make_device_mesh

logger = logging.getLogger(__name__)


# ------------------------------------------------------------ state threading

def infer_state_io(args, out_shape) -> Dict[int, int]:
    """Pair output leaves with input leaves for train-state threading.

    Pairing is strictly positional over the *leading* outputs and inputs —
    `(new_params, new_opt, ...) = step(params, opt, ...)` — and stops at the
    first mismatch.  Positional matching (rather than searching all inputs)
    avoids spuriously pairing e.g. an inference output with a data input of
    the same shape, which would wrongly donate the data buffer.
    Returns {flat_output_index: flat_input_index}.
    """
    def leaf_sig(x):
        return (tuple(x.shape), str(x.dtype)) if hasattr(x, "shape") else None

    outs = out_shape if isinstance(out_shape, tuple) else (out_shape,)
    pairs: Dict[int, int] = {}
    in_base = out_base = 0
    for o, a in zip(outs, args):
        o_leaves, o_td = jax.tree_util.tree_flatten(o)
        a_leaves, a_td = jax.tree_util.tree_flatten(a)
        # only container subtrees qualify as state: a bare-array arg is
        # almost always data, and pairing it would donate the data buffer
        # (pass state_io explicitly for single-leaf state)
        if (not o_leaves or o_td != a_td
                or jax.tree_util.treedef_is_leaf(a_td)
                or [leaf_sig(l) for l in o_leaves] != [leaf_sig(l) for l in a_leaves]):
            # warn only when the unpaired output looks like STATE (a
            # container) — a scalar loss ending the pairing is the normal
            # (new_state, loss) shape, not a donation problem
            if pairs and o_leaves and not jax.tree_util.treedef_is_leaf(o_td):
                logger.info(
                    "state_io pairing stopped at output %d (structure "
                    "mismatch): later state will NOT be donated — pass "
                    "state_io explicitly to avoid the extra buffers",
                    out_base)
            break
        for k in range(len(o_leaves)):
            pairs[out_base + k] = in_base + k
        in_base += len(a_leaves)
        out_base += len(o_leaves)
    return pairs


# ------------------------------------------------------------------ emission

def _emit_attention_variant(eqn, strategies, axis_names, mesh, invals):
    """Lower an ed_attention_{fwd,bwd} eqn to the ring/Ulysses program when
    the solver chose a seq-shard strategy (the variant rides the strategy's
    meta, set by the preset rule).  Returns the output list, or None for
    the generic primitive bind (batch/head strategies: GSPMD partitions the
    lowered einsum ops via the constraints already applied)."""
    if eqn.primitive.name not in ("ed_attention_fwd", "ed_attention_bwd"):
        return None
    variant = axis = None
    for ax_name, s in zip(axis_names, strategies):
        meta = getattr(s, "meta", None) if s is not None else None
        if meta and meta.get("variant"):
            variant, axis = meta["variant"], ax_name
            break
    if variant is None:
        return None
    causal = eqn.params["causal"]
    scale = eqn.params["scale"]
    # re-validate the variant for the ACTUAL axis (the rule priced it at
    # the analyzer's min-axis world size): Ulysses needs head divisibility
    # on THIS axis, and the ring/Ulysses crossover moves with axis size
    n_axis = int(mesh.shape[axis])
    heads = eqn.invars[0].aval.shape[1]
    if variant == "ulysses" and heads % n_axis != 0:
        variant = "ring"
    if variant == "ulysses":
        from easydist_tpu.parallel.ulysses import ulysses_attention as attn
    else:
        from easydist_tpu.parallel.ring_attention import ring_attention as attn

    if eqn.primitive.name == "ed_attention_fwd":
        q, k, v = invals
        return [attn(q, k, v, mesh, axis=axis, causal=causal, scale=scale)]
    q, k, v, dout = invals
    # flash-style recompute backward: vjp of the SAME sequence-parallel
    # program — no [t,t] residual, collectives exactly as priced
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attn(q_, k_, v_, mesh, axis=axis, causal=causal,
                                scale=scale), q, k, v)
    return list(vjp(dout))

def _combined_spec(placements: List[Optional[Placement]],
                   axis_names: Sequence[str], ndim: int) -> PartitionSpec:
    """Merge per-axis placements into one PartitionSpec, spelled as JAX
    spells the sharding of an array a jit hands back (no trailing `None`:
    `P('dp')` for `P('dp', None)`), so that a state leaf's `in_shardings`
    entry EQUALS the sharding it returns in: equivalent alone is another
    key to the jit's cache."""
    entries: List[object] = [None] * ndim
    for axis_name, p in zip(axis_names, placements):
        if p is None or not p.is_shard() or p.dim >= ndim:
            continue
        cur = entries[p.dim]
        if cur is None:
            entries[p.dim] = axis_name
        elif isinstance(cur, tuple):
            entries[p.dim] = cur + (axis_name,)
        else:
            entries[p.dim] = (cur, axis_name)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def emit_sharded_fn(closed_jaxpr, names: VarNames,
                    per_axis: List[Dict[str, NodeStrategy]],
                    axis_names: Sequence[str], mesh, remat_plan=None,
                    partial_regions=None):
    """Build fn(*flat_args) -> flat_outs replaying the jaxpr with sharding
    constraints on every strategy-carrying equation input
    (reference add_sharding_jaxpr, jax/api.py:114-170).

    `remat_plan` (schedule/remat.py) redirects planned far consumers to
    recomputed values: before such a consumer, its chain equations are
    re-executed into a shared overlay whose sources pass through
    `optimization_barrier` (so XLA CSE cannot fold the duplicates back),
    and overlay entries are dropped after their last planned reader."""
    jaxpr = closed_jaxpr.jaxpr
    consts = closed_jaxpr.consts
    recompute = remat_plan.recompute if remat_plan else {}
    overlay_last_use = remat_plan.overlay_last_use if remat_plan else {}
    region_at = {}  # start eqn idx -> PartialRegion
    in_region = set()
    for r in (partial_regions or []):
        region_at[r.start] = r
        in_region.update(range(r.start, r.end + 1))
    # eqn idx -> mesh axes over which that Pallas kernel's rows are sharded
    pallas_rows = {}
    if mesh.size > 1:
        for idx, eqn in enumerate(jaxpr.eqns):
            if eqn.primitive.name == "pallas_call" and idx not in in_region:
                pallas_rows[idx] = _pallas_row_axes(
                    eqn, [chosen.get(f"op{idx}") for chosen in per_axis],
                    axis_names, mesh)

    def sharded_fn(*flat_args):
        from .partial_regions import emit_region

        env = {}
        overlay = {}  # var -> recomputed value (shared across consumers)
        overlay_evict = {}  # eqn idx at which to drop -> [vars]

        def read(v):
            return v.val if isinstance(v, jex_core.Literal) else env[v]

        for var, val in zip(jaxpr.invars, flat_args):
            env[var] = val
        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val

        for idx, eqn in enumerate(jaxpr.eqns):
            if idx in region_at:
                # deferred-reduction region: local chain under shard_map
                # with one psum fence (partial_regions.py)
                emit_region(region_at[idx], jaxpr, env, mesh)
            if idx in in_region:
                continue
            chain = recompute.get(idx)
            if chain:
                for e in chain:
                    ceqn = jaxpr.eqns[e]
                    if all(u in overlay for u in ceqn.outvars):
                        continue
                    csub, cparams = ceqn.primitive.get_bind_params(
                        ceqn.params)
                    cin = []
                    for u in ceqn.invars:
                        if isinstance(u, jex_core.Literal):
                            cin.append(u.val)
                        elif u in overlay:
                            cin.append(overlay[u])
                        else:
                            val = env[u]
                            if hasattr(val, "ndim"):
                                val = jax.lax.optimization_barrier(val)
                            cin.append(val)
                    cout = ceqn.primitive.bind(*csub, *cin, **cparams)
                    if not ceqn.primitive.multiple_results:
                        cout = [cout]
                    last = overlay_last_use.get(e, idx)
                    for u, val in zip(ceqn.outvars, cout):
                        overlay[u] = val
                        overlay_evict.setdefault(last, []).append(u)

            node_name = f"op{idx}"
            strategies = [chosen.get(node_name) for chosen in per_axis]
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            if chain:
                invals = [v.val if isinstance(v, jex_core.Literal)
                          else (overlay[v] if v in overlay else env[v])
                          for v in eqn.invars]
            else:
                invals = [read(v) for v in eqn.invars]

            var_pos = 0
            for i, v in enumerate(eqn.invars):
                if isinstance(v, jex_core.Literal):
                    continue
                placements = [s.in_placements[var_pos]
                              if s is not None and var_pos < len(s.in_placements)
                              else None
                              for s in strategies]
                val = invals[i]
                if hasattr(val, "ndim") and val.ndim > 0 and \
                        any(p is not None and p.is_shard() for p in placements):
                    spec = _combined_spec(placements, axis_names, val.ndim)
                    invals[i] = jax.lax.with_sharding_constraint(
                        val, NamedSharding(mesh, spec))
                var_pos += 1

            out = _emit_attention_variant(eqn, strategies, axis_names, mesh,
                                          invals)
            if out is None and idx in pallas_rows:
                out = _bind_pallas_rows(eqn, subfuns, bind_params, invals,
                                        mesh, pallas_rows[idx])
            if out is None:
                out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
                if not eqn.primitive.multiple_results:
                    out = [out]
            for var, val in zip(eqn.outvars, out):
                env[var] = val
            for u in overlay_evict.pop(idx, ()):
                overlay.pop(u, None)

        return [read(v) for v in jaxpr.outvars]

    return sharded_fn


def _pallas_row_axes(eqn, strategies, axis_names, mesh) -> Tuple[str, ...]:
    """The mesh axes, in `axis_names` order, on which the solved
    `strategies` (one per axis) shard the rows of a row-parallel
    `pallas_call` (presets.pallas_row_extent); `()` for a call that is not
    row-parallel, or whose plan left it whole.  Counts the call as
    `pallas_calls{kernel=<name>, row_shards=<k>}`, once per emission."""
    from .presets import pallas_row_extent

    n = pallas_row_extent(eqn)
    axes, k = [], 1
    for ax_name, s in zip(axis_names, strategies if n is not None else ()):
        placements = [] if s is None else \
            list(s.in_placements) + list(s.out_placements)
        size = int(mesh.shape[ax_name])
        if placements and all(p is not None and p.is_shard() and p.dim == 0
                              for p in placements) \
                and n % (k * size) == 0:
            axes.append(ax_name)
            k *= size
    spans.count("pallas_calls", kernel=eqn.params.get("name") or "pallas_call",
                row_shards=k)
    return tuple(axes)


def _bind_pallas_rows(eqn, subfuns, bind_params, invals, mesh, axes):
    """Bind a Pallas kernel under a `shard_map` over the whole mesh, its
    rows (dimension 0 of every operand and result) split over `axes`.

    The `shard_map` is there for every kernel: inside a jit that GSPMD
    partitions, the TPU lowering refuses a bare Mosaic custom call
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map").  With `axes == ()` every device runs the call
    whole: GSPMD gathers what the neighbours hold sharded and each device
    computes the full result.  Otherwise a device runs the kernel on its
    `n / k` rows only: the traced equation bakes the global row count into
    the grid, the block mappings' array avals and `out_avals`, so the call
    is re-bound with those three at the local extent (what Pallas' own
    batching rule does in the other direction); kernel body, blocks and
    index maps are untouched."""
    import dataclasses

    k = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))
    if k > 1:
        def local(aval):
            return aval.update(shape=(aval.shape[0] // k,) + aval.shape[1:])

        gm = bind_params["grid_mapping"]
        cost = bind_params.get("cost_estimate")
        bind_params = dict(
            bind_params,
            grid_mapping=dataclasses.replace(
                gm, grid=(gm.grid[0] // k,) + tuple(gm.grid[1:]),
                block_mappings=tuple(
                    dataclasses.replace(bm, array_aval=local(bm.array_aval))
                    for bm in gm.block_mappings)),
            out_avals=tuple(local(a) for a in bind_params["out_avals"]),
            cost_estimate=cost and dataclasses.replace(
                cost, flops=cost.flops // k,
                transcendentals=cost.transcendentals // k,
                bytes_accessed=cost.bytes_accessed // k))
    spec = PartitionSpec(axes) if axes else PartitionSpec()

    def kernel(*xs):
        return eqn.primitive.bind(*subfuns, *xs, **bind_params)

    return jax.shard_map(
        kernel, mesh=mesh, in_specs=tuple(spec for _ in invals),
        out_specs=[spec for _ in eqn.outvars], check_vma=False)(*invals)


def _compile_cache_key(closed_jaxpr, axis_specs) -> str:
    """Stable key over the traced program + mesh layout (reference compile
    cache, torch/compile_auto.py:97-106)."""
    import hashlib

    from .interpreter import VarNames, eqn_signature, hash_array_bytes

    h = hashlib.sha256()
    # schema + cost-model salt: cached strategies are only valid for the
    # solver/cost-model that produced them; a version bump or a tuned
    # bandwidth/latency knob must miss, not silently serve stale plans
    h.update(("v9|" + "|".join(
        f"{k}={getattr(edconfig, k)}" for k in
        ("ici_bandwidth", "dcn_bandwidth", "ici_latency", "dcn_latency",
         "hbm_bandwidth", "all_to_all_punish_factor",
         "solver_cluster_dedup", "per_device_memory_cap",
         "enable_partial_pools", "enable_auto_remat",
         "coarsen_level", "enable_graph_coarsen", "predict_comm_overlap",
         "comm_overlap_ratio", "allow_repeated_axis_strategy",
         "solver_backend", "liveness_only_input", "peak_flops",
         # comm compression changes reduction-edge prices (cost_model
         # min(exact, compressed)), so cached strategies are mode-specific
         "comm_quant_dtype", "comm_quant_block",
         "comm_quant_min_numel",
         # overlap knobs: the runtime flush/accum shape and the solver's
         # calibrated discount ratio both change the plan's economics
         "comm_overlap", "grad_accum_microbatches",
         "comm_overlap_ratio_source",
         "comm_overlap_ratio_measured",
         # the NaN-step guard rewrites the traced step (lax.cond
         # skip-and-hold around the update), so guarded and unguarded
         # builds must not share cached strategies
         "resilience_step_guard",
         # decode-attention backend/block choice changes the decode-step
         # program (pallas_call kernel vs masked dot_general) at identical
         # input shapes, so serve decode builds must not share strategies
         # across backends
         "decode_attention_backend", "decode_block_k",
         # chunked-prefill backend: same reasoning as the decode backend —
         # different emitted programs at identical shapes
         "prefill_attention_backend"))).encode())
    names = VarNames()
    for v in closed_jaxpr.jaxpr.invars:
        names.name(v)
    for eqn in closed_jaxpr.jaxpr.eqns:
        h.update(eqn_signature(eqn, None).encode())
        # dataflow wiring: two programs with the same op/shape sequence but
        # different operand routing must not collide
        wiring = ",".join(
            "lit" if isinstance(v, jex_core.Literal) else names.name(v)
            for v in eqn.invars)
        wiring += "->" + ",".join(names.name(v) for v in eqn.outvars)
        h.update(wiring.encode())
    for v in closed_jaxpr.jaxpr.invars:
        h.update(f"{v.aval.shape}{v.aval.dtype}".encode())
    for v, c in zip(closed_jaxpr.jaxpr.constvars, closed_jaxpr.consts):
        h.update(f"c{v.aval.shape}{v.aval.dtype}".encode())
        try:
            h.update(hash_array_bytes(np.asarray(c)).encode())
        except Exception:
            pass
    for s in axis_specs:
        h.update(f"{s.name}:{s.size}:{s.kind}".encode())
    return h.hexdigest()[:32]


def _strategy_cache_load(key: str):
    import os
    import pickle

    path = os.path.join(edconfig.compile_cache_dir, f"strategies_{key}.pkl")
    if os.path.exists(path):
        try:
            with open(path, "rb") as f:
                return pickle.load(f)
        except Exception:
            logger.warning("compile cache read failed for %s", path)
    return None


def _strategy_cache_store(key: str, per_axis) -> None:
    import os
    import pickle
    import tempfile

    os.makedirs(edconfig.compile_cache_dir, exist_ok=True)
    path = os.path.join(edconfig.compile_cache_dir, f"strategies_{key}.pkl")
    # write-to-temp + atomic rename: concurrent serve-bucket compiles may
    # read this file mid-write; os.replace guarantees a reader sees either
    # the old pickle or the complete new one, never a torn file
    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(dir=edconfig.compile_cache_dir,
                                   prefix=f"strategies_{key}.",
                                   suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            pickle.dump(per_axis, f)
        os.replace(tmp, path)
        tmp = None
    except Exception:
        logger.warning("compile cache write failed for %s", path)
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _dump_strategies(graph, per_axis, axis_names):
    """Write MetaIR + solved strategies into edconfig.dump_dir (reference
    DUMP_STRATEGY/DUMP_CLUSTER flags, config.py and metair.py:933-939)."""
    import os

    os.makedirs(edconfig.dump_dir, exist_ok=True)
    if graph is not None and edconfig.dump_cluster:
        with open(os.path.join(edconfig.dump_dir, "metair.txt"), "w") as f:
            f.write(repr(graph))
        with open(os.path.join(edconfig.dump_dir, "clusters.txt"), "w") as f:
            for c in graph.clusters:
                node_names = [n.name for n in c.nodes.values()]
                f.write(f"cluster {c.cid}: {len(c.strategies)} strategies; "
                        f"nodes {node_names}\n")
    if edconfig.dump_strategy:
        with open(os.path.join(edconfig.dump_dir, "strategies.txt"),
                  "w") as f:
            names = sorted({n for chosen in per_axis for n in chosen})
            for name in names:
                parts = [f"{ax}: {chosen.get(name)}"
                         for ax, chosen in zip(axis_names, per_axis)]
                f.write(f"{name}\n  " + "\n  ".join(parts) + "\n")
    if graph is not None and edconfig.dump_graphviz:
        from easydist_tpu.utils.dump import metagraph_to_dot

        with open(os.path.join(edconfig.dump_dir, "metair.dot"), "w") as f:
            f.write(metagraph_to_dot(graph, per_axis, axis_names))
    logger.info("strategies dumped to %s", edconfig.dump_dir)


# ----------------------------------------------------------------- compiler

class SignatureMismatch(Exception):
    """Raised at trace time when a compiled result sees new shapes/tree."""


class CompileResult:

    def __init__(self, jitted, tree_jitted, in_shardings, strategies, graph,
                 mesh, in_tree, out_tree, n_flat_in, in_avals=None):
        self.jitted = jitted  # flat calling convention (driver/debug use)
        self.tree_jitted = tree_jitted  # pytree convention (steady state)
        self.in_shardings = in_shardings
        self.strategies = strategies  # per-axis {node_name: NodeStrategy}
        self.graph = graph
        self.mesh = mesh
        self.in_tree = in_tree
        self.out_tree = out_tree
        self.n_flat_in = n_flat_in
        self.in_avals = in_avals or []
        self._executable = None
        # layer-1 analyzer findings collected during solve_axes, and the
        # per-axis solver-objective audit records (set by _finish_compile)
        self.analysis_findings: List[object] = []
        self.solver_audits: List[Dict[str, float]] = []
        # state threading declaration {flat out idx -> flat in idx} and
        # the donated flat input indices (set by _finish_compile) — the
        # layer-11 donation/aliasing audit surface
        self.state_pairs: Dict[int, int] = {}
        self.donated_invars: tuple = ()
        self.donated_args: tuple = ()
        # set by _finish_compile for the memory analyzer (layer 3)
        self.closed_jaxpr = None
        self.remat_plan = None
        self.memory_plan = None  # cached MemoryPlan from the last analyze()
        self.predicted_peak_bytes: Optional[int] = None
        # set-up seconds {"trace", "discovery", "solve"} (set by compile_step)
        self.phase_seconds: Dict[str, float] = {}
        self.name = "step"  # the wrapped function's (set by _finish_compile)

    def dispatch(self, args, kwargs):
        """`tree_jitted(*args, **kwargs)` inside an `easydist.step.call`
        span, with no fence.
        A dispatch during which the jit's executable cache grew
        (`_cache_size()`) made XLA compile the program or load it from the
        persistent cache — for new shapes, or for the same shapes under
        other input shardings, which `CompiledFunction`'s own cache cannot
        see: it is counted as `xla_compiles{fn=<name>}` and its interval
        recorded as `easydist.step.compile`."""
        jitted = self.tree_jitted
        before = jitted._cache_size()
        with spans.span("easydist.step.call", fn=self.name) as sp:
            out = jitted(*args, **kwargs)
            if jitted._cache_size() > before:
                spans.count("xla_compiles", fn=self.name)
                spans.record_span("easydist.step.compile", sp.t0_ns,
                                  time.perf_counter_ns(), parent_id=sp.id,
                                  fn=self.name)
        return out

    def analyze(self, include_program: bool = True,
                include_memory: bool = True):
        """Static analysis of this compiled result (easydist_tpu.analyze):
        the layer-1 strategy findings recorded at solve time, plus, when
        `include_program`, a layer-2 lint of the emitted program (the flat
        sharded function re-traced on abstract values — partial-region
        fences and comm collectives included, no device execution), plus,
        when `include_memory`, the layer-3 memory verifier (graph memory
        plan audit, HBM budget gate, remat-rewrite audit), plus the
        layer-11 donation/aliasing sanitizer (ALIAS001/002 over the
        traced program's donating dispatches, ALIAS002/003 over the
        declared state pairs — the silent-copy and double-claim cases).
        Returns an AnalysisReport; raising is the CALLER's decision
        (CompiledFunction.analyze gates it on `edconfig.analyze_raise`)."""
        from easydist_tpu.analyze import (AnalysisReport,
                                          audit_donation_pairs,
                                          audit_jaxpr_donation,
                                          lint_jaxpr, make_finding)

        report = AnalysisReport(self.analysis_findings)
        traced = None
        if include_program:
            try:
                traced = jax.make_jaxpr(self.jitted)(*self.in_avals)
                axis_sizes = {str(k): int(v)
                              for k, v in self.mesh.shape.items()}
                report.extend(lint_jaxpr(traced.jaxpr, axis_sizes))
            except Exception as e:  # lint must never be the thing that fails
                report.add(make_finding(
                    "COLL000", "emitted-program",
                    f"program lint skipped: retrace failed "
                    f"({type(e).__name__}: {e})"))
            if traced is not None:
                # honorability (ALIAS003) is audited via the state pairs
                # below, where the out<->in context is attached
                report.extend(audit_jaxpr_donation(
                    traced.jaxpr, node="emitted-program",
                    check_unhonored=False))
            report.extend(audit_donation_pairs(self, node="state-io"))
        if include_memory:
            report.extend(self._memory_findings(traced))
        return report

    def _memory_findings(self, traced=None) -> List[object]:
        """Layer 3a: plan this result's graph memory and run the MEM rule
        family over it (easydist_tpu.analyze.memory_rules).  The plan is
        built from the LAST solved axis's (graph, chosen) pair — that
        graph's shapes are already pre-shrunk by every earlier axis, so
        dividing by its own placements yields true per-device bytes."""
        from easydist_tpu.analyze import (audit_remat_plan,
                                          check_hbm_budget, make_finding,
                                          resolve_hbm_budget,
                                          verify_memory_plan)

        if self.graph is None:
            return [make_finding(
                "MEM000", "memory-plan",
                "no MetaGraph on this result (compile-cache hit or "
                "single-device mesh): the memory layer ran — if ever — "
                "on the solving compile")]
        from easydist_tpu.schedule import plan_graph_memory

        findings: List[object] = []
        axis = getattr(self.graph, "solved_axis", None)
        chosen = getattr(self.graph, "solved_chosen", None)
        per_axis = [chosen] if chosen is not None else []
        axis_sizes = [axis.size] if axis is not None else []
        try:
            plan = plan_graph_memory(self.graph, per_axis, axis_sizes)
        except Exception as e:  # analysis must never be the thing that fails
            return [make_finding(
                "MEM000", "memory-plan",
                f"memory planning failed ({type(e).__name__}: {e}); "
                f"MEM rules skipped")]
        self.memory_plan = plan
        self.predicted_peak_bytes = (
            int(self.remat_plan.predicted_peak) if self.remat_plan
            else int(plan.peak_bytes))
        findings.extend(verify_memory_plan(self.graph, plan, per_axis,
                                           axis_sizes))
        budget = resolve_hbm_budget(self.mesh)
        findings.extend(check_hbm_budget(self.graph, plan, budget,
                                         remat_plan=self.remat_plan))
        if self.remat_plan is not None and self.closed_jaxpr is not None:
            findings.extend(audit_remat_plan(self.closed_jaxpr,
                                             self.remat_plan,
                                             traced=traced))
        return findings

    def executable(self):
        """Lower + compile the flat function (cached) — the object carrying
        XLA cost_analysis()/memory_analysis()."""
        if self._executable is None:
            self._executable = self.jitted.lower(*self.in_avals).compile()
            if edconfig.dump_dir and edconfig.dump_hlo:
                import os

                from easydist_tpu.utils.dump import dump_hlo

                os.makedirs(edconfig.dump_dir, exist_ok=True)
                dump_hlo(self._executable,
                         os.path.join(edconfig.dump_dir, "optimized.hlo"))
        return self._executable

    def materialize(self, init_fn, *init_args, arg_offset: int = 0):
        """Deferred sharded materialization (reference init_helper.py:31-166
        materialization strategies; the TPU-native form): run `init_fn`
        under jit with this step's solved input shardings as out_shardings,
        so state is BORN sharded on device — no replicated host copy ever
        exists.  `arg_offset` is the flat input position where init_fn's
        output leaves land in the step's signature (0 = leading state).
        """
        out_shape = jax.eval_shape(init_fn, *init_args)
        leaves = jax.tree_util.tree_leaves(out_shape)
        n = len(leaves)
        expect = self.in_avals[arg_offset:arg_offset + n]
        got = [(tuple(l.shape), np.dtype(l.dtype).name) for l in leaves]
        want = [(tuple(a.shape), np.dtype(a.dtype).name) for a in expect]
        if got != want:
            raise ValueError(
                f"init_fn output does not match the step's inputs at "
                f"arg_offset={arg_offset}: init produces {got[:4]}..., "
                f"step expects {want[:4]}... — wrong offset or init_fn?")
        shardings = self.in_shardings[arg_offset:arg_offset + n]
        tree = jax.tree_util.tree_structure(out_shape)
        out_sh = jax.tree_util.tree_unflatten(tree, shardings)
        return jax.jit(init_fn, out_shardings=out_sh)(*init_args)


def _axis_solve_order(axis_specs):
    """Solve DCN axes first (coarser, costlier), then ICI by size descending
    — the first solve picks the dominant (usually batch) dim."""
    return sorted(range(len(axis_specs)),
                  key=lambda i: (axis_specs[i].kind != "dcn",
                                 -axis_specs[i].size))


def _apply_user_pins(graph, closed_jaxpr, axis):
    """Restrict each `sharding_constraint` node's strategy pool to the
    user's pinned placement on this axis (fix_sharding / user
    with_sharding_constraint).  Without this the solver treats the pin as a
    freely-shardable identity and can choose a conflicting layout that the
    replayed constraint then fights at emission — measured as 2 MiB of
    involuntary-rematerialization all-gathers on a (dp, tp) mesh where the
    solver picked dp-column weight sharding against a tp-row pin."""
    node_by_name = {n.name: n for n in graph.ops}
    for idx, eqn in enumerate(closed_jaxpr.jaxpr.eqns):
        if eqn.primitive.name != "sharding_constraint":
            continue
        spec = getattr(eqn.params.get("sharding"), "spec", None)
        node = node_by_name.get(f"op{idx}")
        if spec is None or node is None or not node.outvars:
            continue
        dim = None
        for d, entry in enumerate(spec):
            entries = entry if isinstance(entry, tuple) else (entry,)
            if axis.name in [e for e in entries if e is not None]:
                dim = d
        if dim is None:
            node.pinned = node.replicate_strategy()
            continue
        shape = node.outvars[0].shape
        if dim >= len(shape) or shape[dim] % axis.size != 0:
            continue  # pin not realizable on this axis; leave solver free
        node.pinned = NodeStrategy([Placement.shard(dim)],
                                   [Placement.shard(dim)])


def solve_axes(closed_jaxpr, axis_specs, world, rules, shape_info, names,
               state_io_names=None, findings=None, audits=None):
    """The per-axis sequential solve (reference compile_auto.py:128-173):
    strategies chosen on earlier axes are excluded from later pools and
    sharded shapes are pre-shrunk, so no dim is double-sharded past
    divisibility.  Shared by compile_step and scoped_region.

    When `findings` is a list and `edconfig.enable_analyze` is on, the
    layer-1 strategy verifier (easydist_tpu.analyze) runs on each axis's
    (graph, chosen) pair right after its solve — the only moment that
    exact pair exists — appending Finding objects; `audits` collects the
    per-axis solver-objective audit records.

    Returns (per_axis strategies list, last metagraph or None)."""
    order = _axis_solve_order(axis_specs)
    per_axis: List[Optional[Dict[str, NodeStrategy]]] = \
        [None] * len(axis_specs)
    var_shapes: Dict[str, Tuple[int, ...]] = {}
    prev_chosen: List[Dict[str, NodeStrategy]] = []
    graph = None
    for axis_idx in order:
        axis = axis_specs[axis_idx]
        if axis.size == 1:
            # single-device axis: every placement is equivalent, skip solving
            per_axis[axis_idx] = {}
            prev_chosen.append({})
            continue
        t0 = time.perf_counter()
        graph = jaxpr_to_metagraph(closed_jaxpr, rules, shape_info,
                                   world_size=world, names=names,
                                   var_shapes=dict(var_shapes),
                                   state_io=state_io_names or {})
        if edconfig.enable_partial_pools:
            # PARTIAL rides linear op chains in the GLOBAL pools: the ILP
            # can then pay a cheaper reduce_scatter fence (P->S) or a
            # single deferred all_reduce instead of one per producer
            # (reference carries partials globally, metair.py:376-481)
            from .interpreter import _inject_partial_propagation

            _inject_partial_propagation(graph, axis.size)
        _apply_user_pins(graph, closed_jaxpr, axis)

        def exclude_map(node, _prev=tuple(prev_chosen)):
            # a row-parallel kernel's one shard group is its rows
            # (presets.py): a later axis can divide the kernel's work only
            # by splitting them again, and the pool checks divisibility on
            # the shape the earlier axes already shrank
            if edconfig.allow_repeated_axis_strategy \
                    or node.shard_where_valid:
                return []
            out = []
            for chosen in _prev:
                s = chosen.get(node.name)
                if s is not None and not s.is_all_replicate():
                    out.append(s)
            return out

        coarsen_level = (edconfig.coarsen_level
                         if edconfig.enable_graph_coarsen else 0)
        graph.coarsen(axis.size, level=coarsen_level,
                      exclude_map=exclude_map)
        reach = None
        if edconfig.predict_comm_overlap:
            from easydist_tpu.autoflow.reachability import ReachabilityMap

            reach = ReachabilityMap(graph)
        solver = SpmdSolver(graph, axis, reachability=reach)
        chosen = solver.solve()
        # tag the graph with ITS OWN solve pair: later-axis graphs carry
        # shapes pre-shrunk by earlier axes, so the memory analyzer must
        # divide by exactly this one axis's placements (analyze layer 3)
        graph.solved_axis = axis
        graph.solved_chosen = chosen
        if findings is not None and edconfig.enable_analyze:
            from easydist_tpu.analyze import (audit_solver_objective,
                                              verify_axis)

            findings.extend(verify_axis(graph, chosen, axis))
            audit_finding, audit_record = audit_solver_objective(solver,
                                                                 chosen)
            if audit_finding is not None:
                findings.append(audit_finding)
            if audits is not None and "reported" in audit_record:
                audits.append(audit_record)
            if edconfig.predict_comm_overlap:
                from easydist_tpu.analyze import make_finding
                from easydist_tpu.autoflow.cost_model import (
                    overlap_discount_ratio, overlap_ratio_is_measured)

                if (not overlap_ratio_is_measured()
                        and not any(f.rule_id == "OVL003"
                                    for f in findings)):
                    ratio = overlap_discount_ratio()
                    findings.append(make_finding(
                        "OVL003", f"axis:{axis.name}",
                        "predict_comm_overlap is on but no measured "
                        "overlap fraction exists for this backend "
                        f"(source={edconfig.comm_overlap_ratio_source!r} "
                        f"resolves to ratio={ratio:g}"
                        + (", the flat config guess that fails the "
                           "byte-quality gate" if ratio > 0
                           else ", so the discount is inert")
                        + "); run runtime.calibrate.calibrate_overlap() "
                        "on the target to ground the discount"))
        per_axis[axis_idx] = chosen
        prev_chosen.append(chosen)
        logger.info("[solve] axis %s (%d devices) in %.2fs", axis.name,
                    axis.size, time.perf_counter() - t0)

        # shrink shapes sharded on this axis for subsequent solves
        for node in graph.all_nodes():
            strat = chosen.get(node.name)
            if strat is None:
                continue
            for v, p in zip(node.outvars, strat.out_placements):
                if v is not None and p is not None and p.is_shard():
                    shape = list(var_shapes.get(v.name, v.shape))
                    if shape[p.dim] % axis.size == 0:
                        shape[p.dim] //= axis.size
                        var_shapes[v.name] = tuple(shape)
    return per_axis, graph


def compile_step(func, args, kwargs, mesh=None, state_io="auto",
                 donate_state: Optional[bool] = None) -> CompileResult:
    if mesh is None:
        mesh = get_device_mesh()
    if mesh is None:
        mesh = make_device_mesh()
    axis_specs = get_axis_specs(mesh)

    from .inline import inline_calls
    from .scope import _compile_mesh_ctx

    fn_name = getattr(func, "__name__", "step")
    with spans.span("easydist.compile.trace", fn=fn_name) as sp:
        with _compile_mesh_ctx(mesh):
            closed_jaxpr, out_shape = jax.make_jaxpr(
                func, return_shape=True)(*args, **kwargs)
        closed_jaxpr = inline_calls(closed_jaxpr)
    jaxpr = closed_jaxpr.jaxpr
    # set-up seconds by phase, left on the CompileResult and taken from the
    # `easydist.compile.*` spans (XLA's own compile happens later, at the
    # jit's first call: `easydist.step.compile`)
    phase_seconds = {"trace": sp.seconds, "discovery": 0.0, "solve": 0.0}
    logger.info("[trace] %d eqns in %.2fs", len(jaxpr.eqns),
                phase_seconds["trace"])

    # measured hardware constants beat datasheet defaults when available
    # (EASYDIST_AUTO_CALIBRATION=0 opts out; run runtime.calibrate() once
    # on the target to record them)
    if edconfig.auto_calibration:
        from easydist_tpu.runtime.calibrate import apply_calibration

        apply_calibration()

    # ---- persistent compile cache: a hit skips discovery AND solving
    cache_key = cached = None
    if edconfig.enable_compile_cache:
        cache_key = _compile_cache_key(closed_jaxpr, axis_specs)
        cached = _strategy_cache_load(cache_key)
        if cached is not None:
            logger.info("[compile cache] hit %s", cache_key)

    # ---- state threading: map output var names to input var names
    flat_args, in_tree = jax.tree_util.tree_flatten((args, kwargs))
    state_pairs: Dict[int, int] = {}
    if state_io == "auto":
        state_pairs = infer_state_io(args, out_shape)
    elif isinstance(state_io, dict):
        state_pairs = state_io
    out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)

    def finish(names, per_axis, graph, **findings):
        with spans.span("easydist.compile.emit", fn=fn_name):
            result = _finish_compile(
                closed_jaxpr, jaxpr, names, per_axis, graph, axis_specs,
                mesh, args, kwargs, flat_args, in_tree, out_tree,
                state_pairs, donate_state, name=fn_name, **findings)
        result.phase_seconds = phase_seconds
        return result

    if cached is not None:
        # names must match the analyzer's assignment order exactly
        names = VarNames()
        for var in jaxpr.invars + jaxpr.constvars:
            names.name(var)
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                names.name(v)
        per_axis = list(cached)
        graph = None
        cache_findings = []
        if edconfig.enable_analyze:
            from easydist_tpu.analyze import make_finding

            cache_findings.append(make_finding(
                "STRAT000", "compile",
                f"compile-cache hit {cache_key}: layer-1 strategy findings "
                f"were produced by the solving compile; only the emitted-"
                f"program lint runs here"))
        return finish(names, per_axis, graph,
                      analysis_findings=cache_findings)

    # gate shardability on the SMALLEST axis: per-axis pools re-check
    # divisibility, so a dim only shardable on a small axis must not be
    # filtered out by a larger one
    world = min((s.size for s in axis_specs), default=1)
    analyzer = ShardingAnalyzer(closed_jaxpr, world_size=world)
    with spans.span("easydist.compile.discovery", fn=fn_name) as sp:
        rules, shape_info = analyzer.run()  # logs its own one-line summary
    phase_seconds["discovery"] = sp.seconds
    names = analyzer.names
    if edconfig.use_op_cost_db:
        from easydist_tpu.runtime.perfdb import record_discovery

        record_discovery(analyzer.counters.snapshot())

    state_io_names = {}
    for out_idx, in_idx in state_pairs.items():
        if out_idx < len(jaxpr.outvars) and in_idx < len(jaxpr.invars):
            ov = jaxpr.outvars[out_idx]
            if not isinstance(ov, jex_core.Literal):
                state_io_names[names.name(ov)] = names.name(jaxpr.invars[in_idx])

    # ---- per-axis sequential solve (layer-1 analyzer findings collected
    # per axis, on exactly the graph each solve saw)
    # discovery findings (DISC001/DISC002) ride the same report as the
    # solver-layer findings
    analysis_findings: List[object] = list(analyzer.findings)
    solver_audits: List[Dict[str, float]] = []
    with spans.span("easydist.compile.solve", fn=fn_name) as sp:
        per_axis, graph = solve_axes(closed_jaxpr, axis_specs, world, rules,
                                     shape_info, names, state_io_names,
                                     findings=analysis_findings,
                                     audits=solver_audits)
    phase_seconds["solve"] = sp.seconds

    if edconfig.dump_dir:
        _dump_strategies(graph, [c if c is not None else {} for c in per_axis],
                         [s.name for s in axis_specs])
    if cache_key is not None:
        _strategy_cache_store(cache_key,
                              [c if c is not None else {} for c in per_axis])

    return finish(names, per_axis, graph,
                  analysis_findings=analysis_findings,
                  solver_audits=solver_audits)


def _replicated_flops_fraction(jaxpr, per_axis_final, axis_specs) -> float:
    """Fraction of modeled FLOPs in eqns whose chosen strategy is
    all-replicate on every multi-device mesh axis (VERDICT r3 weak #3: the
    silent-zero-parallelism signal)."""
    live_axes = [i for i, s in enumerate(axis_specs) if s.size > 1]
    if not live_axes:
        return 0.0
    total = replicated = 0.0
    for idx, eqn in enumerate(jaxpr.eqns):
        f = _eqn_flops(eqn)
        if f <= 0:
            continue
        total += f
        sharded = False
        for i in live_axes:
            s = per_axis_final[i].get(f"op{idx}")
            if s is not None and any(
                    p is not None and not p.is_replicate()
                    for p in list(s.out_placements) + list(s.in_placements)):
                sharded = True
                break
        if not sharded:
            replicated += f
    return replicated / total if total > 0 else 0.0


# The liveness model is a python-order UPPER bound on XLA's scheduled peak:
# it may exceed what XLA achieves freely, but must never UNDERestimate the
# scheduler's temp bytes by more than this fraction — shared by the remat
# decision here and the bench --analyze planner/XLA drift assertion.
_PEAK_MODEL_UNDER_TOL = 0.05


def peak_model_drift_ok(predicted_bytes, xla_temp_bytes) -> bool:
    """True when the planner's predicted peak respects the upper-bound
    contract vs XLA's own memory_analysis temp bytes.  CPU backends report
    temp_size 0 (same skip as the remat probes above): vacuously OK."""
    if predicted_bytes is None or not xla_temp_bytes or xla_temp_bytes <= 0:
        return True
    return predicted_bytes >= (1.0 - _PEAK_MODEL_UNDER_TOL) * xla_temp_bytes


def _xla_peak_bytes(closed_jaxpr, names, per_axis_final, axis_specs, mesh,
                    remat_plan=None, partial_regions=None):
    """Per-device peak of the sharded program as XLA schedules it: temp +
    argument bytes from memory_analysis (one extra XLA compile; no device
    execution).  Probes the same emission (regions included) that ships."""
    try:
        fn = emit_sharded_fn(closed_jaxpr, names, per_axis_final,
                             [s.name for s in axis_specs], mesh,
                             remat_plan=remat_plan,
                             partial_regions=partial_regions)
        avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                 for v in closed_jaxpr.jaxpr.invars]
        ma = jax.jit(fn).lower(*avals).compile().memory_analysis()
        return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes)
    except Exception as e:
        logger.warning("[remat] XLA peak probe failed (%s); trusting the "
                       "liveness model", e)
        return None


def _finish_compile(closed_jaxpr, jaxpr, names, per_axis, graph, axis_specs,
                    mesh, args, kwargs, flat_args, in_tree, out_tree,
                    state_pairs, donate_state, analysis_findings=None,
                    solver_audits=None, name="step"):
    """Emission + jit from solved strategies (shared by the fresh-solve and
    compile-cache paths).  `name` is the wrapped function's: both jits take
    it, so that the device trace's `XLA Modules` line reads
    `jit_train_step(...)`, `jit__decode_paged(...)` and not `jit_tree_fn`
    for every program."""
    axis_names = [s.name for s in axis_specs]
    per_axis_final = [c if c is not None else {} for c in per_axis]

    # ---- silent-replication signal: a program whose compute-heavy eqns all
    # chose replicate ships with ZERO parallelism — loudly say so
    replicated_fraction = _replicated_flops_fraction(jaxpr, per_axis_final,
                                                     axis_specs)
    if replicated_fraction > edconfig.replicate_warn_threshold:
        logger.warning(
            "[easydist] %.0f%% of modeled FLOPs run fully REPLICATED on a "
            "%d-device mesh — near-zero parallelism.  Common causes: "
            "indivisible dims, control-flow primitives without sharding "
            "rules, or a cost model preferring replication at these sizes.",
            100.0 * replicated_fraction,
            int(np.prod([s.size for s in axis_specs])))

    # ---- deferred-reduction regions for solver-chosen PARTIAL chains
    # (found BEFORE remat so the memory probes measure the program that
    # actually ships, and remat chains never reach inside a region)
    partial_regions = None
    if edconfig.enable_partial_pools:
        from .partial_regions import find_partial_regions

        partial_regions = find_partial_regions(
            jaxpr, per_axis_final, axis_names,
            [mesh.shape[n] for n in axis_names])
    region_eqns = {i for r in (partial_regions or [])
                   for i in range(r.start, r.end + 1)}

    # ---- memory: plan the per-device peak under the (auto-resolved) HBM
    # cap; over cap -> compiler-chosen remat (schedule/remat.py — the TPU
    # form of the reference memory-opt path, compile_auto.py:353-453)
    remat_plan = None
    if edconfig.enable_auto_remat:
        from easydist_tpu.schedule.remat import plan_remat, resolve_memory_cap

        cap = resolve_memory_cap(mesh)
        if cap > 0:
            state_io_names = {}
            for out_idx, in_idx in state_pairs.items():
                if out_idx < len(jaxpr.outvars) and in_idx < len(jaxpr.invars):
                    ov = jaxpr.outvars[out_idx]
                    if not isinstance(ov, jex_core.Literal):
                        state_io_names[names.name(ov)] = \
                            names.name(jaxpr.invars[in_idx])
            t0 = time.perf_counter()
            axis_sizes = [s.size for s in axis_specs]
            remat_plan = plan_remat(closed_jaxpr, names, per_axis_final,
                                    axis_sizes, cap, state_io_names,
                                    banned_eqns=region_eqns)
            if remat_plan is not None and jax.default_backend() != "cpu":
                # the liveness model is a python-order upper bound; before
                # paying recompute, ask XLA's own scheduler (memory_analysis
                # — ground truth, no execution).  CPU backends report
                # temp_size 0 and skip these checks.
                actual = _xla_peak_bytes(closed_jaxpr, names, per_axis_final,
                                         axis_specs, mesh,
                                         partial_regions=partial_regions)
                if actual is not None and actual <= cap:
                    logger.info(
                        "[remat] model peak %.2f GiB over cap but XLA "
                        "schedules it in %.2f GiB (cap %.2f) — no remat",
                        remat_plan.base_peak / 2**30, actual / 2**30,
                        cap / 2**30)
                    remat_plan = None
                elif actual is not None:
                    # verify the rewrite helps XLA before shipping it:
                    # recompute barriers can also BLOCK scheduler freedom
                    actual_rm = _xla_peak_bytes(
                        closed_jaxpr, names, per_axis_final, axis_specs,
                        mesh, remat_plan=remat_plan,
                        partial_regions=partial_regions)
                    if actual_rm is None or actual_rm >= actual:
                        logger.warning(
                            "[remat] rewrite did not reduce XLA peak "
                            "(%.2f -> %s GiB); dropping it — program "
                            "exceeds the %.2f GiB cap by %.2f GiB",
                            actual / 2**30,
                            actual_rm and f"{actual_rm/2**30:.2f}",
                            cap / 2**30, (actual - cap) / 2**30)
                        remat_plan = None
                    else:
                        logger.info(
                            "[remat] XLA peak %.2f -> %.2f GiB (cap %.2f"
                            " GiB)%s", actual / 2**30, actual_rm / 2**30,
                            cap / 2**30,
                            "" if actual_rm <= cap else " — best effort,"
                            " still over cap")
            if remat_plan:
                logger.info("[remat] planned in %.2fs",
                            time.perf_counter() - t0)

    # ---- input shardings from placeholder strategies
    in_shardings = []
    for i, var in enumerate(jaxpr.invars):
        placements = [c.get(names.name(var)) for c in per_axis_final]
        specs = [s.out_placements[0] if s is not None else None
                 for s in placements]
        ndim = len(var.aval.shape)
        in_shardings.append(NamedSharding(mesh, _combined_spec(
            specs, axis_names, ndim)))

    # ---- emit + jit
    sharded_fn = emit_sharded_fn(closed_jaxpr, names, per_axis_final,
                                 axis_names, mesh, remat_plan=remat_plan,
                                 partial_regions=partial_regions)
    if edconfig.remat_policy != "none":
        # rematerialization policy for callers who differentiate THROUGH the
        # compiled function (a compiled train step already contains its own
        # autodiff and is unaffected): "dots" saves matmul outputs only,
        # "all" recomputes everything
        policies = {"dots": jax.checkpoint_policies.checkpoint_dots,
                    "all": jax.checkpoint_policies.nothing_saveable}
        policy = policies.get(edconfig.remat_policy)
        if policy is None:
            raise ValueError(
                f"unknown remat_policy {edconfig.remat_policy!r}; "
                f"expected none|dots|all")
        sharded_fn = jax.checkpoint(sharded_fn, policy=policy)
    if donate_state is None:
        donate_state = edconfig.enable_donation
    donate = tuple(sorted(set(state_pairs.values()))) if donate_state else ()

    # ---- a paired state leaf has ONE sharding, the solved one of the input
    # it replaces.  Left to XLA, a leaf comes back in a sharding of XLA's
    # choosing (the gpt2-xl step's `wte`: solved whole, returned over both
    # axes, its donated buffer not aliased): another key to the jit's
    # cache, so the second call traced, lowered and compiled the whole step
    # again.  Constrained in the trace and not declared as the jit's
    # `out_shardings`: with those, JAX pairs a donated input with an output
    # by their PER-DEVICE shapes, and state brought in uncommitted (its
    # sharding left to XLA) is aliased to another leaf's output and fails
    # to compile.  The array handed back carries the sharding as JAX spells
    # it, which is `_combined_spec`'s spelling.  Unpaired outputs (a loss,
    # a readback) stay XLA's to place; rank-0 leaves as on the way in.
    out_pins = {out_idx: in_shardings[in_idx]
                for out_idx, in_idx in state_pairs.items()
                if out_idx < len(jaxpr.outvars) and in_idx < len(in_shardings)
                and jaxpr.outvars[out_idx].aval.shape}
    if out_pins:
        emitted_fn = sharded_fn

        def sharded_fn(*flat_args):
            outs = list(emitted_fn(*flat_args))
            for out_idx, sharding in out_pins.items():
                outs[out_idx] = jax.lax.with_sharding_constraint(
                    outs[out_idx], sharding)
            return outs

    sharded_fn.__name__ = sharded_fn.__qualname__ = name + "_flat"
    jitted = jax.jit(sharded_fn, in_shardings=in_shardings,
                     donate_argnums=donate)

    # pytree-native variant: flattening/unflattening happens inside the
    # trace, so the per-call path is jax's C++ dispatch (the flat wrapper
    # costs several ms per call at ~250 leaves).  The signature guard runs
    # at TRACE time only: steady-state calls are pure jit cache hits, and a
    # shape/tree change raises SignatureMismatch for the wrapper to catch.
    out_tree_local = out_tree
    expected_tree = in_tree
    expected_avals = [(tuple(v.aval.shape), v.aval.dtype)
                      for v in jaxpr.invars]

    def tree_fn(*t_args, **t_kwargs):
        flat, td = jax.tree_util.tree_flatten((t_args, t_kwargs))
        if td != expected_tree or len(flat) != len(expected_avals) or any(
                tuple(getattr(x, "shape", ())) != s
                or getattr(x, "dtype", None) != d
                for x, (s, d) in zip(flat, expected_avals)):
            raise SignatureMismatch
        # constrain inputs INSIDE the trace rather than pinning jit
        # in_shardings: paired state comes back in its solved sharding
        # (`out_pins`), so the second call hits this jit's cache, but a
        # caller may bring state or a batch committed otherwise, and
        # pinned in_shardings would reject it where this places it
        flat = [jax.lax.with_sharding_constraint(x, s)
                if hasattr(x, "ndim") and x.ndim > 0 else x
                for x, s in zip(flat, in_shardings)]
        return jax.tree_util.tree_unflatten(out_tree_local, sharded_fn(*flat))

    # donate the positional args whose leaves are all state (positional
    # prefix pairing guarantees this shape)
    donate_args = []
    if donate:
        donated = set(donate)
        base = 0
        for i, a in enumerate(args):
            n = len(jax.tree_util.tree_leaves(a))
            if n and all(base + k in donated for k in range(n)):
                donate_args.append(i)
            base += n
    tree_fn.__name__ = tree_fn.__qualname__ = name
    tree_jitted = jax.jit(tree_fn, donate_argnums=tuple(donate_args))

    in_avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                for v in jaxpr.invars]
    result = CompileResult(jitted, tree_jitted, in_shardings, per_axis_final,
                           graph, mesh, in_tree, out_tree, len(flat_args),
                           in_avals=in_avals)
    result.name = name
    result.remat_plan = remat_plan
    result.closed_jaxpr = closed_jaxpr
    # donation audit surface (analyze.audit_decode_donation / SERVE001):
    # flat input indices donated to XLA, and the whole positional args the
    # pytree-native wrapper donates
    result.donated_invars = donate
    result.donated_args = tuple(donate_args)
    # the declared state threading, for the layer-11 donation-pair audit
    # (ALIAS002 double-claimed inputs, ALIAS003 unhonorable pairs)
    result.state_pairs = dict(state_pairs)
    result.replicated_flops_fraction = replicated_fraction
    result.analysis_findings = list(analysis_findings or [])
    result.solver_audits = list(solver_audits or [])
    return result


class CompiledFunction:
    """User-facing wrapper: compiles on first call per input signature and
    replays after (reference CompiledFuncWrapper, jax/api.py:288-304 and
    torch/api.py:53-222)."""

    def __init__(self, func, mesh=None, state_io="auto",
                 donate_state: Optional[bool] = None, compile_only=False):
        self.func = func
        self.mesh = mesh
        self.state_io = state_io
        self.donate_state = donate_state
        self.compile_only = compile_only
        self._cache: Dict[object, CompileResult] = {}
        self._last: Optional[CompileResult] = None
        self._cache_hits = 0
        self._cache_misses = 0
        functools.update_wrapper(self, func)

    @staticmethod
    def _signature(flat_args, treedef):
        # hashable tuple, no string formatting — this runs on every call
        return (treedef,
                tuple((getattr(l, "shape", ()),
                       getattr(l, "dtype", None) or type(l))
                      for l in flat_args))

    def get_compiled(self, *args, **kwargs) -> CompileResult:
        flat_args, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return self._lookup(flat_args, treedef, args, kwargs)

    # ------------------------------------------------------ stable surface
    # (the serving layer keys its shape-bucketed executable cache on these;
    # keep them additive-only)

    def cache_key(self, *args, **kwargs):
        """Stable hashable key for the compiled-result cache entry these
        args resolve to: (input treedef, per-leaf (shape, dtype)).  Two
        call signatures share an executable iff their keys are equal."""
        flat_args, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return self._signature(flat_args, treedef)

    def compiled_signatures(self):
        """Keys (see `cache_key`) of every signature compiled so far."""
        return tuple(self._cache)

    def cache_stats(self) -> Dict[str, int]:
        """{size, hits, misses} of the compile cache.  Hits count lookups
        that found an existing CompileResult; the `_last` fast path in
        `__call__` bypasses lookup entirely and is not counted."""
        return {"size": len(self._cache), "hits": self._cache_hits,
                "misses": self._cache_misses}

    def executable_for(self, *args, **kwargs):
        """The lowered+compiled XLA executable handle for this signature
        (compiling it first if needed) — the object carrying
        cost_analysis()/memory_analysis()."""
        return self.get_compiled(*args, **kwargs).executable()

    def analyze(self, *args, raise_on_error: Optional[bool] = None,
                include_program: bool = True, include_memory: bool = True,
                export: bool = True, **kwargs):
        """Run the static analyzer (easydist_tpu.analyze) on a compiled
        signature: with args, the signature they resolve to (compiling it
        first if needed); without, the last-called one.

        Exports finding counts to the runtime PerfDB under
        ("analyze_stats", <function name>) and raises AnalysisError on
        error-severity findings unless `raise_on_error=False` or the
        `EASYDIST_ANALYZE_RAISE=0` escape hatch is set.  Returns the
        AnalysisReport."""
        if args or kwargs:
            result = self.get_compiled(*args, **kwargs)
        else:
            result = self._last
            if result is None:
                raise RuntimeError(
                    "analyze(): nothing compiled yet — call the function "
                    "first or pass example args")
        report = result.analyze(include_program=include_program,
                                include_memory=include_memory)
        if export:
            report.export_to_perfdb(
                sub_key=getattr(self.func, "__name__", "step"))
        if raise_on_error is None:
            raise_on_error = edconfig.analyze_raise
        if raise_on_error:
            report.raise_on_errors()
        elif report.errors():
            logger.warning("[analyze] %s", report.summary())
        return report

    def _lookup(self, flat_args, treedef, args, kwargs) -> CompileResult:
        sig = self._signature(flat_args, treedef)
        result = self._cache.get(sig)
        if result is None:
            self._cache_misses += 1
            result = compile_step(
                self.func, args, kwargs, mesh=self.mesh,
                state_io=self.state_io, donate_state=self.donate_state)
            self._cache[sig] = result
        else:
            self._cache_hits += 1
        return result

    def __call__(self, *args, **kwargs):
        if not self.compile_only and self._last is not None:
            # hot path: one span round the jit dispatch; a shape/tree change
            # raises SignatureMismatch during retrace and falls through
            try:
                return self._last.dispatch(args, kwargs)
            except SignatureMismatch:
                pass
        flat_args, treedef = jax.tree_util.tree_flatten((args, kwargs))
        result = self._lookup(flat_args, treedef, args, kwargs)
        self._last = result
        if self.compile_only:
            return result
        return result.dispatch(args, kwargs)


def easydist_compile(func=None, mesh=None, state_io="auto",
                     donate_state: Optional[bool] = None,
                     compile_only: bool = False,
                     max_solver_time: Optional[float] = None,
                     liveness_only_input: Optional[bool] = None,
                     pp_stages: Optional[int] = None,
                     n_microbatches: Optional[int] = None,
                     pp_axis: str = "pp", schedule: str = "gpipe",
                     lr: Optional[float] = None, optimizer="adam",
                     tp_axes=None):
    """Decorator entrypoint (reference jax/api.py:307-323).

    With `pp_stages=` the decorated function is treated as a LOSS function
    `loss_fn(params, *batch) -> scalar` (mean reduction over the batch) and
    compiled into a hybrid auto-PP x SPMD train step
    (jaxfront/pp_compile.py — the reference's schedule_cls path,
    compile_auto.py:683-715).  The pp path has a different contract (it
    returns a train step with its own optimizer state, not a compiled copy
    of `func`), so the non-pp kwargs `state_io` / `donate_state` /
    `compile_only` are rejected loudly rather than silently ignored;
    `optimizer` accepts "adam", "sgd", or an optax GradientTransformation.
    """
    if max_solver_time is not None:
        edconfig.solver_time_limit = max_solver_time
    if liveness_only_input is not None:
        edconfig.liveness_only_input = liveness_only_input

    def wrap(f):
        if pp_stages is not None:
            from .pp_compile import PPCompiledFunction

            dropped = [name for name, val, default in (
                ("state_io", state_io, "auto"),
                ("donate_state", donate_state, None),
                ("compile_only", compile_only, False)) if val != default]
            if dropped:
                raise ValueError(
                    f"easydist_compile(pp_stages=...) does not support "
                    f"{dropped}: the hybrid path manages its own train "
                    f"state (donated whole) and always compiles lazily on "
                    f"the first init_state call")
            m = mesh or get_device_mesh()
            if m is None:
                raise ValueError("pp_stages= needs an explicit mesh")
            return PPCompiledFunction(
                f, m, pp_stages=pp_stages,
                n_microbatches=n_microbatches or pp_stages * 2,
                pp_axis=pp_axis, schedule=schedule, lr=lr,
                optimizer=optimizer, tp_axes=tp_axes)
        pp_only = [name for name, val, default in (
            ("n_microbatches", n_microbatches, None),
            ("pp_axis", pp_axis, "pp"), ("schedule", schedule, "gpipe"),
            ("lr", lr, None), ("optimizer", optimizer, "adam"),
            ("tp_axes", tp_axes, None))
            if val != default]
        if pp_only:
            raise ValueError(
                f"{pp_only} only apply with pp_stages=; without it the "
                f"decorated function IS the train step (it owns its "
                f"optimizer), so silently dropping them would change "
                f"training behavior")
        return CompiledFunction(f, mesh=mesh, state_io=state_io,
                                donate_state=donate_state,
                                compile_only=compile_only)

    return wrap(func) if func is not None else wrap


def get_opt_strategy(func, *args, mesh=None, **kwargs):
    """Solve and return the per-axis strategy dict without building the
    executable (reference public API: jax/api.py:173 get_opt_strategy)."""
    result = compile_step(func, args, kwargs, mesh=mesh)
    return result.strategies
