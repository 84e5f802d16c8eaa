"""Jaxpr sharding interpreter: run every equation through ShardCombine.

Walks a jaxpr equation by equation, materializes random concrete inputs on
the host CPU, wraps each primitive bind as a `MetaOp`, and runs sharding
discovery — with a per-(primitive, shapes, params) cache and a prompt
fast-path so each unique op signature is discovered once.  Reshapes are
handled analytically (`view_rule`) instead of by execution.

Reference: easydist/jax/sharding_interpreter.py:51-170.  Differences: var
names are assigned stably (v0, v1, ...) instead of parsing jaxpr printouts,
and avals stay abstract in the environment — inputs are materialized only at
op-execution time, bounding discovery memory to one op's working set.
"""

from __future__ import annotations

import functools
import logging
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from easydist_tpu import config as edconfig
from easydist_tpu.metashard import MetaOp, ShardSpace, view_rule
from easydist_tpu.metashard.metaop import probe_calls

logger = logging.getLogger(__name__)

_JAXPRS = (jex_core.Jaxpr, jex_core.ClosedJaxpr)

# primitives whose sharding rule is computed analytically, not by execution
_VIEW_PRIMS = {"reshape"}

# preset rules the execution harness cannot cross-check: their analytic
# claims hold under GSPMD but the eager probe rejects the sharded rebind
# (absolute-shape params like slice limits / broadcast out-shapes, or
# unpartitionable custom calls) — documented per-rule in presets.py
_CROSSCHECK_SKIP = {
    "gather", "scatter-add", "pallas_call", "sharding_constraint",
    "slice", "broadcast_in_dim", "reshape", "dynamic_slice",
    "dynamic_update_slice", "iota", "ed_attention_fwd", "ed_attention_bwd",
}


def _recombine_matches(expected, got) -> bool:
    """Compare a preset recombine (functools.partial over Recombine.*)
    against what execution discovery matched, up to default halo/block."""
    if expected is None or got is None:
        return expected is None and got is None
    if isinstance(expected, list) or isinstance(got, list):
        if not isinstance(expected, list) or not isinstance(got, list) \
                or len(expected) != len(got):
            return False
        return all(_recombine_matches(e, g)
                   for e, g in zip(expected, got))

    def norm(fn):
        kw = dict(getattr(fn, "keywords", {}) or {})
        if kw.get("halo") == 0:
            del kw["halo"]
        if kw.get("block") == 1:
            del kw["block"]
        return getattr(getattr(fn, "func", None), "__name__", None), kw

    return norm(expected) == norm(got)


class VarNames:
    """Stable names for jaxpr Vars (jax no longer prints short names)."""

    def __init__(self):
        self._names: Dict[jex_core.Var, str] = {}

    def name(self, var) -> str:
        if var not in self._names:
            self._names[var] = f"v{len(self._names)}"
        return self._names[var]


def _materialize(aval, key):
    """Random concrete array for an abstract value (reference jax/api.py:50-61).
    Random (not ones/zeros) so degenerate recombinations don't false-match.
    Floats are strictly POSITIVE (uniform [0.5, 1.5], matching the int
    convention below): signed values make contraction outputs cancel to
    near zero, where the reassociated per-shard partial sums miss the
    allclose atol and a valid reduce candidate is rejected for one shape
    but accepted for a same-role sibling — acceptance must be a function
    of the op's structure, not of which random draws cancelled."""
    name = aval.dtype.name
    if name in ("float64", "float32", "float16", "bfloat16"):
        return jax.random.uniform(key, shape=aval.shape, dtype=aval.dtype,
                                  minval=0.5, maxval=1.5)
    if name in ("int64", "int32", "int16", "int8", "uint8", "uint32", "uint64"):
        return jax.random.randint(key, shape=aval.shape, minval=1, maxval=8,
                                  dtype=aval.dtype)
    if name == "bool":
        return jax.random.bernoulli(key, p=0.5, shape=aval.shape)
    return jnp.zeros(aval.shape, dtype=aval.dtype)


def hash_array_bytes(arr) -> str:
    """Content digest of an array's full bytes — used wherever constant
    VALUES (not just shapes) must feed a cache key; repr() truncates."""
    import hashlib

    import numpy as np

    arr = np.ascontiguousarray(arr)
    return hashlib.sha256(arr.tobytes()).hexdigest()[:16]


@functools.lru_cache(maxsize=256)
def _jaxpr_text(jaxpr) -> str:
    """`repr` of a jaxpr held as an equation's parameter, printed once an
    OBJECT: a model's layers call a Pallas kernel at one signature and
    share its jaxpr (`ops/flash_attention.py::_paged_call`), and printing
    the paged kernels' body costs ~45 ms — a second a program and more, in
    every start, when each of 16 layers' equations is signed three times
    (PERF.md section 6, PR 42)."""
    return repr(jaxpr)


def _params_text(params) -> str:
    """`str(sorted(params.items()))`, letter for letter."""
    return "[" + ", ".join(
        f"({k!r}, "
        f"{_jaxpr_text(v) if isinstance(v, _JAXPRS) else repr(v)})"
        for k, v in sorted(params.items())) + "]"


def eqn_signature(eqn, names: VarNames) -> str:
    """Cache key for an equation: primitive + params + input shapes/dtypes."""
    import numpy as np

    prim = eqn.primitive.name
    parts = []
    for v in eqn.invars:
        if isinstance(v, jex_core.Literal):
            val = v.val
            if isinstance(val, np.ndarray) and val.size > 1:
                parts.append(f"lit:{val.dtype.name}{list(val.shape)}:"
                             f"{hash_array_bytes(val)}")
            else:
                parts.append(f"lit:{val!r}")
        else:
            parts.append(f"{v.aval.dtype.name}{list(v.aval.shape)}")
    try:
        params = _params_text(eqn.params)
    except Exception:
        params = str(eqn.params)
    return f"{prim}|{';'.join(parts)}|{params}"


class ShardingAnalyzer:
    """Discover sharding rules for every eqn of a (closed) jaxpr."""

    def __init__(self, closed_jaxpr, world_size: int, seed: int = 42):
        from .discovery import DiscoveryCounters, get_cache

        self.closed_jaxpr = closed_jaxpr
        self.jaxpr = closed_jaxpr.jaxpr
        self.world_size = world_size
        self.names = VarNames()
        self.key = jax.random.PRNGKey(seed)
        self._eqn_key = self.key
        self._eqn_draws = 0
        # eqn signature -> {"space": ShardSpace, "recombines": {...}}
        self.rules: Dict[str, dict] = {}
        # primitive name -> first discovered space (prompt for other shapes)
        self.prompts: Dict[str, ShardSpace] = {}
        self.shape_info: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        # propagation groups (jaxfront/discovery.py): canonical signature ->
        # (rule, representative row shapes, representative exact signature)
        self.canon_rules: Dict[str, tuple] = {}
        self.counters = DiscoveryCounters()
        # DISC001/DISC002 findings + transfer records for the layer-10 audit
        self.findings: List[object] = []
        self._transfers: List[dict] = []
        self._dcache = get_cache()
        self._is_sub = False
        self._last_discovery_failed = False

    def _next_key(self):
        """Key for the next materialized discovery input.  Derived from
        (base seed, current eqn signature, draw index) — NOT a sequential
        split stream — so an eqn's probe inputs are identical no matter
        which earlier eqns were served from a group, the cache, or a
        preset.  Positional keys would make discovery outcomes depend on
        pruning history and break pruned-vs-unpruned strategy equality."""
        k = jax.random.fold_in(self._eqn_key, self._eqn_draws)
        self._eqn_draws += 1
        return k

    def run(self) -> Tuple[Dict[str, dict], Dict[str, Tuple]]:
        env: Dict[jex_core.Var, object] = {}

        def read_concrete(var):
            if isinstance(var, jex_core.Literal):
                return var.val
            aval = env[var]
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                with jax.default_device(_discovery_device()):
                    return _materialize(aval, self._next_key())
            return aval

        def _discovery_device():
            if edconfig.discovery_on_cpu:
                return jax.local_devices(backend="cpu")[0]
            return jax.devices()[0]

        t0 = time.perf_counter()
        p0 = probe_calls()

        for var in self.jaxpr.invars + self.jaxpr.constvars:
            env[var] = var.aval
            self.shape_info[self.names.name(var)] = (tuple(var.aval.shape),
                                                     var.aval.dtype.name)

        for eqn in self.jaxpr.eqns:
            sig = eqn_signature(eqn, self.names)

            if sig not in self.rules:
                self._eqn_key = jax.random.fold_in(
                    self.key, zlib.crc32(sig.encode()))
                self._eqn_draws = 0
                self.rules[sig] = self._lookup_or_discover(eqn, sig,
                                                           read_concrete)

            # record output shapes from avals (no execution needed)
            for outvar in eqn.outvars:
                aval = outvar.aval
                env[outvar] = aval
                if hasattr(aval, "shape"):
                    self.shape_info[self.names.name(outvar)] = (
                        tuple(aval.shape), aval.dtype.name)

        if not self._is_sub:
            self._finish_trace(time.perf_counter() - t0, probe_calls() - p0)
        return self.rules, self.shape_info

    def _finish_trace(self, elapsed: float, probes: int) -> None:
        """Top-level-trace epilogue: fold the probe/derivation counts into
        this trace's counters (and the process-wide ones), persist newly
        discovered rules, audit every representative->member transfer
        (analyze layer 10), and log ONE summary line for the whole trace —
        the per-op discovery chatter is debug-level now."""
        from .discovery import GLOBAL_COUNTERS

        c = self.counters
        c.discovery_seconds += elapsed
        c.probes_compiled += probes
        c.groups = len(self.canon_rules)
        if self._dcache is not None:
            self._dcache.flush()
        if edconfig.enable_analyze and self._transfers:
            from easydist_tpu.analyze import audit_rule_transfer

            self.findings.extend(audit_rule_transfer(self._transfers))
        GLOBAL_COUNTERS.merge(c)
        logger.info(
            "[discovery] %d signatures: %d preset, %d grouped, %d cached, "
            "%d discovered (%d probes, %d groups) in %.2fs",
            len(self.rules), c.rules_preset, c.rules_from_group,
            c.rules_from_cache, c.rules_discovered, c.probes_compiled,
            c.groups, c.discovery_seconds)

    def _lookup_or_discover(self, eqn, sig: str, read_concrete) -> dict:
        """Rule resolution pipeline for one unseen exact signature:
        analytic preset -> propagation group (discover once per canonical
        signature, instantiate for members) -> persistent rule cache ->
        execution/composite discovery.  The kill switch
        (EASYDIST_DISCOVERY_PRUNE=0) reduces this to preset-or-discover,
        the pre-pruning behavior."""
        from . import discovery as disc
        from .presets import _RULES as preset_registry, preset_rule

        prim_name = eqn.primitive.name
        if edconfig.discovery_use_presets:
            preset = preset_rule(eqn, self.world_size)
            if preset is not None:
                self.counters.rules_preset += 1
                if edconfig.discovery_crosscheck:
                    self._crosscheck_preset(eqn, sig, preset, read_concrete)
                return preset
            if prim_name in preset_registry \
                    and prim_name not in _VIEW_PRIMS \
                    and edconfig.enable_analyze:
                from easydist_tpu.analyze import make_finding

                # DISC002: a preset-covered primitive fell through to the
                # probe harness — the analytic rule declined this instance
                self.findings.append(make_finding(
                    "DISC002", f"discovery.{prim_name}",
                    f"analytic preset for {prim_name!r} declined "
                    f"{sig[:96]!r}; execution discovery runs instead — "
                    f"extend the preset to cover this instance or fix "
                    f"the decline"))

        csig = None
        if edconfig.discovery_prune or self._dcache is not None:
            csig = disc.canonical_signature(eqn, self.world_size)

        if csig is not None and edconfig.discovery_prune:
            got = self.canon_rules.get(csig)
            if got is not None:
                rule, rep_shapes, rep_sig = got
                if disc.rule_transferable(rule, rep_shapes, eqn):
                    self.counters.rules_from_group += 1
                    self._transfers.append({
                        "sig": sig, "prim": prim_name, "rep_sig": rep_sig,
                        "rep_shapes": rep_shapes,
                        "member_shapes": disc.eqn_tensor_shapes(eqn),
                        "rule": rule})
                    return rule

        if csig is not None and self._dcache is not None:
            entry = self._dcache.get(csig)
            if entry is not None and disc.rule_transferable(
                    entry["rule"], entry["shapes"], eqn):
                self.counters.rules_from_cache += 1
                self._transfers.append({
                    "sig": sig, "prim": prim_name, "rep_sig": "<cache>",
                    "rep_shapes": entry["shapes"],
                    "member_shapes": disc.eqn_tensor_shapes(eqn),
                    "rule": entry["rule"]})
                if edconfig.discovery_prune:
                    self.canon_rules[csig] = (entry["rule"],
                                              entry["shapes"], sig)
                return entry["rule"]

        self._last_discovery_failed = False
        rule = self._discover_eqn(eqn, sig, read_concrete)
        self.counters.rules_discovered += 1
        if csig is not None and not self._last_discovery_failed:
            shapes = disc.eqn_tensor_shapes(eqn)
            if edconfig.discovery_prune:
                self.canon_rules[csig] = (rule, shapes, sig)
            if self._dcache is not None:
                self._dcache.put(csig, {"rule": rule, "shapes": shapes,
                                        "prim": prim_name})
        return rule

    def _crosscheck_preset(self, eqn, sig: str, rule: dict,
                           read_concrete) -> None:
        """One-shot preset validation (EASYDIST_DISCOVERY_CROSSCHECK=1):
        every shard group the analytic rule declares must execute through
        the ShardCombine harness and recombine exactly as declared.  A
        failure is counted and logged loudly, never raised — the mode
        exists to audit the preset bank, not to gate compiles."""
        prim_name = eqn.primitive.name
        space = rule.get("space")
        if prim_name in _CROSSCHECK_SKIP or space is None \
                or space.max_group() == 0:
            return
        total = sum(int(np.prod(v.aval.shape))
                    for v in list(eqn.invars) + list(eqn.outvars)
                    if not isinstance(v, jex_core.Literal)
                    and hasattr(getattr(v, "aval", None), "shape"))
        if total > edconfig.discovery_hint_numel:
            return  # cross-check runs on small shapes only

        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)

        def bind_fn(*tensors, **params):
            with jax.disable_jit():
                return eqn.primitive.bind(*subfuns, *tensors, **params)

        invals = [read_concrete(v) for v in eqn.invars]
        op = MetaOp(bind_fn, tuple(invals), kwargs=bind_params,
                    name=prim_name)
        if len(space) != len(op.tensor_indices):
            return  # row convention mismatch (array literal rows)
        try:
            global_out = op.run_global()
        except Exception:
            return
        self.counters.crosscheck_checked += 1
        for group in range(1, space.max_group() + 1):
            res = op._check_candidate(space, group, global_out)
            ok = (res is not None and res[1] is None
                  and _recombine_matches(rule["recombines"].get(group),
                                         res[0]))
            if not ok:
                self.counters.crosscheck_failures += 1
                logger.warning(
                    "[discovery] preset cross-check FAILED for %s group "
                    "%d (%s)", prim_name, group, sig[:120])

    def _discover_eqn(self, eqn, sig: str, read_concrete) -> dict:
        """Actually derive a rule for one eqn (view analysis, composite body
        solving, or execution discovery).  Preset lookup and all reuse paths
        live in _lookup_or_discover; this runs only on a full miss."""
        prim_name = eqn.primitive.name

        if prim_name in _VIEW_PRIMS:
            in_aval = eqn.invars[0].aval
            out_aval = eqn.outvars[0].aval
            try:
                rule = view_rule(list(in_aval.shape), list(out_aval.shape),
                                 world_size=self.world_size)
                return {"space": rule["space"], "recombines": rule["recombines"]}
            except RuntimeError:
                pass  # unalignable view: fall through to execution discovery

        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)

        def bind_fn(*tensors, **params):
            with jax.disable_jit():
                return eqn.primitive.bind(*subfuns, *tensors, **params)

        # hint shrink (reference get_hint_size, sharding_interpreter.py:
        # 256-313): execution discovery on a huge unpreset op would run it
        # eagerly nshards x candidates times — discover on a proportionally
        # shrunk instance instead.  Equal dim sizes shrink together (keeps
        # contraction/broadcast consistency); rules are dim-indexed so they
        # transfer to the original shapes.  Ops whose params encode shapes
        # fail the shrunk bind and fall through to full-size discovery.
        total = sum(int(np.prod(v.aval.shape)) for v in eqn.invars
                    if not isinstance(v, jex_core.Literal)
                    and hasattr(v.aval, "shape"))
        total += sum(int(np.prod(v.aval.shape)) for v in eqn.outvars
                     if hasattr(v.aval, "shape"))
        # jax.checkpoint bodies: recursively analyze the inner jaxpr and
        # compose a rule analytically — execution discovery would run the
        # whole body eagerly per candidate (reference r1 gap: remat regions
        # fell back to replicate)
        if prim_name in ("remat2", "remat", "checkpoint"):
            rule = self._discover_composite(eqn)
            if rule is not None:
                return rule

        # lax.scan: recursive body analysis with carry-placement threading —
        # without it a scan-over-layers model (the idiomatic Llama-scale
        # form) ships fully replicated.  The reference never hits this
        # because make_fx fully unrolls (easydist/torch/compile.py:78-83);
        # the TPU design keeps the rolled loop (XLA compiles the body once)
        # and instead solves the body, pricing per-iteration collectives as
        # the scan strategy's intrinsic cost.
        if prim_name == "scan" and self.world_size > 1:
            try:
                rule = self._discover_scan(eqn)
            except Exception as e:
                logger.warning("scan discovery failed (%s): %s", sig, e)
                rule = None
            if rule is not None:
                return rule

        # lax.cond / lax.while_loop: same composite treatment (VERDICT r4
        # missing #4 — any non-scan control flow shipped replicated)
        if prim_name == "cond" and self.world_size > 1:
            try:
                rule = self._discover_cond(eqn)
            except Exception as e:
                logger.warning("cond discovery failed (%s): %s", sig, e)
                rule = None
            if rule is not None:
                return rule
        if prim_name == "while" and self.world_size > 1:
            try:
                rule = self._discover_while(eqn)
            except Exception as e:
                logger.warning("while discovery failed (%s): %s", sig, e)
                rule = None
            if rule is not None:
                return rule

        if total > edconfig.discovery_hint_numel:
            rule = self._discover_shrunk(eqn, bind_fn, bind_params,
                                         prim_name)
            if rule is not None:
                logger.debug("discovery hint-shrink applied to %s (%d elems)",
                             prim_name, total)
                return rule

        invals = [read_concrete(v) for v in eqn.invars]
        op = MetaOp(bind_fn, tuple(invals), kwargs=bind_params,
                    name=prim_name)
        prompt = self.prompts.get(prim_name)
        try:
            space, recombines = op.discover(prompt=prompt)
        except Exception as e:
            logger.warning("discovery failed for %s (%s): %s — replicating",
                           prim_name, sig, e)
            space, recombines = ShardSpace.for_args(op.flat_args), {}
            # a replicate fallback is shape-circumstantial — never persist
            # it or transfer it across a propagation group
            self._last_discovery_failed = True
        if prim_name not in self.prompts and space.max_group() > 0:
            self.prompts[prim_name] = space
        return {"space": space, "recombines": recombines}

    def _analyze_inner(self, inner):
        """Normalize a call-like eqn's body jaxpr and analyze it with this
        analyzer's caches shared.  Returns (inner ClosedJaxpr, sub analyzer,
        rules, shape_info) or None when the body isn't analyzable."""
        from .inline import inline_calls

        if inner is None:
            return None
        if not hasattr(inner, "jaxpr"):  # raw Jaxpr -> ClosedJaxpr
            if inner.constvars:
                return None
            inner = jex_core.ClosedJaxpr(inner, ())
        inner = inline_calls(inner)  # bodies keep nested pjit calls

        sub = ShardingAnalyzer(inner, world_size=self.world_size)
        sub.prompts = self.prompts  # share caches with the outer analysis
        sub.rules = self.rules
        sub.canon_rules = self.canon_rules
        sub.counters = self.counters
        sub.findings = self.findings
        sub._transfers = self._transfers
        sub._dcache = self._dcache
        sub._is_sub = True  # the top-level trace owns probe/time accounting
        rules, shape_info = sub.run()
        return inner, sub, rules, shape_info

    def _discover_composite(self, eqn):
        """Priced whole-region strategies for a call-like eqn
        (jax.checkpoint body): analyze the inner jaxpr recursively, then
        solve the body graph once per seed input-dim with collectives
        PRICED, not forbidden (the scan/cond/while treatment) — each
        surviving assignment becomes one explicit strategy of the
        composite eqn carrying honest per-strategy compute seconds.

        The earlier dim-group table with free boundaries mispriced remat
        regions two ways: the outer solver's any-shard discount cut the
        WHOLE region's FLOPs 1/n for a strategy that sharded one residual
        chain and replicated everything else, and sync-free-only
        propagation dropped assignments whose optimum includes a priced
        mid-body psum.  Policy checkpoints (remat="dots") exposed both —
        their backward regions take saved dot residuals as extra
        operands, a degenerate seq-dim group over one residual won on
        boundary bytes, and the plan shipped mostly-replicated compute
        plus boundary all-to-alls the un-remat'd twin never emits
        (test_remat_gpt_plan_matches_unremat_twin[dots]).
        """
        from easydist_tpu.metashard.metair import Placement

        got = self._analyze_inner(eqn.params.get("jaxpr"))
        if got is None:
            return None
        inner, sub, rules, shape_info = got

        in_rows = [v for v in eqn.invars
                   if not isinstance(v, jex_core.Literal)]
        inner_invars = inner.jaxpr.invars
        if len(in_rows) != len(inner_invars):
            return None
        in_names = [sub.names.name(v) for v in inner_invars]
        out_names = [None if isinstance(v, jex_core.Literal)
                     else sub.names.name(v) for v in inner.jaxpr.outvars]

        strategies = []  # (in_placements, out_placements, comm, compute)
        seen_keys = set()
        covered = set()  # (invar row, dim) already sharded by a strategy
        full_compute = 0.0
        n_solves = 0
        for row, (v, name) in enumerate(zip(inner_invars, in_names)):
            shape = tuple(v.aval.shape)
            numel = int(np.prod(shape)) if shape else 1
            # bias-sized inputs may ride along in a solve, but never seed
            if numel < self.world_size * 64:
                continue
            for d, size in enumerate(shape):
                if size % self.world_size != 0 or size < self.world_size:
                    continue
                if (row, d) in covered:
                    continue
                if n_solves >= edconfig.scan_max_seed_solves:
                    break
                n_solves += 1
                res = self._solve_body_pinned(
                    inner, sub, rules, shape_info,
                    pins={name: Placement.shard(d)})
                if res is None:
                    continue
                var_p, comm, compute, full = res
                full_compute = full
                ins = []
                for nm in in_names:
                    p = var_p.get(nm)
                    ins.append(Placement.shard(p.dim)
                               if p is not None and p.is_shard()
                               else Placement.replicate())
                if all(p.is_replicate() for p in ins):
                    continue
                outs = []
                for nm in out_names:
                    p = var_p.get(nm) if nm is not None else None
                    if p is not None and p.is_shard():
                        outs.append(Placement.shard(p.dim))
                    elif p is not None and p.is_partial():
                        outs.append(Placement.partial())
                    else:
                        outs.append(Placement.replicate())
                key = (tuple(repr(p) for p in ins),
                       tuple(repr(p) for p in outs))
                if key in seen_keys:
                    continue
                seen_keys.add(key)
                strategies.append((ins, outs, comm, compute))
                for r2, p in enumerate(ins):
                    if p.is_shard():
                        covered.add((r2, p.dim))

        if not strategies:
            return None
        logger.debug("composite rule for %s: %d priced strategies",
                    eqn.primitive.name, len(strategies))
        # same-basis replicate price (see _solve_body_pinned)
        return {"space": None, "recombines": {},
                "strategies": strategies, "compute": full_compute}

    def _solve_body_pinned(self, inner, sub, rules, shape_info, pins,
                           state_io=None, replicate_names=()):
        """Solve a control-flow body graph with `pins` ({placeholder name:
        Placement}) enforced via strategy exclusion, pricing collectives.
        `replicate_names` additionally pins those placeholders to R.
        `state_io` threads loop carries (out -> init placeholder) so
        per-iteration reshards are priced, not forbidden.  Returns
        ({var name: Placement}, comm seconds, compute seconds,
        full-price compute seconds) or None (infeasible, or divisibility
        removed a pin)."""
        from easydist_tpu.autoflow import MeshAxisSpec, SpmdSolver
        from .bridge import jaxpr_to_metagraph

        axis = MeshAxisSpec("_body", self.world_size)
        g = jaxpr_to_metagraph(inner, rules, shape_info,
                               world_size=self.world_size,
                               names=sub.names, state_io=state_io or None)
        _inject_partial_propagation(g, self.world_size)
        replicate_names = set(replicate_names)

        def excl(node):
            target = pins.get(node.name)
            if target is not None:
                return [s for s in node.strategy_pool(self.world_size)
                        if repr(s.out_placements[0]) != repr(target)]
            if node.name in replicate_names:
                return [s for s in node.strategy_pool(self.world_size)
                        if not s.is_all_replicate()]
            return []

        # level 0 (one node per cluster): cone back-build only keeps
        # sync-free intra-cluster assignments, which would hide e.g.
        # TP's P->R psum edge from the pricing
        g.coarsen(self.world_size, level=0, exclude_map=excl)
        try:
            # cluster dedup ties strategies across same-signature clusters,
            # which would fight the per-placeholder pins — disable it for
            # this solve only (not process-wide)
            solver = SpmdSolver(g, axis, free_outputs=True,
                                cluster_dedup=False)
            chosen = solver.solve()
        except Exception:
            return None
        for name, target in pins.items():
            got = chosen.get(name)
            if got is None or repr(got.out_placements[0]) != repr(target):
                return None  # divisibility removed the pin
        comm = solver.assignment_comm_cost(chosen)
        if not np.isfinite(comm):
            return None
        var_p = {}
        for node in list(g.ops) + list(g.inputs):
            s = chosen.get(node.name)
            if s is None:
                continue
            for v, p in zip(node.outvars, s.out_placements):
                if v is not None and p is not None:
                    var_p[v.name] = p
        # per-op body compute under this assignment: the same op-time model
        # the overlap engine uses (MXU ops at peak_flops, memory-bound ops
        # at hbm_bandwidth — VERDICT r4 weak #7: a bytes-only proxy
        # under-prices MXU-bound transformer bodies by ~D/245 at f32),
        # with the outer solver's any-S 1/world discount per op
        from easydist_tpu.autoflow.reachability import node_seconds

        compute = full_compute = 0.0
        for node in g.ops:
            s = chosen.get(node.name)
            sharded = s is not None and any(
                p is not None and p.is_shard()
                for p in list(s.out_placements) + list(s.in_placements))
            sec = node_seconds(node)
            full_compute += sec
            compute += sec * (1.0 / self.world_size if sharded else 1.0)
        # full_compute is the SAME-BASIS replicate price: the outer solver
        # compares strat.compute_cost against the node's compute_proxy, so
        # both must come from one op-time model or replication wins by
        # accounting artifact alone
        return var_p, comm, compute, full_compute

    def _discover_scan(self, eqn):
        """Composite rule for `lax.scan`: analyze the body recursively, then
        solve the body graph once per seed input-dim with the carry threaded
        back to its init placeholder (a state_io edge prices the
        per-iteration reshard, so e.g. megatron TP's in-loop psum is priced,
        not forbidden).  Each surviving assignment becomes one shard group
        of the scan eqn whose `intrinsic_cost` = length x body collective
        seconds — the outer ILP weighs it against boundary resharding.

        Dim mapping: consts and carry rows map 1:1 into the body; xs/ys lose
        their leading scan axis (outer dim d <-> body dim d-1; dim 0 itself
        is the loop and never shards).

        Emission needs no body rewrite: constraining the outer scan operands
        (stacked params, init carry, xs) lets XLA's GSPMD partitioner
        propagate into the while loop and place the in-loop collectives —
        the standard rolled-layers form (MaxText/T5X style).
        """
        from easydist_tpu.autoflow import MeshAxisSpec, SpmdSolver
        from easydist_tpu.metashard.metair import Placement
        from .bridge import jaxpr_to_metagraph

        params = eqn.params
        num_consts = int(params.get("num_consts", 0))
        num_carry = int(params.get("num_carry", 0))
        length = int(params.get("length", 1))
        got = self._analyze_inner(params.get("jaxpr"))
        if got is None:
            return None
        inner, sub, rules, shape_info = got

        body_invars = inner.jaxpr.invars
        if len(eqn.invars) != len(body_invars):
            return None
        in_names = [sub.names.name(v) for v in body_invars]
        body_outvars = inner.jaxpr.outvars
        out_names = [None if isinstance(v, jex_core.Literal)
                     else sub.names.name(v) for v in body_outvars]

        # carry threading: body outvar k loops back into invar num_consts+k
        carry_io = {}
        for k in range(num_carry):
            if out_names[k] is not None:
                carry_io[out_names[k]] = in_names[num_consts + k]

        axis = MeshAxisSpec("_scan", self.world_size)
        carry_names = set(in_names[num_consts:num_consts + num_carry])

        def solve_with_seed(seed_name, seed_dim, carries_replicate=False):
            """Solve the body with the seed placeholder pinned; returns
            ({var name: Placement}, body comm seconds, compute) or None.
            `carries_replicate` pins every carry to R so weight seeds
            produce tensor-parallel assignments (otherwise free R->S slices
            let batch-sharding dominate every solve)."""
            return self._solve_body_pinned(
                inner, sub, rules, shape_info,
                pins={seed_name: Placement.shard(seed_dim)},
                state_io=carry_io,
                replicate_names=carry_names - {seed_name}
                if carries_replicate else ())

        # graph-edge rows: every non-Literal invar, in order (bridge.py
        # builds MetaNode.invars the same way)
        edge_invars = [i for i, v in enumerate(eqn.invars)
                       if not isinstance(v, jex_core.Literal)]
        n_xs_start = num_consts + num_carry
        strategies = []  # (in_placements, out_placements, cost)
        seen_keys = set()
        covered = set()  # (invar idx, outer dim) already sharded by a strat

        def extract(var_p):
            """Whole-body assignment -> (outer in placements, outer out
            placements) with xs/ys dims shifted past the scan axis."""
            ins = []
            for i in edge_invars:
                p = var_p.get(in_names[i])
                if p is None or not p.is_shard():
                    ins.append(Placement.replicate())
                    continue
                outer_dim = p.dim + 1 if i >= n_xs_start else p.dim
                shape = tuple(eqn.invars[i].aval.shape)
                if shape[outer_dim] % self.world_size != 0:
                    return None  # inconsistent mapping; be safe
                ins.append(Placement.shard(outer_dim))
            if all(p.is_replicate() for p in ins):
                return None
            outs = []
            for k, name in enumerate(out_names):
                if k < num_carry:
                    # authoritative carry placement is the init placeholder's
                    # (a mismatched body output pays its priced reshard
                    # inside the loop; GSPMD converges to the same fixed
                    # point at emission)
                    p = var_p.get(in_names[num_consts + k])
                    outs.append(p if p is not None and p.is_shard()
                                else Placement.replicate())
                else:
                    p = var_p.get(name) if name is not None else None
                    if p is None:
                        outs.append(Placement.replicate())
                    elif p.is_shard():
                        outs.append(Placement.shard(p.dim + 1))
                    elif p.is_partial():
                        outs.append(Placement.partial())
                    else:
                        outs.append(Placement.replicate())
            return ins, outs

        n_solves = 0
        full_body_compute = 0.0
        for i in edge_invars:
            v = eqn.invars[i]
            shape = tuple(v.aval.shape)
            numel = int(np.prod(shape)) if shape else 1
            if numel < self.world_size * 64:
                continue  # bias-sized: may ride along, never seeds
            is_xs = i >= n_xs_start
            is_carry = num_consts <= i < n_xs_start
            if not (is_carry or is_xs):
                continue  # hoisted consts ride along with carry seeds
            dim_range = range(1, len(shape)) if is_xs else range(len(shape))
            for outer_d in dim_range:
                if shape[outer_d] % self.world_size != 0 \
                        or shape[outer_d] < self.world_size:
                    continue
                if (i, outer_d) in covered:
                    continue  # already sharded by an earlier strategy
                if n_solves >= edconfig.scan_max_seed_solves:
                    break
                n_solves += 1
                body_d = outer_d - 1 if is_xs else outer_d
                res = solve_with_seed(in_names[i], body_d,
                                      carries_replicate=is_xs)
                if res is None:
                    continue
                full_body_compute = res[3]
                got = extract(res[0])
                if got is None:
                    continue
                ins, outs = got
                key = (tuple(repr(p) for p in ins),
                       tuple(repr(p) for p in outs))
                if key in seen_keys:
                    continue
                seen_keys.add(key)
                strategies.append((ins, outs, length * res[1],
                                   length * res[2]))
                for j, p in zip(edge_invars, ins):
                    if p.is_shard():
                        covered.add((j, p.dim))

        if not strategies:
            return None
        # full-compute proxy: the scan's work is length x the body's, far
        # more than its boundary bytes — without this the outer solver's
        # byte proxy under-prices replication and TP's intrinsic psum cost
        # would never be worth paying
        # same-basis replicate price (see _solve_body_pinned)
        compute = length * full_body_compute

        logger.debug("scan rule: %d whole-body strategies (body %d eqns, "
                    "length %d)", len(strategies), len(inner.jaxpr.eqns),
                    length)
        return {"space": None, "recombines": {},
                "strategies": strategies, "compute": compute}

    def _discover_cond(self, eqn):
        """Composite rule for `lax.cond`/`lax.switch`: every branch body is
        solved per seed input-dim; a whole-eqn strategy survives only when
        EVERY branch admits the identical boundary assignment (the branches
        share operands and output shapes, so a placement valid in one
        branch but not another would force an unpredictable runtime
        reshard).  Priced at the worst branch's collective cost — which
        branch runs is data-dependent.

        The reference never faces this: make_fx fully unrolls/flattens
        control flow so every op is visible
        (easydist/torch/compile.py:78-83); the TPU design keeps `cond`
        compiled (both branches live in the program) and constrains the
        outer operands, letting GSPMD propagate into the branches.
        """
        from easydist_tpu.metashard.metair import Placement

        branches = eqn.params.get("branches")
        if not branches:
            return None
        analyzed = []
        for br in branches:
            got = self._analyze_inner(br)
            if got is None:
                return None
            analyzed.append(got)
        operands = eqn.invars[1:]  # invar 0 is the branch index
        for inner_b, sub_b, _, _ in analyzed:
            if len(inner_b.jaxpr.invars) != len(operands):
                return None
        # operand indices each branch actually READS: cond unions the
        # branch closures, so every branch jaxpr is padded with the other
        # branches' captured weights as dead invars (a top-level invar
        # used anywhere must appear as a top-level eqn invar or outvar)
        used_sets = []
        for inner_b, _, _, _ in analyzed:
            used_vars = set()
            for be in inner_b.jaxpr.eqns:
                used_vars.update(bv for bv in be.invars
                                 if not isinstance(bv, jex_core.Literal))
            used_vars.update(bv for bv in inner_b.jaxpr.outvars
                             if not isinstance(bv, jex_core.Literal))
            used_sets.append({k for k, bv in enumerate(inner_b.jaxpr.invars)
                              if bv in used_vars})

        edge_invars = [i for i, v in enumerate(eqn.invars)
                       if not isinstance(v, jex_core.Literal)]
        strategies = []
        seen_keys = set()
        covered = set()
        n_solves = 0
        full_branch_compute = 0.0

        def branch_extract(inner_b, sub_b, var_p):
            in_names_b = [sub_b.names.name(v) for v in inner_b.jaxpr.invars]
            ins = []
            for i in edge_invars:
                if i == 0:  # branch index: scalar, always replicated
                    ins.append(Placement.replicate())
                    continue
                p = var_p.get(in_names_b[i - 1])
                if p is not None and p.is_shard():
                    shape = tuple(eqn.invars[i].aval.shape)
                    if shape[p.dim] % self.world_size != 0:
                        return None
                    ins.append(Placement.shard(p.dim))
                else:
                    ins.append(Placement.replicate())
            outs = []
            for v in inner_b.jaxpr.outvars:
                p = None if isinstance(v, jex_core.Literal) \
                    else var_p.get(sub_b.names.name(v))
                if p is not None and p.is_shard():
                    outs.append(Placement.shard(p.dim))
                elif p is not None and p.is_partial():
                    outs.append(Placement.partial())
                else:
                    outs.append(Placement.replicate())
            return ins, outs

        for j, v in enumerate(operands):
            shape = tuple(getattr(v.aval, "shape", ()))
            numel = int(np.prod(shape)) if shape else 1
            if isinstance(v, jex_core.Literal) \
                    or numel < self.world_size * 64:
                continue
            for d, size in enumerate(shape):
                if size % self.world_size != 0 or size < self.world_size:
                    continue
                if (j + 1, d) in covered:
                    continue
                if n_solves >= edconfig.scan_max_seed_solves:
                    break
                n_solves += 1
                per_branch = []
                for inner_b, sub_b, rules_b, shape_info_b in analyzed:
                    seed = sub_b.names.name(inner_b.jaxpr.invars[j])
                    res = self._solve_body_pinned(
                        inner_b, sub_b, rules_b, shape_info_b,
                        pins={seed: Placement.shard(d)})
                    if res is None:
                        break
                    got = branch_extract(inner_b, sub_b, res[0])
                    if got is None:
                        break
                    per_branch.append((got, res[1], res[2], res[3]))
                if len(per_branch) != len(analyzed):
                    continue
                # Join the per-branch boundaries treating operands a branch
                # never reads as don't-care: the body solver places a dead
                # invar arbitrarily, so demanding byte-identical boundary
                # keys rejects every seed whenever branches capture
                # different weights.  Disagreement on an operand some
                # branch actually reads still rejects the seed; an operand
                # no branch reads pins to replicate.
                joint_ins = []
                agree = True
                for pos, i in enumerate(edge_invars):
                    if i == 0:
                        joint_ins.append(Placement.replicate())
                        continue
                    picks_here = [per_branch[b][0][0][pos]
                                  for b in range(len(per_branch))
                                  if (i - 1) in used_sets[b]]
                    if len({repr(p) for p in picks_here}) > 1:
                        agree = False
                        break
                    joint_ins.append(picks_here[0] if picks_here
                                     else Placement.replicate())
                out_keys = {tuple(repr(p) for p in outs)
                            for (_, outs), _, _, _ in per_branch}
                if not agree or len(out_keys) != 1:
                    continue  # branches disagree on the boundary
                # fold the full-price compute only for solves that SURVIVED
                # the per-branch agreement check — a rejected solve's price
                # would skew the shard/replicate crossover the outer solver
                # compares against (ADVICE r5 #1)
                full_branch_compute = max(
                    [full_branch_compute] + [fc for _, _, _, fc in per_branch])
                ins, outs = joint_ins, per_branch[0][0][1]
                if all(p.is_replicate() for p in ins):
                    continue
                key = (tuple(repr(p) for p in ins), next(iter(out_keys)))
                if key in seen_keys:
                    continue
                seen_keys.add(key)
                comm = max(c for _, c, _, _ in per_branch)
                compute = max(c for _, _, c, _ in per_branch)
                strategies.append((ins, outs, comm, compute))
                for i, p in zip(edge_invars, ins):
                    if p.is_shard():
                        covered.add((i, p.dim))

        if not strategies:
            return None
        compute = full_branch_compute
        logger.debug("cond rule: %d whole-eqn strategies (%d branches)",
                    len(strategies), len(branches))
        return {"space": None, "recombines": {},
                "strategies": strategies, "compute": compute}

    def _discover_while(self, eqn):
        """Composite rule for `lax.while_loop`: the body is solved per
        carry seed with the carry threaded back to its init placeholder
        (scan's fixed-point treatment — a mismatched body output pays its
        priced in-loop reshard), and the COND jaxpr must then admit the
        chosen carry placements too (its own collectives are priced in —
        a `jnp.max(err) > tol` predicate over a sharded carry costs one
        small all-reduce per trip).  Trip count is unknown at trace time;
        `config.while_trip_estimate` scales the per-iteration price.
        Reference equivalent: full unrolling makes loops invisible
        (easydist/torch/compile.py:78-83); here the loop stays rolled.
        """
        from easydist_tpu.metashard.metair import Placement

        params = eqn.params
        n_cc = int(params.get("cond_nconsts", 0))
        n_bc = int(params.get("body_nconsts", 0))
        got_body = self._analyze_inner(params.get("body_jaxpr"))
        got_cond = self._analyze_inner(params.get("cond_jaxpr"))
        if got_body is None or got_cond is None:
            return None
        inner, sub, rules, shape_info = got_body
        cinner, csub, crules, cshape = got_cond

        body_invars = inner.jaxpr.invars  # [*body_consts, *carry]
        n_carry = len(body_invars) - n_bc
        if len(eqn.invars) != n_cc + n_bc + n_carry \
                or len(cinner.jaxpr.invars) != n_cc + n_carry:
            return None
        in_names = [sub.names.name(v) for v in body_invars]
        cond_in_names = [csub.names.name(v) for v in cinner.jaxpr.invars]
        out_names = [None if isinstance(v, jex_core.Literal)
                     else sub.names.name(v) for v in inner.jaxpr.outvars]
        carry_io = {}
        for k in range(n_carry):
            if out_names[k] is not None:
                carry_io[out_names[k]] = in_names[n_bc + k]

        edge_invars = [i for i, v in enumerate(eqn.invars)
                       if not isinstance(v, jex_core.Literal)]
        trips = float(edconfig.while_trip_estimate)
        strategies = []
        seen_keys = set()
        covered = set()
        n_solves = 0
        full_loop_compute = 0.0

        for k in range(n_carry):
            i = n_cc + n_bc + k  # absolute eqn invar index
            v = eqn.invars[i]
            shape = tuple(getattr(v.aval, "shape", ()))
            numel = int(np.prod(shape)) if shape else 1
            if isinstance(v, jex_core.Literal) \
                    or numel < self.world_size * 64:
                continue
            for d, size in enumerate(shape):
                if size % self.world_size != 0 or size < self.world_size:
                    continue
                if (i, d) in covered:
                    continue
                if n_solves >= edconfig.scan_max_seed_solves:
                    break
                n_solves += 1
                res = self._solve_body_pinned(
                    inner, sub, rules, shape_info,
                    pins={in_names[n_bc + k]: Placement.shard(d)},
                    state_io=carry_io)
                if res is None:
                    continue
                var_p, body_comm, body_compute, body_full = res
                full_loop_compute = body_full

                def carry_placement(kk):
                    p = var_p.get(in_names[n_bc + kk])
                    return p if p is not None else Placement.replicate()

                # the cond graph must run under these carry placements
                cond_pins = {}
                for kk in range(n_carry):
                    p = carry_placement(kk)
                    cond_pins[cond_in_names[n_cc + kk]] = (
                        Placement.shard(p.dim) if p.is_shard()
                        else Placement.replicate())
                # cond consts (loop bounds etc.) are reported replicated at
                # the emitted boundary (`ii < n_cc` below), so the
                # predicate solve must price them that way too — left
                # unpinned it could shard one and under-price the
                # crossover (ADVICE r5 #1; pricing only, never correctness)
                cres = self._solve_body_pinned(
                    cinner, csub, crules, cshape, pins=cond_pins,
                    replicate_names=tuple(cond_in_names[:n_cc]))
                if cres is None:
                    continue
                cond_comm = cres[1]

                ins = []
                ok = True
                for ii in edge_invars:
                    if ii < n_cc:  # cond consts: loop bounds etc, stay R
                        ins.append(Placement.replicate())
                        continue
                    if ii < n_cc + n_bc:
                        p = var_p.get(in_names[ii - n_cc])
                    else:
                        p = carry_placement(ii - n_cc - n_bc)
                    if p is not None and p.is_shard():
                        vshape = tuple(eqn.invars[ii].aval.shape)
                        if vshape[p.dim] % self.world_size != 0:
                            ok = False
                            break
                        ins.append(Placement.shard(p.dim))
                    else:
                        ins.append(Placement.replicate())
                if not ok or all(p.is_replicate() for p in ins):
                    continue
                # while outputs ARE the carry: same placements
                outs = [Placement.shard(carry_placement(kk).dim)
                        if carry_placement(kk).is_shard()
                        else Placement.replicate()
                        for kk in range(n_carry)]
                key = (tuple(repr(p) for p in ins),
                       tuple(repr(p) for p in outs))
                if key in seen_keys:
                    continue
                seen_keys.add(key)
                strategies.append((ins, outs,
                                   trips * (body_comm + cond_comm),
                                   trips * body_compute))
                for ii, p in zip(edge_invars, ins):
                    if p.is_shard():
                        covered.add((ii, p.dim))

        if not strategies:
            return None
        compute = trips * full_loop_compute
        logger.debug("while rule: %d whole-loop strategies (body %d eqns, "
                    "trip estimate %g)", len(strategies),
                    len(inner.jaxpr.eqns), trips)
        return {"space": None, "recombines": {},
                "strategies": strategies, "compute": compute}

    def _discover_shrunk(self, eqn, bind_fn, bind_params, prim_name):
        """Discovery on a size-reduced instance of the eqn, or None if the
        primitive rejects the shrunk shapes (shape-dependent params)."""
        import types

        cap = edconfig.discovery_hint_numel
        unit = max(self.world_size * edconfig.discovery_nshards, 8)
        sizes = sorted({d for v in list(eqn.invars) + list(eqn.outvars)
                        if hasattr(getattr(v, "aval", None), "shape")
                        for d in v.aval.shape if d > unit}, reverse=True)

        def shrunk_total(size_map):
            # inputs AND outputs: an output-dominated op (big matmul result)
            # must shrink too, or discovery materializes it full-size
            t = 0
            for v in list(eqn.invars) + list(eqn.outvars):
                if isinstance(v, jex_core.Literal) \
                        or not hasattr(getattr(v, "aval", None), "shape"):
                    continue
                t += int(np.prod([size_map.get(d, d) for d in v.aval.shape]))
            return t

        size_map = {}
        # halve the largest mapped sizes (to a multiple of `unit`) until the
        # inputs fit the hint budget
        for _ in range(64):
            if shrunk_total(size_map) <= cap:
                break
            grew = False
            for d in sizes:
                cur = size_map.get(d, d)
                nxt = max((cur // 2) // unit * unit, unit)
                if nxt < cur:
                    size_map[d] = nxt
                    grew = True
                    break
            if not grew:
                return None
        if not size_map:
            return None

        with jax.default_device(
                jax.local_devices(backend="cpu")[0]
                if edconfig.discovery_on_cpu else jax.devices()[0]):
            invals = []
            for v in eqn.invars:
                if isinstance(v, jex_core.Literal):
                    invals.append(v.val)
                    continue
                aval = v.aval
                shape = tuple(size_map.get(d, d) for d in aval.shape)
                invals.append(_materialize(
                    types.SimpleNamespace(shape=shape, dtype=aval.dtype),
                    self._next_key()))
            try:
                bind_fn(*invals, **bind_params)  # params consistent?
            except Exception:
                return None
            op = MetaOp(bind_fn, tuple(invals), kwargs=bind_params,
                        name=prim_name)
            try:
                space, recombines = op.discover(
                    prompt=self.prompts.get(prim_name))
            except Exception:
                return None
        if prim_name not in self.prompts and space.max_group() > 0:
            self.prompts[prim_name] = space
        return {"space": space, "recombines": recombines}


# ops through which a partial-sum placement propagates linearly: f(sum_i x_i)
# == sum_i f(x_i) when every other operand is replicated.  Used only inside
# composite (jax.checkpoint body) solves, where a partial may travel to the
# composite boundary and become a reduce recombine — e.g. a bias gradient's
# reduce_sum inside a differentiated remat body.
_PARTIAL_LINEAR_1IN = {"reshape", "transpose", "convert_element_type",
                       "squeeze", "expand_dims", "broadcast_in_dim", "neg",
                       "rev", "slice", "reduce_sum", "copy"}
_PARTIAL_LINEAR_2IN = {"mul", "div", "dot_general"}


def _inject_partial_propagation(graph, world_size: int) -> None:
    # NOTE: mul-by-LITERAL (n_in == 1) deliberately gets no P-passthrough.
    # Scaling by a constant is linear, but injecting it lets P ride into
    # loss-scale and optimizer-update chains where deferral is byte-neutral
    # at best — measured: a worse near-tie on the dp MLP (liveness +56%)
    # and 37 extra all-to-alls on the remat-policy GPT twin.  Revisit once
    # fence costs are priced inside the ILP rather than post-hoc.
    from easydist_tpu.metashard.metair import NodeStrategy, Placement

    par = Placement.partial()
    rep = Placement.replicate()
    for node in graph.ops:
        base = node.strategy_pool(world_size)  # builds _pool_cache
        if not base or node._pool_cache is None:
            continue
        template = base[0]
        n_in = len(template.in_placements)
        n_out = len(template.out_placements)
        extras = []
        if node.op_key in _PARTIAL_LINEAR_1IN and n_in >= 1:
            # partial rides the first (data) operand; any trailing operands
            # must be replicated
            extras.append(NodeStrategy([par] + [rep] * (n_in - 1),
                                       [par] * n_out))
        elif node.op_key in _PARTIAL_LINEAR_2IN and n_in == 2:
            extras.append(NodeStrategy([par, rep], [par] * n_out))
            if node.op_key != "div":  # div is linear in the numerator only
                extras.append(NodeStrategy([rep, par], [par] * n_out))
        node._pool_cache = node._pool_cache + extras
