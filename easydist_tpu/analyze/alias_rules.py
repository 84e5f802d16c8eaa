"""Layer 11: donation/aliasing sanitizer.

Every hot serving path leans on buffer donation for in-place XLA updates
— the paged decode arena (arg0 <-> out0), the chunked-prefill staging
caches, the speculative verify step — but tier-1 runs JAX_PLATFORMS=cpu,
where JAX silently IGNORES donation.  A use-after-donate or a
double-donate therefore passes every CPU test bitwise and corrupts HBM
silently on real TPUs.  This layer catches the hazard statically, at
three altitudes:

ALIAS001 — use of a donated invar after its consuming dispatch.  Two
    forms: (a) a traced driver program whose inner `pjit` equation
    donates a var that a LATER equation (or the program output) still
    reads; (b) the `ast` host-code lint (`lint_host_donation`), which
    flags a retained Python reference to a donated argument loaded
    after the donating call without an intervening rebind.  The repo's
    rebind idiom — `pool.cache, tok = self._decode_c(pool.cache, ...)`
    — is the clean shape: the Store on the call's own statement retires
    the stale reference immediately.

ALIAS002 — double donation: one underlying buffer donated through two
    invar positions of one dispatch, or two state outputs claiming the
    same donated input (`state_pairs` with duplicate input indices).
    XLA hands the storage out twice; whichever write lands second
    clobbers the other.

ALIAS003 — donation declared but unhonorable: the donated input matches
    no output's shape/dtype, so XLA silently COPIES instead of aliasing
    (the `jax.jit` runtime only warns, and only on backends that honor
    donation at all).  The in-place economics the donation was written
    for never happen; at cache scale that is a full HBM copy per step.

ALIAS004 — a donated device buffer still reachable from a live host
    reference across a step boundary: an inflight snapshot, a hot-page
    export, or a prefix-trie node holding a staging row by reference
    rather than by copy.  The next donating dispatch invalidates
    storage the host still intends to read.  The check is identity
    based (`is` over array leaves), run by `serve.generation` at the
    same checkpoint as the donation audits.

The AST lint intentionally reasons per function scope and in source-line
order (no interprocedural or loop-carried dataflow): the donation
convention here is strictly local — compiled callables named `*_c` (or
bound from `easydist_compile(...)`) donate positional arg 0 — so a
scope-local "donate, then load without rebind" walk catches the real
bug class without drowning the driver's baseline in speculative flow
analysis.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from .findings import Finding, make_finding

# ----------------------------------------------------------- jaxpr pass


def _donated_flags(eqn) -> Tuple[bool, ...]:
    """The eqn's donation vector, aligned with its invars (pjit carries
    `donated_invars`; every other primitive donates nothing)."""
    params = getattr(eqn, "params", None)
    if not isinstance(params, dict):
        return ()
    flags = params.get("donated_invars")
    if not flags:
        return ()
    return tuple(bool(b) for b in flags)


def _sub_jaxprs(eqn):
    for param in getattr(eqn, "params", {}).values():
        if hasattr(param, "jaxpr"):
            yield param.jaxpr
        elif isinstance(param, (list, tuple)):
            for p in param:
                if hasattr(p, "jaxpr"):
                    yield p.jaxpr


def _aval_sig(var):
    aval = getattr(var, "aval", None)
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")))


def audit_jaxpr_donation(jaxpr, node: str = "program",
                         check_unhonored: bool = True) -> List[Finding]:
    """ALIAS001/002/003 over one (possibly nested) jaxpr: for every
    equation carrying a `donated_invars` vector,

    * ALIAS001 — a donated var read by any LATER equation or appearing
      in the enclosing jaxpr's outvars (the dispatch freed it; the
      program still uses it);
    * ALIAS002 — one var bound to two invar positions of the same
      equation with at least one position donated (the buffer aliases
      itself across the dispatch boundary);
    * ALIAS003 — a donated invar whose shape/dtype matches NO output of
      its equation (nothing can alias it, so XLA silently copies).

    Recurses into sub-jaxprs (pjit/cond/scan bodies).  One finding per
    (equation, hazard) — a var both double-donated and reused later
    reports each hazard once, not per use.  `check_unhonored=False`
    skips the ALIAS003 arm (CompileResult.analyze passes it because
    `audit_donation_pairs` already audits the top-level dispatch's
    honorability with the state-pair context attached).
    """
    from jax._src import core as jex_core

    findings: List[Finding] = []
    eqns = list(jaxpr.eqns)
    out_vars = [v for v in jaxpr.outvars
                if not isinstance(v, jex_core.Literal)]
    for k, eqn in enumerate(eqns):
        flags = _donated_flags(eqn)
        if any(flags):
            prim = getattr(eqn.primitive, "name", "eqn")
            invars = list(eqn.invars)
            donated = [(i, invars[i]) for i, f in enumerate(flags)
                       if f and i < len(invars)
                       and not isinstance(invars[i], jex_core.Literal)]
            # ALIAS002: one var, >=2 invar positions, >=1 donated
            seen_dup = set()
            for i, v in donated:
                if v in seen_dup:
                    continue
                positions = [j for j, u in enumerate(invars) if u is v]
                if len(positions) > 1:
                    seen_dup.add(v)
                    findings.append(make_finding(
                        "ALIAS002", node,
                        f"eqn {k} ({prim}): var {v} feeds invar positions "
                        f"{positions} with position {i} donated — XLA may "
                        f"overwrite the buffer while another operand "
                        f"still reads it"))
            # ALIAS001: donated var alive after the dispatch
            later_reads = set()
            for later in eqns[k + 1:]:
                later_reads.update(u for u in later.invars
                                   if not isinstance(u, jex_core.Literal))
            for i, v in donated:
                if v in later_reads or any(v is o for o in out_vars):
                    where = ("the program output" if any(
                        v is o for o in out_vars) else "a later equation")
                    findings.append(make_finding(
                        "ALIAS001", node,
                        f"eqn {k} ({prim}) donates invar {i} ({v}: "
                        f"{_aval_sig(v)[0]} {_aval_sig(v)[1]}) but "
                        f"{where} still reads it — bitwise-correct on "
                        f"CPU, silently corrupt where donation is "
                        f"honored"))
            # ALIAS003: donated invar with no alias-compatible output
            out_sigs = [_aval_sig(o) for o in eqn.outvars]
            for i, v in (donated if check_unhonored else ()):
                if _aval_sig(v) not in out_sigs:
                    findings.append(make_finding(
                        "ALIAS003", node,
                        f"eqn {k} ({prim}) donates invar {i} "
                        f"({_aval_sig(v)[0]} {_aval_sig(v)[1]}) but no "
                        f"output matches its shape/dtype — XLA silently "
                        f"copies instead of updating in place"))
        for sub in _sub_jaxprs(eqn):
            findings.extend(audit_jaxpr_donation(
                sub, node=node, check_unhonored=check_unhonored))
    return findings


# ---------------------------------------------------- CompileResult pass


def audit_donation_pairs(result, node: str = "compile") -> List[Finding]:
    """ALIAS002/003 over a CompileResult's state-threading declaration
    (`state_pairs`: flat output index -> flat input index, recorded by
    `_finish_compile`):

    * ALIAS002 — two outputs claim the same donated input (the donate
      set dedupes, so XLA sees one donation, but both callers believe
      they own the storage);
    * ALIAS003 — a pair whose output/input shape or dtype disagree, or
      whose indices fall outside the signature: the donation cannot be
      honored and XLA silently copies.  `infer_state_io`'s positional
      pairing cannot produce this (it requires identical leaf
      signatures); only an explicit `state_io` dict can.
    """
    pairs: Dict[int, int] = dict(getattr(result, "state_pairs", None) or {})
    donated = set(getattr(result, "donated_invars", ()) or ())
    if not pairs or not donated:
        return []
    findings: List[Finding] = []
    by_input: Dict[int, List[int]] = {}
    for out_idx, in_idx in pairs.items():
        by_input.setdefault(in_idx, []).append(out_idx)
    for in_idx, outs in sorted(by_input.items()):
        if in_idx in donated and len(outs) > 1:
            findings.append(make_finding(
                "ALIAS002", node,
                f"outputs {sorted(outs)} all claim donated input "
                f"{in_idx}: the buffer is handed out twice and one "
                f"state write clobbers the other"))
    in_avals = list(getattr(result, "in_avals", ()) or ())
    closed = getattr(result, "closed_jaxpr", None)
    out_avals = list(getattr(closed, "out_avals", ()) or ())
    for out_idx, in_idx in sorted(pairs.items()):
        if in_idx not in donated:
            continue
        if in_idx >= len(in_avals) or (out_avals
                                       and out_idx >= len(out_avals)):
            findings.append(make_finding(
                "ALIAS003", node,
                f"state pair out[{out_idx}] <- in[{in_idx}] indexes "
                f"outside the signature ({len(out_avals)} outputs, "
                f"{len(in_avals)} inputs): the declared donation can "
                f"never be honored"))
            continue
        if not out_avals:
            continue
        i_sig = (tuple(in_avals[in_idx].shape),
                 str(in_avals[in_idx].dtype))
        o_sig = (tuple(out_avals[out_idx].shape),
                 str(out_avals[out_idx].dtype))
        if i_sig != o_sig:
            findings.append(make_finding(
                "ALIAS003", node,
                f"state pair out[{out_idx}] {o_sig[0]} {o_sig[1]} <- "
                f"in[{in_idx}] {i_sig[0]} {i_sig[1]}: shape/dtype "
                f"mismatch, so XLA silently copies instead of donating "
                f"in place"))
    return findings


# ------------------------------------------------------ host-alias pass


def _array_leaves(tree) -> List[object]:
    """Array-like leaves only: identity comparison over Python scalars
    would false-positive on interned ints."""
    import jax

    return [l for l in jax.tree_util.tree_leaves(tree)
            if hasattr(l, "shape") and hasattr(l, "dtype")]


def audit_host_aliases(donated, holders,
                       node: str = "session") -> List[Finding]:
    """ALIAS004: identity overlap between donated device buffers and
    live host-held references.  `donated` maps a label (e.g. "cache",
    "staging", "arena") to a pytree whose array leaves the next
    dispatch will donate; `holders` maps a holder label (e.g.
    "snapshot", "trie", "hot_pages") to a pytree the host retains
    across the step boundary.  A holder leaf that IS (object identity)
    a donated leaf fires one aggregated finding per holder — the trie
    must hold page INDICES (or copies), never the donated arrays
    themselves.
    """
    donated_ids: Dict[int, str] = {}
    for label, tree in donated.items():
        for leaf in _array_leaves(tree):
            donated_ids.setdefault(id(leaf), label)
    if not donated_ids:
        return []
    findings: List[Finding] = []
    for holder, tree in holders.items():
        hit_labels = sorted({donated_ids[id(leaf)]
                             for leaf in _array_leaves(tree)
                             if id(leaf) in donated_ids})
        if hit_labels:
            findings.append(make_finding(
                "ALIAS004", node,
                f"host holder {holder!r} retains a reference to donated "
                f"buffer(s) {hit_labels} across the step boundary — the "
                f"next donating dispatch invalidates storage the host "
                f"still reads (hold a copy or an index, not the array)"))
    return findings


# ------------------------------------------------------- AST host lint

# a callee is "donating" when its terminal name matches this (the
# session's compiled-callable convention: _decode_c, _prefill_chunk_c,
# _paged_c("decode")(...), ...) or when it is a name bound from
# easydist_compile(...) in the same scope
_DONATING_NAME_RE = re.compile(r"^_[a-z0-9_]*_c$")
_COMPILE_FACTORIES = {"easydist_compile", "compile_step"}


def _callee_name(func_node) -> Optional[str]:
    if isinstance(func_node, ast.Attribute):
        return func_node.attr
    if isinstance(func_node, ast.Name):
        return func_node.id
    return None


def _expr_key(node) -> Optional[str]:
    """Stable identity of a Name/Attribute-chain expression (`buf`,
    `pool.cache`, `self.pool.staging`); None for anything else — only
    plain reference chains participate in the retained-reference walk."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _expr_key(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


class _ScopeLint(ast.NodeVisitor):
    """One function scope's donate/store/load event streams, in source
    order.  Nested defs get their own scope (their bodies are skipped
    here and visited separately)."""

    def __init__(self):
        self.donates: List[Tuple[int, int, str]] = []  # (line, end, expr)
        self.stores: Dict[str, List[int]] = {}         # expr -> lines
        self.loads: Dict[str, List[Tuple[int, str]]] = {}
        self.compiled_names: set = set()

    # a nested def is its own scope (collected and visited separately);
    # class bodies stay in the enclosing stream so class-level wiring
    # still participates
    def visit_FunctionDef(self, node):
        pass

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):
        # x = easydist_compile(...) binds a donating callable
        if isinstance(node.value, ast.Call):
            name = _callee_name(node.value.func)
            if name in _COMPILE_FACTORIES:
                for tgt in node.targets:
                    key = _expr_key(tgt)
                    if key:
                        self.compiled_names.add(key)
        self.generic_visit(node)

    def _is_donating_call(self, call: ast.Call) -> bool:
        name = _callee_name(call.func)
        if name is not None:
            return (_DONATING_NAME_RE.match(name) is not None
                    or _expr_key(call.func) in self.compiled_names
                    or name in self.compiled_names)
        if isinstance(call.func, ast.Call):
            # self._paged_c("decode")(arena, ...): the factory matched,
            # the returned callable donates
            inner = _callee_name(call.func.func)
            return (inner is not None
                    and _DONATING_NAME_RE.match(inner) is not None)
        return False

    def visit_Call(self, call):
        if self._is_donating_call(call) and call.args:
            key = _expr_key(call.args[0])
            if key is not None:
                end = getattr(call, "end_lineno", None) or call.lineno
                self.donates.append((call.lineno, end, key))
        self.generic_visit(call)

    def visit_Name(self, node):
        self._record(node, node.lineno)

    def visit_Attribute(self, node):
        key = _expr_key(node)
        if key is not None:
            self._record_key(node, key, node.lineno)
            return  # the chain is one event, not one per attribute hop
        self.generic_visit(node)

    def _record(self, node, line):
        key = _expr_key(node)
        if key is not None:
            self._record_key(node, key, line)

    def _record_key(self, node, key, line):
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            self.stores.setdefault(key, []).append(line)
        else:
            self.loads.setdefault(key, []).append((line, key))


def _scope_findings(scope: _ScopeLint, path: str,
                    node_label: str) -> List[Finding]:
    findings: List[Finding] = []
    for don_line, don_end, key in scope.donates:
        # the donation is live until the first rebind at or after the
        # donating statement (same-line rebind = the clean idiom); loads
        # inside the donating call's own line span ARE the call's
        # arguments, not stale reads
        rebinds = [ln for ln in scope.stores.get(key, ())
                   if ln >= don_line]
        first_rebind = min(rebinds) if rebinds else None
        stale = [ln for ln, _ in scope.loads.get(key, ())
                 if ln > don_end
                 and (first_rebind is None or ln < first_rebind)]
        if stale:
            line = min(stale)
            findings.append(make_finding(
                "ALIAS001", node_label,
                f"`{key}` is read after being donated on line "
                f"{don_line} with no intervening rebind — on donating "
                f"backends that storage is already invalid",
                path=path, line=line))
    return findings


def lint_file_donation(path: str, rel: Optional[str] = None,
                       source: Optional[str] = None) -> List[Finding]:
    """AST ALIAS001 host lint over one Python file.  Returns [] for
    unparsable files (the lint must never be the thing that fails)."""
    rel = rel or path
    if source is None:
        try:
            with open(path, "r", encoding="utf-8") as f:
                source = f.read()
        except OSError:
            return []
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError:
        return []
    findings: List[Finding] = []
    # module scope + every function scope, each analyzed independently
    scopes: List[Tuple[ast.AST, str]] = [(tree, "<module>")]
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes.append((n, n.name))
    for scope_node, label in scopes:
        lint = _ScopeLint()
        for stmt in scope_node.body:
            lint.visit(stmt)
        findings.extend(_scope_findings(lint, rel, f"{rel}:{label}"))
    return findings


def lint_host_donation(root: str,
                       subdirs: Iterable[str] = ("easydist_tpu",
                                                 "examples"),
                       ) -> List[Finding]:
    """The ALIAS001 host lint over every .py file beneath
    `root/<subdir>` (repo-relative paths on the findings, so baselines
    travel)."""
    findings: List[Finding] = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, root)
                findings.extend(lint_file_donation(full, rel=rel))
    return findings
