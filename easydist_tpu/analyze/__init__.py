"""`easydist_tpu.analyze`: static SPMD strategy, collective, memory &
schedule verifier.

A rule-based analyzer that runs after solving and before execution
(DistIR-style static checking over a typed distributed IR):

  layer 1  strategy verifier over solved MetaIR (`verify_axis`,
           `audit_solver_objective`) — placement typing, S-dim validity,
           PARTIAL resolution, solver objective audit;
  layer 2  collective-program linter over emitted jaxprs and comm plans
           (`lint_jaxpr`, `lint_fn`, `lint_bucket_plan`) — axis
           existence, cond-branch deadlock shapes, bucket tiling, int8
           accumulation;
  layer 3  memory-plan & pipeline-schedule verifier
           (`verify_memory_plan`, `check_hbm_budget`, `audit_remat_plan`,
           `verify_schedule_tables`) — independent liveness/sizing audit
           of the graph memory plan, skyline soundness, the MEM004 HBM
           budget gate with its remat advisory, the remat-rewrite audit,
           and deadlock/stash-bound/bubble checks over pipeline tick
           schedules;
  layer 4  resilience auditor (`audit_guard_parity`,
           `audit_checkpoint_root`) — guard-off jaxpr parity (RES001) and
           checkpoint commit-protocol integrity over a checkpoint root
           (RES002 corrupt COMMITTED, RES003 stale debris);
  layer 5  serving auditor (`audit_decode_donation`,
           `audit_chunked_prefill`, `audit_prefix_cache`) — the SERVE001
           decode-step KV-cache donation lint (a non-donated cache turns
           every generated token into a full-cache HBM copy) and the
           SERVE002 chunked-prefill contract lint (staging donation,
           length-masked attention over the full bucket window so stale
           cache rows cannot leak into live logits, prefix-trie
           refcount/byte-accounting integrity) and the SERVE003
           speculative-rewind contract lint
           (`audit_speculative_rewind`: verify-step length masking,
           accept-walk bookkeeping never past the first mismatch,
           rollback leaves no table row on a released page);
  layer 7  paged-KV auditor (`audit_page_table`) — KV001 cross-checks
           the paged decode cache's host bookkeeping (kv/pool.py page
           refcounts, kv/table.py slot->page tables, prefix-trie page
           references): a freed page under a live table entry, a page
           with more holders than refcount, double frees, leaked pages,
           or byte-conservation drift all mean one sequence silently
           reads or reuses another's K/V;
  layer 6  fleet auditor (`audit_routing`, `audit_page_handoff`,
           `audit_drained_session`) — multi-replica serving hygiene:
           FLEET001 routing into a tripped-breaker/draining replica,
           FLEET002 KV page handoffs whose payload disagrees with the
           sha256 manifest, FLEET004 dispatch to a DEAD replica,
           FLEET005 resume descriptors that would break bitwise
           recovery, FLEET003 orphaned pinned trie pages left
           behind by a drain;
  layer 8  redistribution auditor (`audit_reshard_plan`,
           `audit_restored_state`) — RESHARD001 a chunked
           redistribution plan whose peak live bytes exceed the
           O(max(src_shard, dst_shard) + chunk) bound (silent
           degeneration to global materialization — the elastic-restore
           OOM), RESHARD002 a restored leaf whose sharding disagrees
           with the restore template's spec;
  layer 9  simulator/autoscaler auditor (`audit_prediction`,
           `audit_scale_decisions`, analyze/sim_rules.py) — SIM001 a
           simulator prediction whose relative error against a measured
           bench actual exceeds the committed bound
           (sim.simulate.SIM_REL_ERROR_BOUND) — the capacity planner
           and autoscaler would steer the fleet on numbers the hardware
           no longer agrees with; SIM002 autoscaler flap — opposite-
           direction scale actuations inside the hysteresis window (an
           A-B-A oscillation), each reversal paying a drain +
           page-migration + spin-up round trip for nothing;
  layer 10 pruned-discovery auditor (`audit_rule_transfer`,
           analyze/discovery_rules.py) — DISC001 a propagation-group or
           rule-cache transfer that instantiated a representative rule
           the member's shapes cannot carry (row/rank mismatch, halo
           wider than a member shard, size-sensitive rule across
           non-identical shapes); DISC002 execution discovery firing for
           a primitive whose analytic preset declined the instance;
  layer 11 donation/aliasing sanitizer (`audit_jaxpr_donation`,
           `audit_donation_pairs`, `audit_host_aliases`,
           `lint_host_donation`, analyze/alias_rules.py) — tier-1 runs
           JAX_PLATFORMS=cpu where JAX silently IGNORES buffer
           donation, so a use-after-donate passes every CPU test
           bitwise and corrupts HBM on real TPUs: ALIAS001 a donated
           invar read after its consuming dispatch (jaxpr form and the
           `ast` host-code lint over retained Python references),
           ALIAS002 one buffer donated through two positions / two
           state outputs claiming one donated input, ALIAS003 a
           donation XLA cannot honor (shape/dtype mismatch with every
           output — the silent-copy case), ALIAS004 a donated device
           buffer still reachable from a live host reference across a
           step boundary (snapshots, hot-page exports, trie-held rows);
  layer 12 fleet protocol model checker + concurrency sanitizer
           (`audit_spec`, `check_protocol_specs`,
           `check_protocol_conformance`, analyze/modelcheck.py +
           analyze/protocol_rules.py) — an explicit-state explorer over
           deterministic specs of the four fleet protocols
           (HealthMonitor ALIVE/SUSPECT/DEAD, FleetRouter
           drain/handoff/failover, ResumeDescriptor token-position
           commit, KVTransport chunked idempotent retry) enumerating
           EVERY interleaving of crash/duplicate/reorder/stall at small
           committed scope: PROTO001 a safety violation (false DEAD,
           double completion, double-commit) with the shortest
           counterexample trace attached, PROTO002 a reachable stuck
           state from which the goal is unreachable, PROTO003 drift
           between a live component's recorded `transitions()` stream
           (fleet/elastic drill logs replayed in CI) and the spec's
           admitted behavior; plus the host-code concurrency lint —
           PROTO004 a read of private fleet state across an object
           boundary, PROTO005 a mutation of a shared fleet structure
           outside its owning class (observers must consume snapshot
           surfaces; single-writer is what keeps the specs faithful);
  layer 13 quantized/tiered-KV sanitizer (`audit_quant_arena`,
           `audit_quant_program`, `audit_tier_roundtrip`,
           analyze/kv_quant_rules.py) — KVQ001 a block-scaled int8
           arena whose scale leaves are missing, mis-typed, or do not
           block-partition their payload (dequant would broadcast the
           wrong scales, bitwise-silently), KVQ002 a compiled paged
           step feeding int8 K/V into a `dot_general` without the
           dequant convert (logits off by the per-block scale), KVQ003
           a host-tier entry whose stored bytes fail their sha256
           manifest or whose byte accounting drifted (promotion would
           serve corrupt K/V).

Surfaced via `CompiledFunction.analyze()`, `bench.py --analyze`, the
dryrun gate, and the analyzer driver (`python -m easydist_tpu.analyze`:
inline suppressions, committed baseline, SARIF/JSON export, incremental
result cache — analyze/driver.py); findings export through the runtime
PerfDB under `("analyze_stats", <sub_key>)`.  Error-severity findings
raise by default (`EASYDIST_ANALYZE_RAISE=0` is the escape hatch;
`EASYDIST_ANALYZE=0` skips every layer); rule catalog in
docs/ANALYZE.md.
"""

from __future__ import annotations

import logging

from .alias_rules import (audit_donation_pairs, audit_host_aliases,
                          audit_jaxpr_donation, lint_file_donation,
                          lint_host_donation)
from .findings import (LAYERS, RULES, SEV_INFO, AnalysisError,
                       AnalysisReport, Finding, layer_of, make_finding,
                       rule_index_rows)
from .fleet_rules import (audit_drained_session, audit_page_handoff,
                          audit_resume, audit_routing)
from .jaxpr_rules import lint_bucket_plan, lint_fn, lint_jaxpr
from .kv_quant_rules import (audit_quant_arena, audit_quant_program,
                             audit_tier_roundtrip)
from .kv_rules import audit_page_table
from .modelcheck import (ALL_SPECS, COMMITTED_STATES, HealthSpec,
                         ResumeSpec, RouterSpec, Spec, TransportSpec,
                         audit_spec, explore, replay_health_events,
                         replay_restore_attempts,
                         replay_router_protocol,
                         replay_transport_commits)
from .protocol_rules import lint_file_concurrency, lint_host_concurrency
from .memory_rules import (audit_remat_plan, check_hbm_budget,
                           recompute_liveness, remat_advisory,
                           resolve_hbm_budget, verify_memory_plan)
from .overlap_rules import (lint_overlap_fn, lint_overlap_jaxpr,
                            lint_overlap_plan)
from .discovery_rules import audit_rule_transfer
from .reshard_rules import audit_reshard_plan, audit_restored_state
from .resilience_rules import (audit_checkpoint_root, audit_guard_parity,
                               guard_off_jaxpr)
from .schedule_rules import (gpipe_schedule_tables, schedule_stats,
                             verify_schedule_tables)
from .serve_rules import (audit_chunked_prefill, audit_decode_donation,
                          audit_prefix_cache, audit_speculative_rewind)
from .sim_rules import audit_prediction, audit_scale_decisions
from .strategy_rules import audit_solver_objective, verify_axis

logger = logging.getLogger(__name__)

__all__ = [
    "RULES", "AnalysisError", "AnalysisReport", "Finding", "make_finding",
    "lint_bucket_plan", "lint_fn", "lint_jaxpr",
    "audit_solver_objective", "verify_axis", "check_bucket_plan",
    "verify_memory_plan", "check_hbm_budget", "audit_remat_plan",
    "recompute_liveness", "remat_advisory", "resolve_hbm_budget",
    "verify_schedule_tables", "gpipe_schedule_tables", "schedule_stats",
    "check_schedule_tables",
    "lint_overlap_plan", "lint_overlap_jaxpr", "lint_overlap_fn",
    "check_overlap_plan",
    "audit_guard_parity", "audit_checkpoint_root", "guard_off_jaxpr",
    "audit_decode_donation", "check_decode_donation",
    "audit_chunked_prefill", "audit_prefix_cache",
    "check_chunked_prefill", "check_prefix_cache",
    "audit_speculative_rewind", "check_speculative_rewind",
    "audit_routing", "audit_page_handoff", "audit_drained_session",
    "audit_resume",
    "check_fleet_routing", "check_page_handoff", "check_fleet_drain",
    "check_resume_descriptor",
    "audit_page_table", "check_page_table",
    "audit_quant_arena", "audit_quant_program", "audit_tier_roundtrip",
    "check_quant_arena", "check_quant_program", "check_tier_roundtrip",
    "audit_reshard_plan", "audit_restored_state",
    "check_reshard_plan", "check_restored_state",
    "audit_prediction", "audit_scale_decisions",
    "check_sim_prediction", "check_sim_autoscale",
    "audit_rule_transfer",
    "audit_jaxpr_donation", "audit_donation_pairs",
    "audit_host_aliases", "lint_host_donation", "lint_file_donation",
    "check_donation_pairs", "check_host_aliases",
    "Spec", "HealthSpec", "RouterSpec", "ResumeSpec", "TransportSpec",
    "ALL_SPECS", "COMMITTED_STATES", "explore", "audit_spec",
    "replay_health_events", "replay_router_protocol",
    "replay_transport_commits", "replay_restore_attempts",
    "lint_file_concurrency", "lint_host_concurrency",
    "check_protocol_specs", "check_protocol_conformance",
    "LAYERS", "layer_of", "rule_index_rows",
]


def _enabled() -> bool:
    """The layer kill switch (EASYDIST_ANALYZE=0): every check_* hook
    returns empty without computing anything when analysis is off."""
    from easydist_tpu import config as edconfig

    return edconfig.enable_analyze


def check_bucket_plan(leaves, buckets) -> None:
    """Trace-time self-check hook for `comm.bucketer`: lint the plan and
    raise (or log, with the escape hatch) on error findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return
    findings = lint_bucket_plan(leaves, buckets)
    if not findings:
        return
    report = AnalysisReport(findings)
    if edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)


def check_overlap_plan(leaves, order, buckets=None) -> None:
    """Trace-time self-check hook for `comm.overlap`: validate the
    emission-order permutation and the reordered bucket plan, raising (or
    logging, with the escape hatch) on error findings.  `leaves` are the
    ORDERED leaves when `buckets` is given."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return
    findings = lint_overlap_plan(leaves, order, buckets)
    if not findings:
        return
    report = AnalysisReport(findings)
    if edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)


def check_schedule_tables(tables, n_stages: int, n_virtual: int,
                          n_microbatches: int, fwd_only: bool = False,
                          node: str = "pipeline") -> None:
    """Build-time self-check hook for the pipeline schedule builders
    (`parallel/pipeline.py`, `parallel/auto_pipeline.py`): verify the tick
    tables and raise (or log, with the escape hatch) on error findings.
    Warning/info findings (the SCHED003 bubble report) only log."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return
    findings = verify_schedule_tables(tables, n_stages, n_virtual,
                                      n_microbatches, fwd_only=fwd_only,
                                      node=node)
    if not findings:
        return
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.log(logging.INFO if f.severity == SEV_INFO
                   else logging.WARNING, "[analyze] %s", f)


def check_decode_donation(result, cache_arg: int = 0,
                          node: str = "decode"):
    """Compile-time self-check hook for `serve.generation`: audit the
    compiled decode step's cache donation (SERVE001, warning severity —
    logs, never raises; a non-donated cache is slow, not wrong).
    Returns the findings so callers/tests can assert on them."""
    if not _enabled():
        return []
    findings = audit_decode_donation(result, cache_arg=cache_arg,
                                     node=node)
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_chunked_prefill(result, cache_arg: int = 0,
                          node: str = "prefill_chunk"):
    """Compile-time self-check hook for the chunked-prefill scheduler:
    audit staging donation (warning — slow) and the length-mask (error —
    stale-row leakage).  Error findings raise under `analyze_raise`
    (missing mask means WRONG tokens, not slow ones); warnings log.
    Returns the findings so callers/tests can assert on them."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_chunked_prefill(result, cache_arg=cache_arg,
                                     node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_speculative_rewind(result=None, *, cache_arg: int = 0,
                             node: str = "verify", draft=None,
                             target=None, n_accepted=None, pool=None,
                             table=None, trie=None):
    """Self-check hook for speculative decoding (SERVE003), called by
    `serve.generation` at each artifact's natural checkpoint: the
    compiled verify step once per signature (`result` — donation warns,
    a missing length mask errors), the accept-walk bookkeeping every
    commit (`draft`/`target`/`n_accepted` — advancing past the first
    mismatch errors), and the paged page table after every rollback that
    released pages (`pool`/`table` — a dangling released page errors).
    Error findings raise under `analyze_raise`; warnings log.  Returns
    the findings so callers/tests can assert on them."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_speculative_rewind(
        result, cache_arg=cache_arg, node=node, draft=draft,
        target=target, n_accepted=n_accepted, pool=pool, table=table,
        trie=trie)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_prefix_cache(trie, node: str = "prefix_cache"):
    """Runtime self-check hook for the prefix trie: refcount/byte
    accounting invariants (SERVE002).  Drift raises under
    `analyze_raise` — eviction over corrupt bookkeeping could free a
    pinned chunk under a live slot.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_prefix_cache(trie, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_page_table(pool, table, trie=None, node: str = "kv",
                     on_path=None):
    """Runtime self-check hook for the paged KV session (KV001): audit
    the page pool / page table / prefix-trie bookkeeping against each
    other and raise (or log, with the escape hatch) on error findings —
    serving on corrupt page accounting reads or frees another sequence's
    K/V, bitwise-silently.  Returns the findings so callers/tests can
    assert on them.  `on_path` is `audit_page_table`'s: told "vector"
    or "listed", before anything raises."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_page_table(pool, table, trie=trie, node=node,
                                on_path=on_path)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_quant_arena(arena, node: str = "kv.quant"):
    """Runtime self-check hook for the quantized paged arena (KVQ001):
    payload/scale structural consistency.  Raises (or logs, with the
    escape hatch) on error findings — a desynced scale arena
    dequantizes pages into garbage, bitwise-silently.  Returns the
    findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_quant_arena(arena, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_quant_program(result, node: str = "decode.quant"):
    """Compile-time self-check hook for quantized paged steps (KVQ002):
    lint the program for int8 operands reaching a dot_general (the
    missing-dequant bug).  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_quant_program(result, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_tier_roundtrip(tier, node: str = "kv.tier"):
    """Runtime self-check hook for the host KV tier (KVQ003): manifest
    re-verification + byte accounting over every stored entry.  Returns
    the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_tier_roundtrip(tier, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_fleet_routing(decisions, node: str = "fleet"):
    """Audit hook for a fleet router's decision log: FLEET001 (routed to
    a tripped-breaker or draining replica) raises under `analyze_raise`.
    Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_routing(decisions, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_page_handoff(manifest, path, node: str = "handoff"):
    """Transfer-time self-check hook for `fleet.transport`: FLEET002
    (payload disagrees with the sha256 manifest) raises under
    `analyze_raise` — committing a corrupt page poisons every request
    sharing the prefix.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_page_handoff(manifest, path, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_fleet_drain(session, node: str = "drain"):
    """Drain-time self-check hook for the fleet router: FLEET003
    (orphaned pinned pages / trie bookkeeping drift on a drained
    session) — warning severity, logs and returns the findings."""
    if not _enabled():
        return []
    findings = audit_drained_session(session, node=node)
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_reshard_plan(plan, node: str = "reshard"):
    """Plan-time self-check hook for `easydist_tpu.reshard`: RESHARD001
    (peak live bytes over the chunked bound) raises under
    `analyze_raise` BEFORE any byte moves — a degenerate plan at model
    scale is the restore OOM, so it must fail at planning, not on the
    device.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_reshard_plan(plan, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_restored_state(restored, template, node: str = "restore"):
    """Post-restore self-check hook for `runtime.checkpoint`: RESHARD002
    (a restored leaf's sharding disagrees with the template spec) raises
    under `analyze_raise` — training on a silently re-laid-out state
    works but pays n_devices x memory and a re-shard collective every
    step.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_restored_state(restored, template, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_resume_descriptor(descriptor, resume_prompt=None,
                            node: str = "resume"):
    """Resume-time self-check hook for the fleet failover path: FLEET005
    (descriptor disagrees with the original request — prefix mismatch,
    budget overrun, or eos already emitted) raises under `analyze_raise`
    BEFORE the resubmit, so a recovery that would silently change tokens
    fails loudly instead.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_resume(descriptor, resume_prompt, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_sim_prediction(rows, bound=None, node: str = "sim"):
    """Validation hook for `bench.py --simulate`: SIM001 (a prediction
    row's relative error exceeds the committed bound) raises under
    `analyze_raise` — a fleet steered on drifted predictions is the
    failure the simulator gate exists to catch.  Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_prediction(rows, bound=bound, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_sim_autoscale(decisions, window=None, node: str = "autoscale"):
    """Post-drill hook for `bench.py --autoscale`: SIM002 (opposite
    scale actuations inside the hysteresis window — an A-B-A flap)
    raises under `analyze_raise` over the autoscaler's decision log.
    Returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_scale_decisions(decisions, window=window, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_donation_pairs(result, node: str = "state-io"):
    """Compile-time self-check hook for the layer-11 donation-pair
    audit (ALIAS002 two outputs claiming one donated input, ALIAS003 a
    declared donation XLA cannot honor — the silent-copy case).  Error
    findings raise under `analyze_raise`; returns the findings so
    callers/tests can assert on them."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_donation_pairs(result, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_protocol_specs(specs=None, max_states: int = None,
                         node: str = None):
    """Layer-12a self-check hook: exhaustively explore the protocol
    specs (default: the four shipped fleet protocols at committed
    scope) and convert violations to findings — PROTO001 a safety
    violation with the shortest counterexample interleaving, PROTO002 a
    reachable stuck state.  Error findings raise under `analyze_raise`;
    returns the findings so callers/tests can assert on them."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    from .modelcheck import MAX_STATES_DEFAULT

    findings = []
    for spec in (specs if specs is not None else ALL_SPECS()):
        fs, _res = audit_spec(
            spec, node=node,
            max_states=max_states or MAX_STATES_DEFAULT)
        findings.extend(fs)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_protocol_conformance(router=None, health=None, transport=None,
                               restore_attempts=None,
                               node: str = "drill"):
    """Layer-12b conformance hook: replay live components' recorded
    `transitions()` streams (and an elastic restore's attempt trail)
    through the spec automata — PROTO003 fires on any event the spec
    does not admit (a dropped completion, an illegal health edge, a
    double KV commit, a restore halving that skipped a step).  The
    fleet/elastic chaos drills call this after every run, so every CI
    drill log doubles as a conformance trace.  Error findings raise
    under `analyze_raise`; returns the findings."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = []
    if router is not None:
        findings.extend(replay_router_protocol(
            router.transitions(), node=f"{node}:router"))
    if health is not None:
        findings.extend(replay_health_events(
            health.transitions(), node=f"{node}:health"))
    if transport is not None:
        findings.extend(replay_transport_commits(
            transport.transitions(), node=f"{node}:transport"))
    if restore_attempts is not None:
        findings.extend(replay_restore_attempts(
            restore_attempts, node=f"{node}:restore"))
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings


def check_host_aliases(donated, holders, node: str = "session"):
    """Step-boundary self-check hook for `serve.generation` (ALIAS004):
    identity overlap between the buffers the next dispatch donates
    (cache/staging/arena) and host-held references that outlive the
    step (snapshots, hot-page exports, trie-held rows).  Error findings
    raise under `analyze_raise`; returns the findings so callers/tests
    can assert on them."""
    from easydist_tpu import config as edconfig

    if not edconfig.enable_analyze:
        return []
    findings = audit_host_aliases(donated, holders, node=node)
    report = AnalysisReport(findings)
    if report.errors() and edconfig.analyze_raise:
        report.raise_on_errors()
    for f in findings:
        logger.warning("[analyze] %s", f)
    return findings
