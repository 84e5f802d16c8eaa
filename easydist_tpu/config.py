"""Flat env-var-driven configuration (reference: easydist/config.py:28-126).

Every knob is a module global, overridable by environment variable at import
time and mutated by API kwargs at runtime.  Imported everywhere as `edconfig`.
"""

import logging
import os


def _env_bool(name: str, default: bool) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, default))


# ---------------- logging / dumps ----------------
log_level = getattr(logging, os.environ.get("EASYDIST_LOGLEVEL", "INFO").upper())
dump_dir = os.environ.get("EASYDIST_DUMP_DIR", None)
dump_strategy = _env_bool("EASYDIST_DUMP_STRATEGY", True)
dump_cluster = _env_bool("EASYDIST_DUMP_CLUSTER", False)
# graphviz DOT of the MetaIR graph with chosen placements (resharding
# edges highlighted) — reference DUMP_FX_GRAPH, compile_auto.py:487-508
dump_graphviz = _env_bool("EASYDIST_DUMP_GRAPHVIZ", True)
# optimized-HLO text of each compiled executable (what GSPMD emitted)
dump_hlo = _env_bool("EASYDIST_DUMP_HLO", False)

# ---------------- compile cache ----------------
# caches are anchored to the checkout, never to the working directory: a
# cache that moves with `cd` is a cold cache nobody asked for
checkout_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
enable_compile_cache = _env_bool("EASYDIST_COMPILE_CACHE", False)
compile_cache_dir = os.environ.get(
    "EASYDIST_COMPILE_CACHE_DIR", os.path.join(checkout_dir, ".easydist_cache"))

# ---------------- ShardCombine discovery ----------------
# number of shards used when executing candidate shardings (reference
# metashard/metaop.py:62 uses 2)
discovery_nshards = _env_int("EASYDIST_DISCOVERY_NSHARDS", 2)
# run discovery ops on CPU even when a TPU is present (device dispatch for
# thousands of tiny eager ops is wasteful; discovery is compile-time analysis)
discovery_on_cpu = _env_bool("EASYDIST_DISCOVERY_ON_CPU", True)
# allclose tolerance for recombination checks (reference platform/jax.py:24
# uses rtol 5e-3 because of tf32; we default tighter on CPU float32)
allclose_rtol = _env_float("EASYDIST_ALLCLOSE_RTOL", 1e-3)
allclose_atol = _env_float("EASYDIST_ALLCLOSE_ATOL", 1e-5)
# explore halo/block-cyclic extensions of the gather space (reference
# config.py `extend_space`)
extend_space = _env_bool("EASYDIST_EXTEND_SPACE", True)
# cap tensor elements during discovery: ops larger than this get hint-shrunk
# (reference torch/sharding_interpreter.py:256-313)
discovery_hint_numel = _env_int("EASYDIST_DISCOVERY_HINT_NUMEL", 2**24)
# hard cap on candidate shardings executed per shard group (the DFS is
# exponential in the number of tensor args; jax primitives rarely exceed 3)
discovery_max_candidates = _env_int("EASYDIST_DISCOVERY_MAX_CANDIDATES", 4096)

# ---------------- pruned discovery (jaxfront/discovery.py) ----------------
# Automap-style propagation grouping (arXiv:2112.02958): canonicalize eqn
# signatures into dim-role equivalence classes and run discovery once per
# group representative, instantiating the rule for every member.  The kill
# switch (EASYDIST_DISCOVERY_PRUNE=0) restores per-signature discovery
# end-to-end; chosen strategies are identical either way (gated by
# tests/test_jaxfront/test_discovery.py and bench.py --discovery).
discovery_prune = _env_bool("EASYDIST_DISCOVERY_PRUNE", True)
# persist discovered rules across process restarts, keyed by canonical
# signature + a knob/cost-model salt (atomic tempfile+replace store like
# the strategy cache's) — warm runs skip probe compilation entirely
discovery_persistent_cache = _env_bool("EASYDIST_DISCOVERY_CACHE", True)
# cache directory; empty = "<compile_cache_dir>/discovery"
discovery_cache_dir = os.environ.get("EASYDIST_DISCOVERY_CACHE_DIR", "")
# fuse a candidate's per-shard probe executions into ONE batched (vmapped)
# bind instead of nshards sequential eager calls; falls back to the
# sequential loop per-op on any batching failure
discovery_batch_probes = _env_bool("EASYDIST_DISCOVERY_BATCH_PROBES", True)
# analytic preset rules (jaxfront/presets.py); 0 forces execution
# discovery for every primitive (bench probe-ratio measurement uses this
# to compare pruned vs unpruned discovery on honest probe counts)
discovery_use_presets = _env_bool("EASYDIST_DISCOVERY_PRESETS", True)
# one-shot cross-check mode: execute-validate each analytic preset rule
# against the ShardCombine harness on small shapes (every preset shard
# group must execute and recombine exactly as declared); expensive,
# default off — enabled by the preset-validation test
discovery_crosscheck = _env_bool("EASYDIST_DISCOVERY_CROSSCHECK", False)

# ---------------- solver ----------------
enable_graph_coarsen = _env_bool("EASYDIST_ENABLE_GRAPH_COARSEN", True)
coarsen_level = _env_int("EASYDIST_COARSEN_LEVEL", 1)
solver_time_limit = _env_float("EASYDIST_SOLVER_TIME_LIMIT", 60.0)
solver_mip_rel_gap = _env_float("EASYDIST_SOLVER_MIP_REL_GAP", 1e-3)
all_to_all_punish_factor = _env_float("EASYDIST_ALL_TO_ALL_PUNISH", 3.0)
# allow re-picking a strategy already chosen on a previous mesh axis
allow_repeated_axis_strategy = _env_bool("EASYDIST_ALLOW_REPEATED_AXIS_STRATEGY", False)
# discount resharding cost when independent compute can hide the collective
# (reference predict_comm_overlap + comm_overlap_ratio, solver.py:74-84);
# the discount is bounded by the hideable seconds of independent peer work
# (MXU ops at peak_flops, memory-bound ops at hbm_bandwidth) per edge.
# The ratio the solver applies is resolved by
# autoflow.cost_model.overlap_discount_ratio() from three sources
# (`comm_overlap_ratio_source`):
#   "auto"     (default) the MEASURED fraction when runtime.calibrate.
#              calibrate_overlap() has recorded one for this backend in the
#              PerfDB (loaded at compile time by apply_calibration), else
#              the configured `comm_overlap_ratio`;
#   "measured" only the measured fraction — the discount is OFF (ratio 0)
#              until a calibration exists, so an uncalibrated compile can
#              never trade bytes for imagined overlap;
#   "config"   always the configured `comm_overlap_ratio` (the reference's
#              flat-guess behavior).
# predict_comm_overlap stays off by default: with the UNCALIBRATED flat 0.5
# guess, the GPT dp x tp solve picks plans moving ~1.5x the collective
# bytes of the byte-minimal plan (fails the hand-GSPMD quality gate); with
# a measured fraction the discount reflects what the runtime's
# backward-ordered bucket flush (comm/overlap.py) actually hides, and the
# same solve stays byte-minimal (tests/test_autoflow/
# test_overlap_pricing.py).  Calibrate once on the target, then enable.
predict_comm_overlap = _env_bool("EASYDIST_PREDICT_COMM_OVERLAP", False)
comm_overlap_ratio = _env_float("EASYDIST_COMM_OVERLAP_RATIO", 0.5)
comm_overlap_ratio_source = os.environ.get("EASYDIST_COMM_OVERLAP_SOURCE", "auto")
# set by runtime.calibrate (calibrate_overlap / apply_calibration), never
# by hand: achieved overlap fraction measured on THIS backend, or None
comm_overlap_ratio_measured = None
# device peak FLOP/s for overlap bounding.  This default prices CPU virtual
# meshes only: on a TPU it is replaced with the device kind's datasheet
# value at compile time (runtime.calibrate.apply_device_constants; a kind
# the table does not know raises) unless the env var is set
peak_flops = _env_float("EASYDIST_PEAK_FLOPS", 4.9e13)
# (mem_cost_weight was removed: the solver derives the memory tie-break
# weight from the comm-cost scale so it can order comm-equal solutions but
# never flip a comm decision — a fixed weight could do either)
# per-device memory cap in bytes: -1 = auto (ask the real device's
# memory_stats at compile; unknown backends stay uncapped), 0 = off,
# >0 = explicit cap.  v5e has 16 GiB HBM.
per_device_memory_cap = _env_int("EASYDIST_MEMORY_CAP", -1)
memory_ratio = _env_float("EASYDIST_MEMORY_RATIO", 0.9)
# compiler-chosen rematerialization when the planned peak exceeds the cap
# (schedule/remat.py); max eqns re-executed per recompute chain
enable_auto_remat = _env_bool("EASYDIST_AUTO_REMAT", True)
remat_max_chain_len = _env_int("EASYDIST_REMAT_MAX_CHAIN", 96)
liveness_only_input = _env_bool("EASYDIST_LIVENESS_ONLY_INPUT", False)
solver_backend = os.environ.get("EASYDIST_SOLVER", "milp")  # milp | beam
beam_width = _env_int("EASYDIST_BEAM_WIDTH", 100)
# tie ILP variables of isomorphic clusters (identical transformer layers
# collapse to one set of decision variables; solve time for an L-layer stack
# approaches the 1-layer solve)
solver_cluster_dedup = _env_bool("EASYDIST_SOLVER_CLUSTER_DEDUP", True)
# carry PARTIAL placements in the GLOBAL strategy pools so the ILP can
# defer an all-reduce across linear consumers (reference metair.py:376-481
# carries partials globally; previously composite-rule inner solves only)
enable_partial_pools = _env_bool("EASYDIST_PARTIAL_POOLS", True)
# lax.scan composite discovery: cap on per-seed body ILP solves (each seed
# dim of each scan operand costs one small ILP; real models have dozens)
scan_max_seed_solves = _env_int("EASYDIST_SCAN_MAX_SEED_SOLVES", 48)
# lax.while_loop trip count is unknown at trace time; this estimate scales
# the per-iteration collective price of a sharded loop body (solver only —
# a wrong guess shifts the shard/replicate crossover, never correctness)
while_trip_estimate = _env_int("EASYDIST_WHILE_TRIP_ESTIMATE", 16)
# warn when more than this fraction of modeled FLOPs lands on equations
# whose chosen strategy is all-replicate on every mesh axis — the
# silent-zero-parallelism failure mode (a user gets 1-chip performance on
# an 8-chip mesh with no signal)
replicate_warn_threshold = _env_float("EASYDIST_REPLICATE_WARN_THRESHOLD", 0.5)

# ---------------- mesh / comm cost model ----------------
# per-axis link bandwidth in bytes/s used to weight collective cost between
# mesh axes; ICI (intra-slice) vs DCN (cross-slice).  v5e: 4x 400Gbps ICI
# links/chip ≈ 200 GB/s; DCN ≈ 25 GB/s per host.
ici_bandwidth = _env_float("EASYDIST_ICI_BANDWIDTH", 2.0e11)
dcn_bandwidth = _env_float("EASYDIST_DCN_BANDWIDTH", 2.5e10)
# alpha term: fixed seconds per collective launch (ring setup + sync); makes
# the solver stop scattering tiny tensors whose collectives are pure latency
ici_latency = _env_float("EASYDIST_ICI_LATENCY", 1.0e-6)
dcn_latency = _env_float("EASYDIST_DCN_LATENCY", 2.0e-5)
# HBM bandwidth (bytes/s): prices the compute-redundancy of replicated ops
# (elementwise ops are memory-bound; v5e ~ 810 GB/s)
hbm_bandwidth = _env_float("EASYDIST_HBM_BANDWIDTH", 8.1e11)

# ---------------- gradient-collective compression (easydist_tpu.comm) ----
# wire dtype for gradient reductions: "none" (exact fp32 path, the
# default — emitted programs stay bitwise-identical to pre-comm behavior)
# | "int8" (two-pass block-scaled, ~3.9x fewer wire bytes) | "bf16" (cast,
# 2x).  See docs/COMM.md for the scheme and accuracy guidance.
comm_quant_dtype = os.environ.get("EASYDIST_COMM_QUANT", "none")
# elements per scaling block for int8 (one f32 scale per block; larger
# blocks = less scale overhead, coarser dynamic range)
comm_quant_block = _env_int("EASYDIST_COMM_QUANT_BLOCK", 256)
# fuse leaf gradients into buckets of at most this many bytes before
# reducing (0 = one collective per leaf, the historical emission).  Fewer
# launches amortize the per-collective alpha and fill the ICI rings.
comm_bucket_bytes = _env_int("EASYDIST_COMM_BUCKET_BYTES", 0)
# per-tree opt-out: leaves whose key path matches this regex (case-
# insensitive) stay at exact fp32 — norm scales/biases are tiny but
# disproportionately sensitive to quantization noise
# (the `'b'` alternative catches dict-key paths like "[0]['b']" that
# jax.tree_util.keystr produces for the toy models' bias leaves)
comm_quant_skip = os.environ.get(
    "EASYDIST_COMM_QUANT_SKIP", r"bias|norm|\bln\b|scale|gamma|beta|'b'")
# leaves below this many elements are never quantized: block padding plus
# per-block scales would move MORE bytes than fp32, and tiny collectives
# are alpha-bound anyway (bucket them instead)
comm_quant_min_numel = _env_int("EASYDIST_COMM_QUANT_MIN_NUMEL", 2048)
# ---------------- overlapped gradient collectives (comm/overlap.py) -------
# flush gradient buckets in backward EMISSION order, each launch pinned to
# the previous with optimization_barrier so XLA's latency-hiding scheduler
# slides the collective under the remaining backward compute.  Off by
# default: the dp/zero wrappers then emit the historical sequential flush
# (bitwise-identical programs).  Value-safe when on: reductions are
# elementwise, so the reordered flush is bitwise-identical to the
# sequential one whenever quantization is off (docs/COMM.md).
comm_overlap = _env_bool("EASYDIST_COMM_OVERLAP", False)
# K-microbatch double-buffered gradient accumulation in the dp/zero step
# builders: a lax.scan whose carry holds microbatch k-1's in-flight grads,
# reduced while microbatch k's backward runs.  0/1 = off (single-shot
# step); per-call kwargs on ddp_step/zero2_step/zero3_step override.
grad_accum_microbatches = _env_int("EASYDIST_GRAD_ACCUM_MICROBATCHES", 0)
# replace peak_flops/hbm_bandwidth defaults with the real device kind's
# datasheet constants at compile time (a CPU host keeps the defaults; an
# unknown TPU kind raises)
auto_device_constants = _env_bool("EASYDIST_AUTO_DEVICE_CONSTANTS", True)
# load measured alpha/beta/HBM values from the PerfDB when present
# (runtime.calibrate.calibrate() records them on the target hardware)
auto_calibration = _env_bool("EASYDIST_AUTO_CALIBRATION", True)
multihost = _env_bool("EASYDIST_MULTIHOST", False)

# ---------------- static analyzer (easydist_tpu.analyze) ----------------
# run the layer-1 strategy verifier + solver objective audit after every
# per-axis solve, and the bucketer's plan self-check (both are pure python
# over already-built structures; cost is negligible next to the solve)
enable_analyze = _env_bool("EASYDIST_ANALYZE", True)
# error-severity findings raise AnalysisError; set 0 to demote to logging
# (the escape hatch for shipping past a false positive while it is triaged)
analyze_raise = _env_bool("EASYDIST_ANALYZE_RAISE", True)
# MEM004 HBM budget gate (bytes/device): -1 = auto (ask the real device's
# memory_stats; CPU virtual meshes fall back to hbm_capacity_default), 0 =
# gate off, >0 = explicit budget.  Unlike per_device_memory_cap (which
# DRIVES remat), this only verifies — it never changes the program.
analyze_hbm_budget = _env_int("EASYDIST_ANALYZE_HBM_BUDGET", -1)
# HBM capacity assumed for CPU virtual meshes, which report none (a TPU
# that reports none raises instead)
hbm_capacity_default = _env_int("EASYDIST_HBM_CAPACITY", 16 * 2**30)
# SCHED003: warn when a pipeline tick schedule's static bubble fraction
# (idle fwd/bwd slots over total slots) exceeds this
analyze_bubble_warn_frac = _env_float("EASYDIST_ANALYZE_BUBBLE_WARN", 0.6)

# ---------------- runtime ----------------
# donate params/opt-state buffers in the emitted jit (XLA buffer aliasing: the
# TPU analog of the reference's in-place CUDA memory reuse)
enable_donation = _env_bool("EASYDIST_ENABLE_DONATION", True)
# jax.remat policy applied to the emitted function: "none" | "dots" | "all"
remat_policy = os.environ.get("EASYDIST_REMAT_POLICY", "none")

# ---------------- decode serving (easydist_tpu.serve.generation) --------
# attention backend for the cache-carrying decode step: "auto" (Pallas
# single-query flash kernel on TPU, masked dot_general elsewhere), "flash"
# (force the kernel; interpreted off-TPU), "xla" (force the masked
# dot_general path), "paged" (force the page-gathering kernel in
# `paged_decode_attention`; contiguous callers degrade to auto).
# TRACE-AFFECTING: the backends emit different programs for identical
# input shapes, so this is part of the strategy-cache salt.
decode_attention_backend = os.environ.get("EASYDIST_DECODE_ATTENTION",
                                          "auto")
# K/V rows streamed per grid step by the decode kernel (VMEM residency per
# program is O(block), independent of cache length).  TRACE-AFFECTING:
# changes the pallas_call grid, so it salts the strategy cache too.
decode_block_k = _env_int("EASYDIST_DECODE_BLOCK_K", 256)
# attention backend for a chunk of queries (chunked prefill, speculation
# verify): "auto" | "paged" | "flash" | "xla", the decode knob's values.
# "paged"/"flash" pick the Pallas kernel that reads a row's pages through
# the table up to the row's extent (`paged_chunk_attention`; a paged arena
# without scale leaves), "xla" the gather + masked dot_general path over
# the whole bucket; "auto" is the kernel on TPU and "xla" elsewhere.  An
# int8 arena and the contiguous layout take the "xla" path whatever it
# says.  TRACE-AFFECTING: part of the strategy-cache salt like the decode
# backend.
prefill_attention_backend = os.environ.get("EASYDIST_PREFILL_ATTENTION",
                                           "auto")
# speculative decoding defaults (`ServeConfig.speculate_k` /
# `.speculate_drafter` read these when not set explicitly): k = draft
# tokens proposed per verify round (0 disables speculation entirely —
# the session never compiles a verify program), drafter = "ngram"
# (zero-cost prompt lookup) or "draft_model" (a second small model's
# cached greedy decode; the session needs a drafter/draft_model wired).
# NOT trace-affecting by themselves: the verify program's shape is
# (slots, k+1), which reaches the signature cache as an input shape.
speculate_k = _env_int("EASYDIST_SPECULATE_K", 0)
speculate_drafter = os.environ.get("EASYDIST_SPECULATE_DRAFTER", "ngram")

# ---------------- reshard (easydist_tpu.reshard) ----------------
# chunk ceiling (bytes) for redistribution plans: the "+ chunk" term of
# the RESHARD001 peak-live-bytes bound.  Each plan step stages at most
# this much on top of one src shard + one dst shard; smaller chunks cap
# transient memory at the price of more collective launches (the
# elastic.restore.oom recovery path halves this and re-plans).
reshard_chunk_bytes = _env_int("EASYDIST_RESHARD_CHUNK_BYTES", 64 * 2**20)

# ---------------- resilience (easydist_tpu.resilience) ----------------
# deterministic fault schedule, e.g. "step.nan_grad@7,ckpt.write.partial@2"
# — names must come from resilience.faultinject.FAULT_POINTS (validated at
# arm time AND at import time by the faultinject module); empty = disarmed
fault_plan = os.environ.get("EASYDIST_FAULT_PLAN", "")
# NaN/Inf step guard: lax.cond skip-and-hold folded into the compiled step
# (dp/zero builders + GuardedStep for the auto path).  Off by default —
# guard-off programs are bitwise-identical to pre-guard builds.
# TRACE-AFFECTING: part of the strategy-cache salt.
resilience_step_guard = _env_bool("EASYDIST_STEP_GUARD", False)
# consecutive non-finite steps the guard holds before raising
resilience_guard_max_skips = _env_int("EASYDIST_GUARD_MAX_SKIPS", 8)
# overflow scale decays by this factor on each held step ...
resilience_guard_scale_decay = _env_float("EASYDIST_GUARD_SCALE_DECAY", 0.5)
# ... and doubles back (capped at its initial value) after this many clean
# steps
resilience_guard_scale_growth_every = _env_int(
    "EASYDIST_GUARD_GROWTH_EVERY", 200)
# checkpoint save/load I/O retry policy: exponential backoff with jitter
resilience_ckpt_retries = _env_int("EASYDIST_CKPT_RETRIES", 3)
resilience_ckpt_backoff_s = _env_float("EASYDIST_CKPT_BACKOFF", 0.05)
resilience_ckpt_backoff_jitter = _env_float("EASYDIST_CKPT_JITTER", 0.25)
# SIGTERM grace budget: the final synchronous checkpoint must land inside
# this window (GCE preemptible gives 30s; TPU spot similar)
resilience_preempt_grace_s = _env_float("EASYDIST_PREEMPT_GRACE", 30.0)
# data-stall watchdog for the elastic loop: a batch fetch exceeding this
# raises DataStallError (0 = watchdog off)
resilience_data_timeout_s = _env_float("EASYDIST_DATA_TIMEOUT", 0.0)


def _validate_resilience() -> None:
    """Fail at import on out-of-range resilience knobs: a bad env var must
    not surface as a wedged recovery path mid-incident."""
    if resilience_guard_max_skips < 1:
        raise ValueError(
            f"EASYDIST_GUARD_MAX_SKIPS must be >= 1, got "
            f"{resilience_guard_max_skips}")
    if not 0.0 < resilience_guard_scale_decay <= 1.0:
        raise ValueError(
            f"EASYDIST_GUARD_SCALE_DECAY must be in (0, 1], got "
            f"{resilience_guard_scale_decay}")
    if resilience_guard_scale_growth_every < 1:
        raise ValueError(
            f"EASYDIST_GUARD_GROWTH_EVERY must be >= 1, got "
            f"{resilience_guard_scale_growth_every}")
    if resilience_ckpt_retries < 0:
        raise ValueError(
            f"EASYDIST_CKPT_RETRIES must be >= 0, got "
            f"{resilience_ckpt_retries}")
    if resilience_ckpt_backoff_s < 0:
        raise ValueError(
            f"EASYDIST_CKPT_BACKOFF must be >= 0, got "
            f"{resilience_ckpt_backoff_s}")
    if not 0.0 <= resilience_ckpt_backoff_jitter <= 1.0:
        raise ValueError(
            f"EASYDIST_CKPT_JITTER must be in [0, 1], got "
            f"{resilience_ckpt_backoff_jitter}")
    if resilience_preempt_grace_s <= 0:
        raise ValueError(
            f"EASYDIST_PREEMPT_GRACE must be > 0, got "
            f"{resilience_preempt_grace_s}")
    if resilience_data_timeout_s < 0:
        raise ValueError(
            f"EASYDIST_DATA_TIMEOUT must be >= 0, got "
            f"{resilience_data_timeout_s}")


_validate_resilience()

# ---------------- profiling / perf db ----------------
prof_db_path = os.environ.get("EASYDIST_PERF_DB", os.path.expanduser("~/.easydist_tpu/perf.db"))
# price solver compute-redundancy with measured per-op seconds from the
# PerfDB when available (runtime/op_profile.py); proxy otherwise
use_op_cost_db = _env_bool("EASYDIST_OP_COST_DB", True)
