#!/usr/bin/env bash
# Tier-1-adjacent static gate: ruff + mypy over easydist_tpu/, configured
# in pyproject.toml (scoped, baseline-clean, no blanket ignores).
#
# Run from the repo root:  bash scripts/static_checks.sh
# Exit code is nonzero iff an installed tool reports findings; a missing
# tool is reported and skipped (the hermetic CI image does not ship them —
# install with `pip install ruff mypy` where allowed).
set -u
cd "$(dirname "$0")/.."
rc=0
ran=0

if command -v ruff >/dev/null 2>&1; then
    ran=1
    echo "== ruff check easydist_tpu"
    ruff check easydist_tpu || rc=1
else
    echo "static_checks: ruff not installed; skipping (pip install ruff)"
fi

if command -v mypy >/dev/null 2>&1; then
    ran=1
    echo "== mypy easydist_tpu"
    mypy --config-file pyproject.toml || rc=1
else
    echo "static_checks: mypy not installed; skipping (pip install mypy)"
fi

# the sharding/memory/schedule lint ships in-tree but needs a jax to trace
# the preset models: bench.py --analyze gates zero error-severity findings
# (STRAT/COLL plus the MEM/SCHED memory-plan & pipeline-schedule rules and
# the HBM-budget/peak-drift assertions) when jax is importable, and skips
# gracefully where it is not (bare linting containers)
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --analyze (sharding + memory/schedule lint gate)"
    out=$(python bench.py --analyze 2>/dev/null) || rc=1
    echo "$out"
    errors=$(python - "$out" <<'EOF'
import json, sys
try:
    print(json.loads(sys.argv[1].strip().splitlines()[-1])["value"])
except Exception:
    print(-1)
EOF
)
    if [ "$errors" != "0" ]; then
        echo "static_checks: sharding lint reported $errors error finding(s)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --analyze"
fi

# analyzer driver gate (docs/ANALYZE.md "Driver"): the layer-11 host
# donation lint + the preset analyze stack behind the shared driver —
# inline suppressions and the committed baseline (analyze_baseline.json)
# applied, SARIF artifact emitted for CI, incremental cache warm across
# repeat runs.  Fails on any NON-BASELINED error; refresh the baseline
# with `python -m easydist_tpu.analyze --refresh-baseline` (see README).
if python -c "import jax" >/dev/null 2>&1; then
    echo "== python -m easydist_tpu.analyze (driver gate: ast + presets + protocol)"
    mkdir -p "${EASYDIST_ARTIFACT_DIR:-/tmp/easydist_artifacts}"
    sarif="${EASYDIST_ARTIFACT_DIR:-/tmp/easydist_artifacts}/analyze.sarif"
    python -m easydist_tpu.analyze --targets ast,presets,protocol \
        --sarif "$sarif" || {
        echo "static_checks: analyzer driver reported new (non-baselined)" \
             "error finding(s)"
        rc=1
    }
    [ -s "$sarif" ] && echo "static_checks: SARIF artifact at $sarif"
else
    echo "static_checks: jax not importable; skipping the analyzer driver"
fi

# protocol model-check gate (docs/ANALYZE.md layer 12): exhaustively
# explore the four fleet protocol specs (health, router, resume,
# transport — analyze/modelcheck.py) over EVERY interleaving at their
# committed scope.  Needs no jax, so it runs even in bare containers.
# The exploration is bounded twice over: a hard wall-clock timeout here,
# and the committed per-spec state budgets inside — exhausting more (or
# fewer) states than COMMITTED_STATES by >20% is a PROTO003 error (the
# spec changed shape without a conscious budget re-commit), and any
# PROTO001 safety violation / PROTO002 stuck state fails the gate with
# its shortest counterexample trace in the output.
echo "== python -m easydist_tpu.analyze --targets protocol (model-check gate)"
proto_json="${EASYDIST_ARTIFACT_DIR:-/tmp/easydist_artifacts}/protocol.json"
mkdir -p "$(dirname "$proto_json")"
if timeout 120 python -m easydist_tpu.analyze --targets protocol \
        --no-cache --json "$proto_json"; then
    python - "$proto_json" <<'PYEOF'
import json, sys
d = json.load(open(sys.argv[1]))
for name, st in sorted(d.get("protocol", {}).items()):
    print(f"static_checks: protocol[{name}] {st['states']} states "
          f"(committed {st['committed']}, exhausted={st['exhausted']})")
PYEOF
else
    echo "static_checks: protocol model-check gate FAILED (safety" \
         "violation, stuck state, budget drift >20%, or timeout)"
    rc=1
fi

# overlapped-collectives gate: the backward-ordered barrier-pinned flush
# must stay bitwise-identical to the sequential one (quantization off) and
# the emission-ordered bucket chain must expose a nonzero SCHEDULABLE
# overlap fraction (bench.py --overlap `value`; program-structure bound,
# deterministic — measured wall-clock fractions and step-time deltas on
# virtual CPU meshes are noise, so only the deterministic bits gate)
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --overlap (overlapped-flush parity gate)"
    out=$(python bench.py --overlap 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'EOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_bitwise"):
        print("parity_bitwise false")
    elif not r.get("value", 0) > 0:
        print("overlap_fraction not > 0")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
EOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: overlap gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --overlap"
fi

# resilience gate: every drill in bench.py --resilience is deterministic
# (injected faults, bitwise recovery checks, trace-identity audit), so the
# whole JSON record gates — value 1.0 means torn writes stayed invisible,
# the preempted run resumed bitwise-identical, the guard-off trace matched
# the default build, and the serve watchdog recovered after an injected
# execute timeout
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --resilience (fault-injection recovery gate)"
    out=$(python bench.py --resilience 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'EOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif r.get("value") != 1.0:
        print("recovery drill value != 1.0")
    elif not r.get("guard_off_trace_identical"):
        print("guard-off trace not identical")
    elif not r.get("ckpt_torn_write_invisible"):
        print("torn checkpoint write became visible")
    elif not r.get("preempt_resume_bitwise"):
        print("preempt resume not bitwise-identical")
    elif not r.get("serve_watchdog_recovered"):
        print("serve watchdog did not recover")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
EOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: resilience gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --resilience"
fi

# decode-serving gate: KV-cached generation must beat the naive full
# re-forward greedy loop >= 5x in tokens/s at seq 512 (the O(T) vs O(T^2)
# economics), with bitwise greedy parity and a decode signature cache that
# stays at one compiled step per bucket across every generated token.
# The mixed-length section additionally gates lengths of 24..440 tokens in
# one pool: greedy ids bitwise == the full re-forward, ONE compiled decode
# step for every length, and a zero-copy prefix restore
# (copy_on_restore_bytes_saved)
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --decode (KV-cache decode speedup + parity gate)"
    out=$(python bench.py --decode 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'EOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_greedy"):
        print("cached greedy ids diverge from full re-forward")
    elif not r.get("signature_cache_constant"):
        print("decode signature cache grew across tokens")
    elif not r.get("value", 0) >= 5.0:
        print(f"speedup {r.get('value')} < 5.0x")
    elif not r.get("paged_parity_greedy"):
        print("mixed-length greedy ids diverge from full re-forward")
    elif not r.get("paged_signature_constant"):
        print("paged decode signature cache grew across mixed lengths")
    elif not r.get("copy_on_restore_bytes_saved", 0) > 0:
        print("paged prefix restore saved zero copy bytes")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
EOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: decode gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --decode"
fi

# chunked-prefill / prefix-cache gate: restoring a shared 256-token
# prefix from the trie must cut TTFT >= 2x vs recomputing it (cache-off),
# with bitwise greedy parity cache-on vs cache-off vs full re-forward and
# ONE compiled chunk program per bucket across all prompt lengths
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --prefill (prefix-cache TTFT speedup + parity gate)"
    out=$(python bench.py --prefill 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_greedy"):
        print("cache-on greedy ids diverge from cache-off")
    elif not r.get("parity_vs_full_forward"):
        print("greedy ids diverge from the full re-forward reference")
    elif not r.get("signature_cache_constant"):
        print("prefill signature cache grew across prompt lengths")
    elif not r.get("value", 0) >= 2.0:
        print(f"TTFT speedup {r.get('value')} < 2.0x")
    elif not r.get("copy_on_restore_bytes_saved", 0) > 0:
        print("prefix restore saved zero copy bytes")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: prefill gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --prefill"
fi

# fleet-serving gate: multi-replica routing must keep bitwise greedy
# parity with the single session (including the disaggregated-prefill and
# drain-mid-traffic arms), the affinity policy must beat uniform-random
# on the aggregate prefix-trie hit rate, and a graceful drain under live
# load must drop zero requests
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --fleet (multi-replica routing + drain gate)"
    out=$(python bench.py --fleet 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_greedy"):
        print("fleet greedy ids diverge from the single-session run")
    elif not r.get("affinity_beats_random"):
        print(f"affinity hit rate {r.get('value')} does not beat random "
              f"{r.get('random_hit_rate')}")
    elif not r.get("drain_zero_drop"):
        print(f"drain dropped {r.get('drain_dropped_requests')} request(s)")
    elif not r.get("prefill_handoffs", 0) > 0:
        print("disaggregated prefill never handed off a page")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: fleet gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --fleet"
fi

# fleet-chaos gate: a seeded fault schedule kills one replica per
# traffic wave mid-decode (revived between waves); every stream must
# still finish bitwise-identical to the single-session run with zero
# dropped requests, at least one request actually recovered from its
# ResumeDescriptor, every scheduled fault fired (a drill whose faults
# never fired tested nothing), a clean FLEET001/004 routing audit, and
# TTFT p99 within the bounded multiple of the calm arm
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --fleet-chaos (crash/revive recovery drill gate)"
    out=$(python bench.py --fleet-chaos 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_bitwise"):
        print("chaos-arm greedy ids diverge from the single-session run")
    elif r.get("dropped_requests", 1) != 0:
        print(f"chaos drill dropped {r.get('dropped_requests')} request(s)")
    elif not r.get("requests_recovered", 0) > 0:
        print("no request was ever recovered (drill tested nothing)")
    elif r.get("replica_crashes") != r.get("crashes_scheduled"):
        print(f"observed {r.get('replica_crashes')} crash(es), scheduled "
              f"{r.get('crashes_scheduled')}")
    elif r.get("fault_plan_unfired", 1) != 0:
        print(f"{r.get('fault_plan_unfired')} scheduled fault(s) never fired")
    elif r.get("routing_findings", 1) != 0:
        print(f"routing audit raised {r.get('routing_findings')} "
              f"FLEET001/004 finding(s)")
    elif r.get("proto_findings", 1) != 0:
        print(f"protocol conformance replay raised "
              f"{r.get('proto_findings')} PROTO003 finding(s) — the "
              f"drill's transitions() streams drifted from the specs")
    elif not r.get("ttft_p99_inflation", 1e18) <= r.get("ttft_p99_bound", 0):
        print(f"ttft p99 inflated {r.get('ttft_p99_inflation')}x under "
              f"chaos (bound {r.get('ttft_p99_bound')}x)")
    elif not r.get("verify_steps", 0) > 0:
        print("no speculative verify round was in flight during the drill")
    elif not r.get("int8_wave_parity"):
        print("int8 wave: quantized crash-resume diverged from the "
              "single-session int8 reference (re-prefilled pages must "
              "rebuild bitwise)")
    elif r.get("int8_wave_dropped", 1) != 0 \
            or not r.get("int8_wave_recovered", 0) > 0 \
            or r.get("int8_wave_crashes") != 1 \
            or r.get("int8_wave_unfired", 1) != 0:
        print(f"int8 wave drill incomplete (dropped="
              f"{r.get('int8_wave_dropped')}, recovered="
              f"{r.get('int8_wave_recovered')}, crashes="
              f"{r.get('int8_wave_crashes')}, unfired="
              f"{r.get('int8_wave_unfired')})")
    elif r.get("value") != 1.0:
        print(f"only {r.get('value')} of requests finished clean")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: fleet-chaos gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --fleet-chaos"
fi

# kv-scale gate: the quantized + host-tiered paged-KV economics.  The
# int8 arm must admit >= 1.8x the sequences per HBM byte, agree with the
# exact arm >= 0.995 (free-running greedy AND teacher-forced) under a
# bounded logit drift; the exact arm must stay bitwise with a scale-free
# arena (quant off is the pre-quant program); the host tier must restore
# >= 0.9 of its prefix tokens at a 10x-HBM working set with zero sha256
# manifest failures; and both kv.tier fault points must drill live with
# every scheduled fault fired
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --kv-scale (quantized + tiered KV density gate)"
    out=$(python bench.py --kv-scale 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("exact_bitwise"):
        print("exact arm diverged from the full re-forward "
              "(quant-off must stay bitwise)")
    elif not r.get("exact_scale_free"):
        print("exact arm's arena carries scale leaves or int8 payloads "
              "(quant-off purity broken)")
    elif not r.get("value", 0) >= r.get("ratio_floor", 1.8):
        print(f"int8 density {r.get('value')}x below the "
              f"{r.get('ratio_floor')}x slots-per-HBM-byte floor")
    elif not r.get("greedy_match", 0) >= r.get("match_floor", 0.995) \
            or not r.get("teacher_forced_match", 0) >= \
            r.get("match_floor", 0.995):
        print(f"int8 A/B agreement below floor (greedy "
              f"{r.get('greedy_match')}, teacher-forced "
              f"{r.get('teacher_forced_match')}, floor "
              f"{r.get('match_floor')})")
    elif not r.get("logit_drift_max", 1e18) <= \
            r.get("logit_drift_bound", 0):
        print(f"int8 logit drift {r.get('logit_drift_max')} exceeds "
              f"bound {r.get('logit_drift_bound')}")
    elif not r.get("tier_hit_rate", 0) >= r.get("tier_hit_floor", 0.9):
        print(f"tier hit rate {r.get('tier_hit_rate')} below "
              f"{r.get('tier_hit_floor')} at "
              f"{r.get('tier_working_set_x')}x HBM working set")
    elif r.get("tier_manifest_failures", 1) != 0:
        print(f"{r.get('tier_manifest_failures')} tier manifest "
              f"failure(s) — host pages round-tripped corrupt")
    elif not r.get("tier_pass_bitwise") \
            or not r.get("tier_invariants_clean"):
        print("tiered pass diverged or tier/trie invariants dirty")
    elif r.get("drill_fetch_corrupt_unfired", 1) != 0 \
            or r.get("drill_host_oom_unfired", 1) != 0:
        print("a scheduled kv.tier fault never fired (drill tested "
              "nothing)")
    elif not r.get("tier_fetch_retries", 0) >= 1 \
            or not r.get("drill_host_oom_paused"):
        print("kv.tier drills left no footprint (no manifest-caught "
              "refetch, or OOM never paused demotion)")
    elif r.get("verdict") != "ok":
        print(f"scenario verdict {r.get('verdict')}")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% "
              f"below last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: kv-scale gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --kv-scale"
fi

# elastic-chaos gate: train on 8 virtual devices, take a mesh-shrink
# SIGTERM mid-run, restart on a 4-device sub-mesh (newest checkpoint
# corrupted -> one-step fallback + replay), grow back to 8 (restore
# chunk budget "OOMs" -> halve and replan); the full loss stream AND
# final state must be bitwise-identical to an uninterrupted 8-device
# run, both restores must detect the topology shift and route through
# the reshard planner inside the RESHARD001 byte bound with zero
# findings, and every scheduled fault must fire
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --elastic-chaos (topology-shift recovery drill gate)"
    out=$(python bench.py --elastic-chaos 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("final_state_bitwise"):
        print("final state diverges from the uninterrupted 8-device run")
    elif not r.get("loss_stream_bitwise"):
        print(f"loss stream diverges at {r.get('loss_mismatches')}")
    elif not r.get("shrink_notice_preempted"):
        print("mesh-shrink notice never preempted the loop")
    elif r.get("fault_plan_unfired", 1) != 0:
        print(f"{r.get('fault_plan_unfired')} scheduled fault(s) never fired")
    elif r.get("topology_shifts_detected") != 2:
        print(f"detected {r.get('topology_shifts_detected')} topology "
              f"shift(s), expected 2 (8->4 and 4->8)")
    elif not r.get("restore_peak_within_bound"):
        print("a restore plan's peak live bytes exceeded the chunked bound")
    elif r.get("reshard_findings", 1) != 0:
        print(f"{r.get('reshard_findings')} RESHARD001/002 finding(s)")
    elif r.get("proto_findings", 1) != 0:
        print(f"restore-attempt conformance replay raised "
              f"{r.get('proto_findings')} PROTO003 finding(s)")
    elif not r.get("steps_replayed_after_fallback"):
        print("corrupt-checkpoint fallback replayed no step "
              "(drill tested nothing)")
    elif r.get("value") != 1.0:
        print("drill gate value != 1.0")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: elastic-chaos gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --elastic-chaos"
fi

# speculative-decoding gate: draft/verify greedy decode must beat plain
# decode >= 1.4x tokens/s on the repetitive (hot-prompt) workload and
# slow the adversarial (always-rejected-drafts) workload by <= 1.15x,
# with bitwise greedy parity on BOTH workloads (the accept rule is
# self-validating), ONE compiled verify signature, and the paged
# mini-arm's spill-page rollback actually releasing pages
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --speculate (draft/verify speedup + parity gate)"
    out=$(python bench.py --speculate 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif not r.get("parity_greedy"):
        print("speculative greedy ids diverge from plain decode")
    elif not r.get("paged_parity_greedy"):
        print("paged speculative greedy ids diverge from plain decode")
    elif not r.get("verify_signature_constant"):
        print("verify signature cache grew past one compiled step")
    elif not r.get("value", 0) >= 1.4:
        print(f"repetitive speedup {r.get('value')} < 1.4x")
    elif not r.get("adversarial_slowdown", 1e18) <= r.get(
            "adversarial_slowdown_bound", 0):
        print(f"adversarial slowdown {r.get('adversarial_slowdown')}x over "
              f"bound {r.get('adversarial_slowdown_bound')}x")
    elif not r.get("speculative_rollback_pages_released", 0) > 0:
        print("paged rollback released zero spill pages (arm tested nothing)")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: speculate gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --speculate"
fi

# simulator-validation gate: every held-out validation preset's predicted
# time must land within the committed relative-error bound of the bench
# actual measured on THIS host (calibration presets fit the per-domain
# residual and are excluded), with zero SIM001 analyze findings
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --simulate (calibrated-simulator validation gate)"
    out=$(python bench.py --simulate 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif r.get("value", 0) < r.get("n_validation_presets", 4):
        print(f"only {r.get('value')}/{r.get('n_validation_presets')} "
              f"validation presets within the "
              f"{r.get('rel_error_bound')} bound "
              f"(worst rel err {r.get('worst_rel_error')})")
    elif r.get("sim_findings", 1) != 0:
        print(f"{r.get('sim_findings')} SIM001 finding(s) on the "
              f"validation rows")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: simulate gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --simulate"
fi

# autoscale ramp-drill gate: the deterministic ramp-up/hold/ramp-down
# drill must drop zero requests, keep committed tokens bitwise-identical
# to the fixed-fleet reference, converge each phase to the capacity
# planner's independently computed target, log zero SIM002 flap
# findings, and degrade gracefully (hold + loud warning, still zero
# drops, still bitwise) under both catalogued autoscale fault points
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --autoscale (SLO-autoscaler ramp drill gate)"
    out=$(python bench.py --autoscale 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif r.get("dropped_requests", 1) != 0:
        print(f"ramp drill dropped {r.get('dropped_requests')} request(s)")
    elif not r.get("parity_bitwise"):
        print("scaled-fleet ids diverge from the fixed-fleet run")
    elif not r.get("targets_match_planner"):
        print(f"phase replica counts {r.get('phase_replicas')} do not "
              f"match planner targets (high={r.get('planner_target_high')}"
              f", low={r.get('planner_target_low')})")
    elif r.get("flap_findings", 1) != 0:
        print(f"{r.get('flap_findings')} SIM002 flap finding(s) in the "
              f"decision log")
    elif not (r.get("stale_arm", {}).get("drops", 1) == 0
              and r.get("stale_arm", {}).get("bitwise")):
        print(f"stale-metrics arm degraded unsafely: {r.get('stale_arm')}")
    elif not (r.get("scaleup_fail_arm", {}).get("drops", 1) == 0
              and r.get("scaleup_fail_arm", {}).get("bitwise")):
        print("scale-up-failure arm degraded unsafely: "
              f"{r.get('scaleup_fail_arm')}")
    elif r.get("value", 0) != 1.0:
        print(f"ramp survival {r.get('value')} != 1.0")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: autoscale gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --autoscale"
fi

# pruned-discovery gate: propagation groups + batched probes + the
# persistent rule cache must cut execution-discovery probe compiles
# >=5x cold and >=10x warm across the four-variant gpt recompile
# scenario, while the discovered rules AND the solved per-axis
# strategies stay byte-identical to the unpruned (seed-behavior) sweep
if python -c "import jax" >/dev/null 2>&1; then
    echo "== bench.py --discovery (pruned ShardCombine discovery gate)"
    out=$(python bench.py --discovery 2>/dev/null) || rc=1
    echo "$out"
    verdict=$(python - "$out" <<'PYEOF'
import json, sys
try:
    r = json.loads(sys.argv[1].strip().splitlines()[-1])
    if "error" in r:
        print("error: " + r["error"])
    elif r.get("ratio_cold", 0) < 5.0:
        print(f"cold probe reduction {r.get('ratio_cold')}x < 5x "
              f"({r.get('probes_cold')} vs {r.get('probes_baseline')} "
              f"baseline)")
    elif r.get("ratio_warm", 0) < 10.0:
        print(f"warm probe reduction {r.get('ratio_warm')}x < 10x "
              f"({r.get('probes_warm')} vs {r.get('probes_baseline')} "
              f"baseline)")
    elif not r.get("rules_equal"):
        print("pruned discovery rules diverge from the unpruned sweep")
    elif not r.get("strategies_equal"):
        print("pruned solver strategies diverge from the unpruned sweep")
    elif r.get("perf_regression"):
        print(f"committed-floor regression: {r.get('value')} is >10% below "
              f"last-good {r.get('last_good_value')}")
    else:
        print("ok")
except Exception as e:
    print(f"unparseable: {e}")
PYEOF
)
    if [ "$verdict" != "ok" ]; then
        echo "static_checks: discovery gate failed ($verdict)"
        rc=1
    fi
else
    echo "static_checks: jax not importable; skipping bench.py --discovery"
fi

[ "$ran" = 0 ] && echo "static_checks: no external linters ran (configs still validated by CI tests)"
exit $rc
