#!/usr/bin/env bash
# CI gate, in named stages with per-stage timing:
#
#   lint             — python -m compileall (syntax/import rot fails fast)
#                      + ruff when available
#   tier-1           — the full pytest suite
#   claim-checks     — quick benchmark runs that hard-assert the paper's
#                      claims AND the indexed fast path's perf envelope
#                      (assign µs/slot at the 4096-host point, dispatch
#                      events/s vs the naive reference)
#   elastic-claims   — churn-disabled bit-identity with the static
#                      simulator, disabled-durability bit-identity with
#                      the PR 2 elastic simulator, per-seed determinism,
#                      no-assignment-to-departed-hosts, re-replication
#                      locality gain, checkpoint zero-loss and the
#                      replication-factor trade-off — all asserted
#                      inside bench_elastic
#   fabric-claims    — fabric-disabled bit-identity with the committed
#                      PR 3 golden trajectories (25 cases), bit-identity
#                      of the class-aggregated allocator with the
#                      per-flow reference (every contention cell + the
#                      scale point), per-stream parity on an uncontended
#                      fabric, INT ordering, the contention-widens-JoSS-
#                      margin probe, flow-completion determinism, and
#                      the allocator speedup floor — all asserted inside
#                      bench_fabric
#   migration-claims — graceful-preemption claims, all asserted inside
#                      bench_migration: the notice-window sweep is
#                      monotone (more warning, less work lost), the
#                      claims probe holds losses to <= 5% of the
#                      kill+requeue baseline with strictly fewer
#                      re-executions for all five algorithms, the
#                      restore path runs, migration traffic is bounded,
#                      zero-notice runs are bit-identical to
#                      no-migration runs, decisions are deterministic
#                      per seed, and fleet compaction cuts VPS-hours
#                      and WTT on the straggler tail without losing work
#   chaos-claims     — chaos-layer claims, all asserted inside
#                      bench_chaos: the attached-but-calm fault layer
#                      (empty campaign + inert detector) is bit-identical
#                      to the committed golden trajectories, the calm
#                      campaign injects and detects nothing, the hostile-
#                      campaign detection A/B probe cuts WTT AND task
#                      re-executions vs detection-off for all five
#                      algorithms with every job still finishing under
#                      quarantine, and injection/decision logs are
#                      deterministic per seed
#   obs-claims       — telemetry claims, all asserted inside bench_obs:
#                      telemetry-on runs are bit-identical to all 25
#                      committed golden trajectories, events/s stays
#                      inside the overhead envelope at the contended
#                      scale point (trajectory itself bit-identical
#                      on/off), the scoreboard exposes per-window
#                      utilization for every fabric link, a scoreboard-
#                      fed BacklogThresholdScaler reproduces the
#                      observation-fed run's full signature, the trace
#                      JSONL is byte-stable per seed (sha256), and
#                      trace_limit caps the buffer while counting drops
#   sweep-claims     — the run-matrix orchestrator's own claims, all
#                      asserted inside bench_sweep: per-cell results
#                      bit-identical across worker counts and shuffled
#                      submission orders (aggregate JSON byte-identical),
#                      warm content-addressed re-runs >= 20x the serial
#                      baseline with zero cells re-executed, the scalar
#                      fill reference bit-identical to the live
#                      allocator, the batched vmap kernel bit-close with
#                      identical completion orderings, and the
#                      statistical claim rows (paired JoSS WTT gap CI >
#                      0 at every oversubscribed level, widening with
#                      contention, INT CIs disjoint). SWEEP_LANE=full
#                      (main) runs 32 seeds; the default fast lane (PRs)
#                      runs 8
#   lockstep-claims  — the PR 9 lockstep executor's claims, all
#                      asserted inside bench_sweep.run_lockstep: the
#                      batched executor's per-cell metrics bit-identical
#                      to serial scalar runs at the committed gate
#                      point (aggregate claim JSON byte-identical), the
#                      scalar deferred oracle bit-identical too,
#                      and the batched fill path holds the throughput
#                      smoke floor (full 3x envelope gated on the
#                      committed BENCH_sweep.json lockstep block by
#                      bench-regression)
#   bench-regression — fresh dispatch sweep vs the committed
#                      BENCH_dispatch.json trajectory (>25% regression at
#                      the 4096/8192-host points fails) + re-simulated
#                      elastic WTT vs BENCH_elastic.json (any drift is a
#                      behaviour change, tolerance 0.1%) + fresh
#                      contended fabric events/s vs the BENCH_fabric.json
#                      gate point (which must also hold the 5x
#                      fast-vs-reference acceptance envelope) + the
#                      migration row of BENCH_elastic.json re-simulated
#                      bit-exactly (loss/re-exec/restore counters and
#                      the decision-log signature must match, and the
#                      <= 5% loss envelope must hold) + the committed
#                      chaos detection gate of BENCH_chaos.json
#                      re-simulated bit-exactly (WTT / re-exec / timeout
#                      / quarantine counters and the injection- and
#                      decision-log signatures must match, and detection
#                      must beat detection-off on WTT and re-executions
#                      for every stored algorithm) + the committed
#                      BENCH_obs.json telemetry gate (stored overhead
#                      ratio must hold the 90% envelope; the trace
#                      probe re-simulated and its sha256/event count
#                      must match bit-exactly) + the PR 8 statistical
#                      gates (committed sweep speedup >= 20x re-measured
#                      fresh; every committed claim row n >= 32 with a
#                      CI; fresh reduced-seed CIs must overlap the
#                      stored ones) + the PR 9 lockstep gate (the
#                      committed lockstep block must hold the 3x
#                      fill-path envelope at >= 32 seeds; a fresh
#                      reduced-seed run must stay bit-identical to
#                      scalar execution and clear the half-envelope
#                      smoke floor)
#
# Every stage carries a soft time budget; a per-stage table at the end
# flags overruns as warnings (never failures — budgets catch creep, the
# assertions catch breakage).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# sweep lane: "full" (main; 32 seeds, rewrites the committed claim rows'
# working copies) vs the default "fast" PR lane (8 seeds, report only)
SWEEP_LANE="${SWEEP_LANE:-fast}"

STAGE_NAMES=()
STAGE_TIMES=()
STAGE_BUDGETS=()

stage() {
    local name="$1" budget="$2"; shift 2
    echo "== ${name} =="
    local t0=$SECONDS
    "$@"
    local dt=$((SECONDS - t0))
    STAGE_NAMES+=("$name")
    STAGE_TIMES+=("$dt")
    STAGE_BUDGETS+=("$budget")
    echo "-- [stage ${name}: ${dt}s (budget ${budget}s)]"
}

budget_table() {
    echo "== stage time budgets =="
    printf '%-18s %8s %8s  %s\n' stage time budget status
    local i over=0
    for i in "${!STAGE_NAMES[@]}"; do
        local status=ok
        if (( STAGE_TIMES[i] > STAGE_BUDGETS[i] )); then
            status="WARN over budget (soft)"
            over=$((over + 1))
        fi
        printf '%-18s %7ss %7ss  %s\n' "${STAGE_NAMES[$i]}" \
            "${STAGE_TIMES[$i]}" "${STAGE_BUDGETS[$i]}" "$status"
    done
    if (( over > 0 )); then
        echo "-- ${over} stage(s) over budget; soft warning only"
    fi
}

lint() {
    python -m compileall -q src benchmarks scripts tests
    if command -v ruff >/dev/null 2>&1; then
        ruff check src benchmarks scripts tests
    else
        echo "(ruff not installed; compileall only)"
    fi
}

sweep_claims() {
    if [ "$SWEEP_LANE" = "full" ]; then
        python -m benchmarks.run --only sweep
    else
        python -m benchmarks.run --fast --only sweep
    fi
}

stage lint 90 lint
stage tier-1 900 python -m pytest -x -q
stage claim-checks 900 python -m benchmarks.run --quick --only overhead,dispatch,small
stage elastic-claims 900 python -m benchmarks.run --quick --only elastic
stage fabric-claims 900 python -m benchmarks.run --quick --only fabric
stage migration-claims 600 python -m benchmarks.run --quick --only migration
stage chaos-claims 600 python -m benchmarks.run --quick --only chaos
stage obs-claims 600 python -m benchmarks.run --quick --only obs
stage sweep-claims 600 sweep_claims
stage lockstep-claims 300 python -m benchmarks.run --quick --only lockstep
stage bench-regression 900 python scripts/check_bench_regression.py
budget_table
echo "== CI green: $((SECONDS))s total =="
