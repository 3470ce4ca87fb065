"""PR 8 tentpole: the vectorized Monte-Carlo sweep engine and its
statistical claims.

Everything statistical in the repo now flows through
``repro.sweep``: the (algorithm x scenario x seed) run matrices, the
content-addressed result store, and the bootstrap-CI aggregation that
turns per-cell metrics into the claim rows committed in
``BENCH_fabric.json`` / ``BENCH_elastic.json``. This bench measures the
orchestrator itself and asserts its contracts:

  * **throughput** — re-running the full contention matrix against a
    warm content-addressed store must be >= ``MIN_SWEEP_SPEEDUP`` (20x)
    faster per cell than the serial single-process baseline
    (``run_serial``); on unchanged code a sweep re-run is effectively
    free, which is what makes 32-seed statistical gates affordable in
    CI;
  * **determinism** — the same sub-matrix through an inline engine, a
    shuffled submission order, and a spawn pool produces bit-identical
    per-cell metric dicts and a byte-identical aggregate JSON (workers
    re-derive every RNG stream from the cell key and *poison* their
    inherited globals, so pool state cannot leak into results);
  * **cache transparency** — cells served from the store equal the
    freshly-executed ones bit-for-bit, and a fully warm re-run executes
    zero simulations;
  * **vmap equivalence** — the batched ``jax.vmap`` progressive-fill
    kernel (``repro.sweep.vmap_fill``) is held against real fill
    problems captured from a contended run: the scalar reference is
    **bit-identical** to what the live allocator recorded, the batched
    kernel bit-identical to the reference (rates, etas, fronts),
    plus a problems/s microbench of batched vs serial evaluation.

Statistical claims (the paper's Fig. 12 story with error bars, n_seeds
>= 32 on full runs):

  * the per-seed paired WTT gap (mean baseline - mean JoSS) has a
    bootstrap CI entirely above zero at every oversubscribed level —
    JoSS's win is statistically significant, not a lucky seed;
  * the mean gap widens with WAN oversubscription;
  * at every contention level, the worst JoSS INT CI sits entirely
    below the best baseline INT CI (disjoint intervals).

Full (non-quick, non-fast) runs write ``BENCH_sweep.json`` (orchestrator
gate + determinism + vmap rows) and refresh the ``claims`` blocks of
``BENCH_fabric.json`` and ``BENCH_elastic.json`` in place — claims can
be updated without re-running the expensive fabric scale sweeps.
``scripts/check_bench_regression.py`` gates all three: the committed
speedup must hold the 20x envelope (re-measured fresh), every committed
claim row must carry n >= 32 with a CI, and a fresh reduced-seed sweep
must not produce a CI disjoint from the stored one in the bad
direction.
"""
from __future__ import annotations

import hashlib
import json
import os
import random
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.common import table
from repro.sweep import (ResultStore, SweepEngine, aggregate,
                         aggregate_cells, aggregate_json,
                         code_fingerprint, matrix, run_serial)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(_ROOT, "BENCH_sweep.json")
FABRIC_JSON_PATH = os.path.join(_ROOT, "BENCH_fabric.json")
ELASTIC_JSON_PATH = os.path.join(_ROOT, "BENCH_elastic.json")

ALGOS = ("joss-t", "joss-j", "fifo", "fair", "capacity")
JOSS = ("joss-t", "joss-j")
BASELINES = ("fifo", "fair", "capacity")

#: the contention matrix (the bench_fabric sweep with seeds): WAN
#: oversubscription levels from repro.sweep.cells.WAN_OVERSUB
SCENARIOS = ("uncontended", "oversub8", "oversub24")
#: the elastic churn matrix (the bench_elastic sweep with seeds)
ELASTIC_SCENARIOS = ("flaky", "spot")

#: metrics carried as committed claim rows (means + bootstrap CIs)
FABRIC_CLAIM_METRICS = ("wtt", "int_mb")
ELASTIC_CLAIM_METRICS = ("wtt", "work_lost_mb", "cost_dollars",
                         "n_reexec")

#: the orchestrator acceptance envelope: warm-store cells/s over the
#: serial single-process baseline at the full contention matrix
MIN_SWEEP_SPEEDUP = 20.0

#: the PR 9 lockstep acceptance envelope: scalar inline fill-path
#: seconds over lockstep batched fill-path seconds at the committed
#: gate point (fill-path throughput, not end-to-end wall — stepping
#: the simulators costs the same either way and dilutes the ratio)
MIN_LOCKSTEP_FILL_SPEEDUP = 3.0

#: the lockstep gate point: 8 pods x 8 hosts, 24 jobs — 17 fabric
#: links and fills spanning up to ~47 traffic classes, large enough
#: that the batched kernel beats the scalar allocator per problem
LOCKSTEP_HOSTS_PER_POD = (8,) * 8
LOCKSTEP_N_JOBS = 24

#: replicas per (algorithm, scenario) point on full sweeps — the floor
#: every committed claim row must carry
FULL_SEEDS = 32
FAST_SEEDS = 8


def sweep_seeds(reduced: bool) -> int:
    """Replica count: ``SWEEP_SEEDS`` env override, else 32 full /
    8 reduced (the --fast PR lane and --quick CI stages)."""
    env = os.environ.get("SWEEP_SEEDS")
    if env:
        return max(2, int(env))
    return FAST_SEEDS if reduced else FULL_SEEDS


def contention_matrix(n_seeds: int) -> list:
    return matrix("fabric_contention", ALGOS, SCENARIOS, n_seeds,
                  hosts_per_pod=(8, 8), n_jobs=12)


def elastic_matrix(n_seeds: int) -> list:
    return matrix("elastic_churn", ALGOS, ELASTIC_SCENARIOS, n_seeds,
                  fleet=(8, 8), n_jobs=40)


def lockstep_matrix(n_seeds: int) -> list:
    """The lockstep gate matrix: the contention family at the larger
    8x8-pod / 24-job operating point (480 cells at 32 seeds)."""
    return matrix("fabric_contention", ALGOS, SCENARIOS, n_seeds,
                  hosts_per_pod=LOCKSTEP_HOSTS_PER_POD,
                  n_jobs=LOCKSTEP_N_JOBS)


def _by_spec(results: Dict[str, dict]) -> Dict[tuple, dict]:
    """{(algo, scenario, seed): metrics} view of an engine result."""
    out = {}
    for key, metrics in results.items():
        d = json.loads(key)
        out[(d["algo"], d["scenario"], d["seed"])] = metrics
    return out


def fabric_claims(results: Dict[str, dict]) -> Tuple[List[dict],
                                                     List[dict]]:
    """The committed fabric claim rows: per-(scenario, algo) summary
    rows for ``FABRIC_CLAIM_METRICS``, plus one paired-gap row per
    scenario — ``gap_i = mean(baseline WTT) - mean(JoSS WTT)`` within
    replica ``i``, aggregated over replicas. Pairing by replica index
    cancels none of the variance (each cell derives its own seed) but
    keeps the row count independent of the algorithm split."""
    rows = aggregate_cells(results, metrics=FABRIC_CLAIM_METRICS)
    cells = _by_spec(results)
    seeds = sorted({s for (_, _, s) in cells})
    gaps: List[dict] = []
    for scen in SCENARIOS:
        vals = []
        for i in seeds:
            mean_joss = sum(cells[(a, scen, i)]["wtt"]
                            for a in JOSS) / len(JOSS)
            mean_base = sum(cells[(a, scen, i)]["wtt"]
                            for a in BASELINES) / len(BASELINES)
            vals.append(mean_base - mean_joss)
        row = {"scenario": scen, "metric": "wtt_gap"}
        row.update(aggregate(vals, key=f"{scen}:wtt_gap"))
        gaps.append(row)
    return rows, gaps


def elastic_claims(results: Dict[str, dict]) -> List[dict]:
    """The committed elastic claim rows: per-(scenario, algo) summary
    rows for ``ELASTIC_CLAIM_METRICS``."""
    return aggregate_cells(results, metrics=ELASTIC_CLAIM_METRICS)


def claim_row(rows: Sequence[dict], scenario: str, algo: Optional[str],
              metric: str) -> dict:
    for r in rows:
        if (r.get("scenario") == scenario and r.get("metric") == metric
                and r.get("algo", None) == algo):
            return r
    raise KeyError((scenario, algo, metric))


def _merge_key(path: str, key: str, value: dict) -> None:
    """Read-modify-write one top-level block of a committed BENCH
    file, preserving every block another bench owns (e.g. the
    migration row bench_migration owns in BENCH_elastic.json, or the
    lockstep block in BENCH_sweep.json)."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except OSError:
        payload = {}
    payload[key] = value
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def _merge_claims(path: str, claims: dict) -> None:
    _merge_key(path, "claims", claims)


def refresh_fabric_claims(n_seeds: int = FULL_SEEDS) -> Tuple[List[dict],
                                                              List[dict]]:
    """Recompute and re-commit BENCH_fabric.json's claims block through
    the orchestrator (free on unchanged code thanks to the store) —
    lets a full --only fabric sweep refresh its claim rows without
    re-running this bench, and vice versa."""
    engine = SweepEngine(workers=1, store=ResultStore())
    results, _ = engine.run(contention_matrix(n_seeds))
    rows, gaps = fabric_claims(results)
    _merge_claims(FABRIC_JSON_PATH,
                  {"n_seeds": n_seeds, "rows": rows, "gaps": gaps})
    return rows, gaps


def refresh_elastic_claims(n_seeds: int = FULL_SEEDS) -> List[dict]:
    """BENCH_elastic.json counterpart of :func:`refresh_fabric_claims`
    (the migration row and gated points are preserved)."""
    engine = SweepEngine(workers=1, store=ResultStore())
    results, _ = engine.run(elastic_matrix(n_seeds))
    rows = elastic_claims(results)
    _merge_claims(ELASTIC_JSON_PATH, {"n_seeds": n_seeds, "rows": rows})
    return rows


def run(quick: bool = False, fast: bool = False) -> str:
    n_seeds = sweep_seeds(quick or fast)
    write = not (quick or fast)
    fp = code_fingerprint()
    store = ResultStore()
    engine = SweepEngine(workers=1, store=store)
    out = (f"\n## Sweep engine — run-matrix orchestrator "
           f"(n_seeds={n_seeds}, store fingerprint {fp[:16]})")

    # ------------------------------------------------ execute matrices --
    specs = contention_matrix(n_seeds)
    results, cold = engine.run(specs)
    e_specs = elastic_matrix(n_seeds)
    e_results, e_cold = engine.run(e_specs)
    out += (f"\n\ncontention matrix: {cold.n_cells} cells "
            f"({cold.n_cached} cached, {cold.n_executed} executed, "
            f"{cold.wall_s:.1f}s); elastic matrix: {e_cold.n_cells} "
            f"cells ({e_cold.n_cached} cached, {e_cold.n_executed} "
            f"executed, {e_cold.wall_s:.1f}s)")

    # --------------------------------------- throughput: warm vs serial --
    results_warm, warm = engine.run(specs)
    assert warm.n_executed == 0, \
        "warm sweep re-executed cells the store should have served"
    assert results_warm == results, \
        "warm (cached) sweep diverged from the executed results"
    sample = [s for s in specs if s.seed < max(1, min(2, n_seeds))]
    t0 = time.perf_counter()
    serial_results = run_serial(sample)
    serial_s = time.perf_counter() - t0
    serial_cps = len(sample) / serial_s
    speedup = warm.cells_per_s / serial_cps
    assert speedup >= MIN_SWEEP_SPEEDUP, \
        f"warm sweep only {speedup:.1f}x the serial baseline " \
        f"(need >= {MIN_SWEEP_SPEEDUP:.0f}x)"
    assert all(results[k] == v for k, v in serial_results.items()), \
        "serial baseline diverged from the orchestrated results"
    out += "\n" + table(
        "Sweep throughput — warm content-addressed store vs serial "
        f"single-process baseline ({warm.n_cells}-cell contention "
        "matrix; the envelope the CI gate re-checks)",
        ["path", "cells", "wall s", "cells/s"],
        [["serial (sample)", len(sample), f"{serial_s:.2f}",
          f"{serial_cps:.1f}"],
         ["warm store", warm.n_cells, f"{warm.wall_s:.3f}",
          f"{warm.cells_per_s:.0f}"],
         ["speedup", "-", "-", f"{speedup:.0f}x"]])
    out += (f"\n[claim check: warm sweep >= {MIN_SWEEP_SPEEDUP:.0f}x "
            f"serial ({speedup:.0f}x), re-run executed 0 cells, cached "
            "== executed bit-for-bit]")

    # ------------------------------------------------ determinism claims --
    det = [s for s in specs if s.seed == 0]
    r_inline, _ = SweepEngine(workers=1, store=None).run(det)
    shuffled = random.Random(0xC0FFEE).sample(det, len(det))
    r_shuf, _ = SweepEngine(workers=1, store=None).run(shuffled)
    n_pool = 2 if (quick or fast) else 4
    r_pool, _ = SweepEngine(workers=n_pool, store=None).run(det)
    assert r_inline == r_shuf, \
        "shuffled submission order changed per-cell results"
    assert r_inline == r_pool, \
        f"pool-of-{n_pool} diverged from the inline engine"
    agg_a = aggregate_json(r_inline, metrics=FABRIC_CLAIM_METRICS)
    agg_b = aggregate_json(r_shuf, metrics=FABRIC_CLAIM_METRICS)
    agg_c = aggregate_json(r_pool, metrics=FABRIC_CLAIM_METRICS)
    assert agg_a == agg_b == agg_c, \
        "aggregate JSON is not byte-identical across schedules"
    assert all(results[k] == v for k, v in r_inline.items()), \
        "store-served cells diverged from a fresh no-store run"
    agg_sha = hashlib.sha256(agg_a.encode()).hexdigest()
    out += (f"\n[claim check: inline == shuffled-order == "
            f"pool-of-{n_pool} bit-identical on {len(det)} cells; "
            f"aggregate JSON byte-identical (sha {agg_sha[:12]}...)]")

    # ------------------------------------------------------ vmap kernel --
    from repro.sweep import vmap_fill as vf
    snaps = vf.contention_snapshots(
        "joss-t", "oversub8", limit=120 if (quick or fast) else 240)
    rec_rates = [np.array([c["rate"] for c in s["classes"]])
                 for s in snaps]
    for s, rec in zip(snaps, rec_rates):
        ref = vf.fill_reference(s)
        assert np.array_equal(np.asarray(ref["rates"]), rec), \
            "scalar fill reference diverged from the live allocator"
    out += (f"\n[claim check: scalar fill reference bit-identical to "
            f"the live allocator on {len(snaps)} captured fill "
            "problems]")
    vmap_row: dict = {"n_snapshots": len(snaps)}
    batch = vf.batched_fill(snaps)          # compiles
    refb = vf.batched_fill_reference(snaps)
    for key in ("rates", "etas", "dt_next"):
        assert np.array_equal(batch[key], refb[key]), \
            f"batched fill {key} differ from the scalar reference"
    t0 = time.perf_counter()
    vf.batched_fill(snaps)                   # warm, compiled
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    vf.batched_fill_reference(snaps)
    ref_s = time.perf_counter() - t0
    vmap_row.update(
        batched_problems_per_s=len(snaps) / batched_s,
        ref_problems_per_s=len(snaps) / ref_s,
        ratio=ref_s / batched_s)
    out += "\n" + table(
        "Batched fill kernel — problems/s over the captured corpus "
        "(vmap over independent fill problems vs the scalar loop)",
        ["path", "problems", "wall s", "problems/s"],
        [["vmap (jit, warm)", len(snaps), f"{batched_s:.3f}",
          f"{len(snaps) / batched_s:.0f}"],
         ["scalar loop", len(snaps), f"{ref_s:.3f}",
          f"{len(snaps) / ref_s:.0f}"]])
    out += (f"\n[claim check: batched kernel bit-identical to the "
            f"scalar allocator on all {len(snaps)} problems]")

    # ------------------------------------------- statistical claim rows --
    rows, gaps = fabric_claims(results)
    e_rows = elastic_claims(e_results)
    assert all(r["n"] == n_seeds for r in rows + gaps + e_rows), \
        "claim rows lost replicas"
    g_disp = []
    for g in gaps:
        if g["scenario"] != "uncontended":
            assert g["ci_lo"] > 0.0, \
                f"JoSS WTT gap not significant under {g['scenario']}: " \
                f"CI [{g['ci_lo']:.1f}, {g['ci_hi']:.1f}]"
        g_disp.append([g["scenario"], f"{g['mean']:.1f}",
                       f"[{g['ci_lo']:.1f}, {g['ci_hi']:.1f}]",
                       g["n"]])
    for (a, b) in zip(gaps, gaps[1:]):
        assert b["mean"] > a["mean"], \
            f"mean WTT gap did not widen {a['scenario']} -> " \
            f"{b['scenario']}"
    for scen in SCENARIOS:
        worst_joss = max(claim_row(rows, scen, a, "int_mb")["ci_hi"]
                         for a in JOSS)
        best_base = min(claim_row(rows, scen, a, "int_mb")["ci_lo"]
                        for a in BASELINES)
        assert worst_joss < best_base, \
            f"INT CIs overlap under {scen}: joss hi {worst_joss:.0f} " \
            f"vs baseline lo {best_base:.0f}"
    out += "\n" + table(
        f"Paired WTT gap (mean baseline - mean JoSS) over {n_seeds} "
        "seeds — the paper's contention story with error bars "
        "(bootstrap 95% CI)",
        ["wan", "gap s", "95% CI", "n"], g_disp)
    out += ("\n[claim check: gap CI > 0 at every oversubscribed level, "
            "mean gap widens with oversubscription, and every JoSS INT "
            "CI is disjoint below every baseline INT CI]")

    # -------------------------------------------------- committed files --
    if write:
        # read-modify-write: the lockstep block (owned by run_lockstep)
        # survives a full sweep refresh
        for key, value in (
                ("matrix", {"family": "fabric_contention",
                            "algos": list(ALGOS),
                            "scenarios": list(SCENARIOS),
                            "n_seeds": n_seeds, "n_cells": cold.n_cells}),
                ("gate", {"n_seeds": n_seeds, "n_cells": warm.n_cells,
                          "serial_cells_per_s": serial_cps,
                          "warm_cells_per_s": warm.cells_per_s,
                          "speedup": speedup,
                          "serial_sample": len(sample),
                          "fingerprint": fp[:16]}),
                ("determinism", {"n_cells": len(det),
                                 "workers_checked": [1, n_pool],
                                 "aggregate_sha256": agg_sha}),
                ("vmap", vmap_row)):
            _merge_key(JSON_PATH, key, value)
        _merge_claims(FABRIC_JSON_PATH,
                      {"n_seeds": n_seeds, "rows": rows, "gaps": gaps})
        _merge_claims(ELASTIC_JSON_PATH,
                      {"n_seeds": n_seeds, "rows": e_rows})
        out += (f"\n\n[wrote {os.path.basename(JSON_PATH)}; refreshed "
                "claims blocks in BENCH_fabric.json and "
                "BENCH_elastic.json]")
    else:
        report = os.path.join(_ROOT, "SWEEP_REPORT.json")
        with open(report, "w") as f:
            json.dump({"n_seeds": n_seeds, "fingerprint": fp[:16],
                       "fabric": rows, "gaps": gaps,
                       "elastic": e_rows}, f, indent=1, sort_keys=True)
            f.write("\n")
        out += f"\n\n[reduced-seed run: aggregate report -> {report}]"
    return out


def _scalar_baseline(specs) -> Tuple[Dict[str, dict], float, float, int]:
    """Serial scalar reference for the lockstep table: every cell runs
    through the same lockstep builder but with a *timed* inline
    backend, so the fill-path seconds are the honest cost of the
    scalar allocator doing exactly the solves the inline path does
    (no deferral, no coalescing). Returns (results, wall_s, fill_s,
    n_fills)."""
    from repro.sim.network import InlineFillBackend
    from repro.sweep.cells import LOCKSTEP_BUILDERS
    results: Dict[str, dict] = {}
    fill_s = 0.0
    n_fills = 0
    t0 = time.perf_counter()
    for spec in specs:
        sim, finish = LOCKSTEP_BUILDERS[spec.family](spec)
        sim.begin()
        backend = InlineFillBackend(timed=True)
        sim.fabric.fill_backend = backend
        end = sim.step()
        results[spec.key()] = finish(sim.finish(end))
        fill_s += backend.fill_s
        n_fills += backend.n_fills
    wall_s = time.perf_counter() - t0
    return ({k: results[k] for k in sorted(results)},
            wall_s, fill_s, n_fills)


def run_lockstep(quick: bool = False, fast: bool = False) -> str:
    """PR 9 tentpole bench: the lockstep batched executor vs the
    scalar inline allocator vs the process pool, at the committed
    gate point (``LOCKSTEP_HOSTS_PER_POD`` x ``LOCKSTEP_N_JOBS``).

    Asserted claims:

      * **bit-identity** — lockstep per-cell metric dicts equal the
        scalar runs exactly (completion orderings included: the
        metrics are completion-derived) and the aggregate claim JSON
        is byte-identical;
      * **scalar oracle** — the executor's scalar deferred path
        (``use_jax=False``) reproduces the same results bit-for-bit;
      * **fill throughput** — the batched fill path is >=
        ``MIN_LOCKSTEP_FILL_SPEEDUP`` (3x) faster than the scalar
        allocator's fill path on full runs (half that as a smoke
        floor on reduced --quick/--fast lanes, where per-run noise
        on 120 cells is material).

    Full runs merge a ``lockstep`` block into ``BENCH_sweep.json``
    (read-modify-write — the orchestrator blocks ``run`` owns are
    preserved), which ``scripts/check_bench_regression.py`` gates.
    """
    from repro.sweep import LockstepExecutor
    n_seeds = sweep_seeds(quick or fast)
    write = not (quick or fast)
    specs = lockstep_matrix(n_seeds)
    out = (f"\n## Lockstep batched execution — live simulation through "
           f"the vmap fill kernel ({len(specs)} cells at "
           f"{len(LOCKSTEP_HOSTS_PER_POD)}x"
           f"{LOCKSTEP_HOSTS_PER_POD[0]} hosts, "
           f"{LOCKSTEP_N_JOBS} jobs, n_seeds={n_seeds})")

    # ------------------------------------------------- scalar baseline --
    scalar, s_wall, s_fill, s_fills = _scalar_baseline(specs)

    # ------------------------------------------------ lockstep executor --
    ex = LockstepExecutor()
    res = ex.run(specs)
    st = ex.stats
    assert set(res) == set(scalar), "lockstep lost or invented cells"
    assert all(res[k] == scalar[k] for k in scalar), \
        "lockstep per-cell metrics diverged from the scalar runs"
    agg_l = aggregate_json(res, metrics=FABRIC_CLAIM_METRICS)
    agg_s = aggregate_json(scalar, metrics=FABRIC_CLAIM_METRICS)
    assert agg_l == agg_s, \
        "lockstep aggregate claim JSON is not byte-identical"
    agg_sha = hashlib.sha256(agg_l.encode()).hexdigest()

    # ------------------------------------------------ scalar oracle --
    nojax_specs = [s for s in specs if s.seed == 0]
    nojax = LockstepExecutor(use_jax=False).run(nojax_specs)
    assert all(nojax[s.key()] == scalar[s.key()] for s in nojax_specs), \
        "scalar deferred path (use_jax=False) diverged from the inline runs"

    # ------------------------------------------------- process pool row --
    n_pool = 2 if (quick or fast) else 4
    t0 = time.perf_counter()
    r_pool, _ = SweepEngine(workers=n_pool, store=None).run(specs)
    pool_wall = time.perf_counter() - t0
    assert r_pool == scalar, \
        f"pool-of-{n_pool} diverged from the scalar baseline"

    # -------------------------------------------------------- the table --
    fill_speedup = s_fill / st.fill_s if st.fill_s > 0 else float("inf")
    coalesce = st.problems / max(1, s_fills)
    out += "\n" + table(
        "Lockstep vs scalar vs process pool — same cells, bit-identical "
        "metrics; 'fill s' is wall time inside the allocator (the gated "
        "axis), 'wall s' is end-to-end",
        ["path", "cells", "fill s", "fill solves", "wall s"],
        [["scalar inline", len(specs), f"{s_fill:.2f}", s_fills,
          f"{s_wall:.2f}"],
         ["lockstep (batched)", st.n_cells, f"{st.fill_s:.2f}",
          st.problems, f"{st.wall_s:.2f}"],
         [f"process pool x{n_pool}", len(r_pool), "-", "-",
          f"{pool_wall:.2f}"],
         ["fill speedup", "-", f"{fill_speedup:.2f}x", "-",
          f"{s_wall / st.wall_s:.2f}x"]])
    out += (f"\n[lockstep: {st.epochs} epochs, {st.batches} kernel "
            f"batches, {st.inline_small} small problems inlined, "
            f"deferred coalescing {coalesce:.2f}x "
            f"({st.problems} problems vs {s_fills} inline solves), "
            f"used_jax={st.used_jax}, platform={st.platform}]")
    out += (f"\n[claim check: lockstep bit-identical to scalar on "
            f"{len(specs)} cells (aggregate sha {agg_sha[:12]}...); "
            f"scalar deferred path bit-identical on "
            f"{len(nojax_specs)} cells]")

    floor = (MIN_LOCKSTEP_FILL_SPEEDUP if write
             else MIN_LOCKSTEP_FILL_SPEEDUP / 2)
    assert fill_speedup >= floor, \
        f"lockstep fill path only {fill_speedup:.2f}x the scalar " \
        f"allocator (need >= {floor:.1f}x; fill s {s_fill:.2f} scalar " \
        f"vs {st.fill_s:.2f} lockstep over {st.batches} batches, " \
        f"platform={st.platform})"
    out += (f"\n[claim check: batched fill path {fill_speedup:.2f}x "
            f"the scalar allocator (floor {floor:.1f}x)]")

    if write:
        _merge_key(JSON_PATH, "lockstep", {
            "hosts_per_pod": list(LOCKSTEP_HOSTS_PER_POD),
            "n_jobs": LOCKSTEP_N_JOBS, "n_seeds": n_seeds,
            "n_cells": len(specs), "gang_size": ex.gang_size,
            "scalar_fill_s": s_fill, "scalar_fills": s_fills,
            "lockstep_fill_s": st.fill_s, "problems": st.problems,
            "epochs": st.epochs, "batches": st.batches,
            "inline_small": st.inline_small,
            "fill_speedup": fill_speedup,
            "scalar_wall_s": s_wall, "lockstep_wall_s": st.wall_s,
            "pool_wall_s": pool_wall, "pool_workers": n_pool,
            "aggregate_sha256": agg_sha})
        out += (f"\n\n[merged lockstep block into "
                f"{os.path.basename(JSON_PATH)}]")
    return out


if __name__ == "__main__":
    print(run())
