"""The lockstep batched executor (PR 9 tentpole): live simulators
driven in synchronized epochs with their fabric fills solved through
the batched vmap kernel must be **bit-identical** — not bit-close — to
the scalar ``run_cell`` path: same per-cell metric dicts (completion
orderings included; the metrics are completion-derived), same
aggregate claim JSON bytes, under any gang size, on the kernel and on
the scalar oracle (``use_jax=False``).
The deferred-fill protocol itself is exercised at both ends: the
inline backend as the equivalence anchor, and the settle guard that
refuses to advance time across an undelivered fill."""
import pytest

from repro.sim.network import InlineFillBackend
from repro.sweep import (LockstepExecutor, ResultStore, SweepEngine,
                         aggregate_json, matrix, run_cell)
from repro.sweep.cells import build_fabric_contention
from repro.sweep.lockstep import DeferredFillBackend

#: the bench gate operating point (8 pods x 8 hosts, 24 jobs): fills
#: span enough classes that the batched kernel actually engages — at
#: smaller points every problem falls under the INLINE_C scalar route
#: and the kernel path would go untested
GATE = dict(hosts_per_pod=(8,) * 8, n_jobs=24)


def _specs(n_seeds=2, algos=("joss-t", "fifo"),
           scenarios=("oversub8", "uncontended")):
    return matrix("fabric_contention", algos, scenarios, n_seeds,
                  **GATE)


@pytest.fixture(scope="module")
def scalar_results():
    """The ground truth: every cell through the plain scalar path."""
    return {s.key(): run_cell(s) for s in _specs()}


# ------------------------------------------------ deferred protocol --
def test_inline_backend_is_trajectory_identical(scalar_results):
    """The equivalence anchor: a fabric with the inline deferred
    backend (defer -> solve immediately) reproduces the no-backend run
    bit-for-bit — deferral itself changes nothing."""
    spec = _specs()[0]
    sim, finish = build_fabric_contention(spec)
    sim.begin()
    backend = InlineFillBackend(timed=True)
    sim.fabric.fill_backend = backend
    res = finish(sim.finish(sim.step()))
    assert res == scalar_results[spec.key()]
    assert backend.n_fills > 0 and backend.fill_s > 0.0


def test_settle_guard_refuses_undelivered_fill():
    """A backend that defers and never delivers must be caught at the
    next dt>0 settle, not silently integrate stale rates."""
    sim, _ = build_fabric_contention(_specs()[0])
    sim.begin()
    sim.fabric.fill_backend = DeferredFillBackend()
    with pytest.raises(RuntimeError, match="deferred fill"):
        sim.step()          # no pause predicate: nothing delivers


def _deferred_fabric():
    """A bare fabric with a pending deferred fill (no simulator): one
    flow started under the deferred backend leaves fill_pending set."""
    from repro.core.topology import LinkCapacities
    from repro.sim.engine import EventKernel
    from repro.sim.network import NetworkFabric
    from repro.sim.workloads import make_cluster

    class _Sim:
        pass
    cluster = make_cluster((2, 2),
                           links=LinkCapacities(pod_up=1e6, pod_down=1e6,
                                                wan=100.0))
    fab = NetworkFabric(cluster)
    fab.attach(_Sim(), EventKernel())
    fab.fill_backend = DeferredFillBackend()
    fab.start_flow(0.0, 50.0, 0, 1, cap=1e6, kind="t",
                   done=lambda now: None)
    assert fab.fill_pending
    return fab


def test_settle_time_advance_guard_direct():
    """The ``_settle`` guard itself (PR 10 satellite — previously only
    reachable through the executor): advancing simulated time across an
    undelivered fill raises; a dt == 0 re-settle of the same instant is
    legal (the barrier settles before delivering)."""
    fab = _deferred_fabric()
    fab._settle(0.0)        # same instant: no integration, no error
    assert fab.fill_pending
    with pytest.raises(RuntimeError,
                       match="time advanced across a deferred fill"):
        fab._settle(1.0)
    # delivery clears the flag and time may advance again
    fab.solve_fill_inline()
    assert not fab.fill_pending
    fab._settle(1.0)


def test_fill_delivery_without_pending_raises():
    """Both delivery entry points refuse to run with no deferred fill
    outstanding — a double delivery would re-arm from stale state."""
    fab = _deferred_fabric()
    fab.solve_fill_inline()
    with pytest.raises(RuntimeError, match="no fill pending"):
        fab.solve_fill_inline()
    with pytest.raises(RuntimeError, match="no fill pending"):
        fab.apply_fill([0.0])


# ------------------------------------------------- executor (no jax) --
def test_executor_scalar_path_matches_run_cell(scalar_results):
    ex = LockstepExecutor(use_jax=False)
    res = ex.run(_specs())
    assert res == scalar_results
    assert not ex.stats.used_jax
    assert ex.stats.n_cells == len(scalar_results)
    assert ex.stats.n_fallback == 0
    assert ex.stats.problems > 0 and ex.stats.epochs > 0


def test_executor_falls_back_on_unbatchable_family(scalar_results):
    """Families without a lockstep builder run through scalar
    run_cell inside the executor — mixed matrices still work."""
    fabric = _specs(n_seeds=1)
    elastic = matrix("elastic_churn", ("fifo",), ("flaky",), 1,
                     fleet=(4, 4), n_jobs=12)
    ex = LockstepExecutor(use_jax=False)
    res = ex.run(fabric + elastic)
    assert ex.stats.n_fallback == len(elastic)
    for s in fabric:
        assert res[s.key()] == scalar_results[s.key()]
    for s in elastic:
        assert res[s.key()] == run_cell(s)


# --------------------------------------------------- executor (jax) --
def test_executor_batched_path_bit_identical(scalar_results):
    """The tentpole contract: metrics equal the scalar runs exactly
    and the aggregate claim JSON is byte-identical."""
    ex = LockstepExecutor()
    res = ex.run(_specs())
    assert ex.stats.used_jax
    assert res == scalar_results
    assert (aggregate_json(res)
            == aggregate_json(scalar_results))   # byte-identical


def test_gang_size_never_changes_results(scalar_results):
    """Batch composition is an implementation detail: a gang of 2
    (many small batches, heavy refill churn) and a gang of 64 (one
    batch per epoch) produce identical bytes."""
    small = LockstepExecutor(gang_size=2).run(_specs())
    large = LockstepExecutor(gang_size=64).run(_specs())
    assert small == large == scalar_results


def test_executor_accounts_batches_and_inlining():
    ex = LockstepExecutor()
    ex.run(_specs(n_seeds=1))
    st = ex.stats
    assert st.batches > 0 and st.fill_s > 0.0
    # both routes exercised: some problems inlined (<= INLINE_C
    # classes), the rest batched through the kernel
    assert 0 < st.inline_small < st.problems


# ------------------------------------------------- engine integration --
def test_engine_lockstep_backend_matches_pool(tmp_path, scalar_results):
    """``SweepEngine(backend="lockstep")`` is a drop-in: same results,
    same store entries — a lockstep-populated cache serves a pool
    engine and vice versa."""
    specs = _specs(n_seeds=1)
    store = ResultStore(str(tmp_path))
    engine = SweepEngine(store=store, backend="lockstep")
    res, stats = engine.run(specs)
    assert engine.lockstep_stats is not None
    assert engine.lockstep_stats.n_cells == len(specs)
    assert res == {s.key(): scalar_results[s.key()] for s in specs}
    # warm re-run through a *pool* engine: served from the same store
    res2, stats2 = SweepEngine(store=store, backend="pool").run(specs)
    assert stats2.n_executed == 0 and res2 == res


def test_engine_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        SweepEngine(backend="warp")


# ---------------------------------------- fills_dropped (satellite) --
def _capture_run(capture: int):
    """A small contended run with a fill-capture budget (the lockstep
    builder hardcodes its config, so construct the cell by hand)."""
    from repro.core.joss import make_algorithm
    from repro.sim.cluster_sim import SimConfig, Simulator
    from repro.sim.network import FabricConfig
    from repro.sim.workloads import (fabric_links, make_cluster,
                                     small_workload)
    links = fabric_links((8, 8), wan_oversub=8.0)
    cluster = make_cluster((8, 8), links=links)
    jobs = small_workload(cluster, seed=7, n_jobs=12)
    for j in jobs:
        j.submit_time = 0.0
    cfg = SimConfig(fabric=FabricConfig(completion_log=False,
                                        capture_fills=capture))
    sim = Simulator(cluster, make_algorithm("fifo", cluster), jobs,
                    config=cfg, seed=7)
    sim.run()
    return sim.fabric


def test_fills_dropped_counts_past_capture_budget():
    """``fills_dropped`` mirrors ``log_dropped``: solves past the
    ``capture_fills`` budget are counted, never silently elided — a
    truncated corpus is visible as snapshots + dropped = total."""
    fabric = _capture_run(capture=5)
    assert len(fabric.fill_snapshots) == 5
    assert fabric.summary.fills_dropped > 0


def test_fills_dropped_zero_when_capture_disabled():
    fabric = _capture_run(capture=0)
    assert fabric.fill_snapshots == []
    assert fabric.summary.fills_dropped == 0


# ------------------------------------------- pool workers stay off jax --
def test_pool_worker_path_never_imports_jax():
    """A spawned sweep worker imports ``repro.sweep.engine`` and runs
    ``run_cell``; neither may load JAX, or a worker would contend for the
    accelerator the lockstep parent holds."""
    import os
    import subprocess
    import sys
    spec = _specs(n_seeds=1)[0]
    prog = ("import sys\n"
            "from repro.sweep.engine import _worker_run\n"
            f"_worker_run({spec.key()!r})\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, PYTHONPATH=src))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
