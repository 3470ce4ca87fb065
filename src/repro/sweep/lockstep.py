"""Lockstep batched execution: many live simulators, one fill kernel.

PR 8 proved the ``jax.vmap`` progressive-fill kernel bit-close against
the live allocator — on *captured* corpora. This module makes the
accelerator path live: the seeds/cells of one sweep group run as
resumable coroutines (``Simulator.begin/step/finish``) advancing in
synchronized epochs, and every fabric fill the epoch produces is solved
in one batched kernel call instead of one scalar recompute per fabric.

The mechanism, end to end:

1. Each lane's fabric gets a :class:`FillBackend` whose ``defer`` does
   nothing but leave ``fill_pending`` set — the flag doubles as the
   event kernel's ``pause`` predicate, so the simulator suspends at the
   exact event boundary where the inline allocator would have solved.
2. The executor steps every lane until it pauses (a fill is pending) or
   drains, then gathers the pending problems — dense arrays straight
   from ``NetworkFabric.fill_problem()`` — and hands the whole epoch to
   ``vmap_fill.BatchedFillSolver`` — one kernel call per epoch, padded
   to a coarse shape grid that bounds jit recompiles (padding is inert
   in every kernel reduction, so each problem's result is independent
   of batch composition).
3. Rates go back through ``apply_fill``, which rearms the completion
   event with the *same* ``_arm`` arithmetic the inline path uses; the
   lane resumes next epoch exactly where it paused.

Lanes are **not** time-synchronized — each advances at its own pace
between barriers, one fill problem per lane per epoch. A dynamic gang
(default 64 lanes) refills from the cell queue as lanes retire, keeping
batches full for the whole matrix.

Correctness contract (tests/test_lockstep.py): per-cell metrics equal
to scalar ``run_cell`` runs under ``==`` and byte-identical aggregate
claim JSON — equality is checked downstream, not here. The kernel always
runs; a JAX that cannot load raises. ``use_jax=False`` is the scalar
test oracle: ``solve_fill_inline`` per lane (same deferred protocol,
scalar solve), arithmetic-identical to the inline path by construction.

This module imports JAX only when a solver is built, so sweep pool
workers, which import ``repro.sweep`` to run cells, never load it and
never contend for the parent's accelerator.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Sequence

from repro.sim.network import FillBackend
from repro.sweep.cells import LOCKSTEP_BUILDERS, CellSpec, run_cell

MetricRow = Dict[str, float]

#: problems with at most this many classes are solved inline at the
#: barrier: the scalar recompute on a handful of classes is cheaper
#: than the batched path's fixed per-problem cost (pack + jit dispatch
#: + apply), measured crossover ~8-12 classes on 1 CPU core
INLINE_C = 8


class DeferredFillBackend(FillBackend):
    """The lockstep fabric hook: ``defer`` is a no-op because the
    ``fill_pending`` flag it leaves behind *is* the whole signal — the
    kernel's pause predicate reads it, and the executor delivers rates
    at the epoch barrier."""

    def defer(self, fabric, now: float) -> None:
        pass


@dataclasses.dataclass
class LockstepStats:
    """Execution accounting for one :meth:`LockstepExecutor.run`."""

    n_cells: int = 0      # cells completed (batched + fallback)
    n_fallback: int = 0   # cells run scalar (family not batchable)
    epochs: int = 0       # barrier rounds
    problems: int = 0     # fill problems delivered at barriers
    inline_small: int = 0  # problems routed to the scalar solve (<= INLINE_C)
    batches: int = 0      # kernel invocations (pow2 buckets x epochs)
    fill_s: float = 0.0   # wall seconds in the batched fill path
    wall_s: float = 0.0
    used_jax: bool = False
    platform: str = ""    # device platform the kernel's outputs were on


class _Lane:
    """One live cell: its simulator, its result adapter, and the last
    event time ``step`` returned (the makespan once drained)."""

    __slots__ = ("key", "sim", "fabric", "finish", "end", "pause")

    def __init__(self, key: str, sim, finish):
        self.key = key
        self.sim = sim
        self.fabric = sim.fabric
        self.finish = finish
        self.end = 0.0
        # Pause only once the pending fill's rates could actually be
        # read: rates are consumed exclusively by dt>0 settles, so the
        # lane keeps stepping while the heap head cannot cause one.
        # Two coalescing opportunities fall out, both with bit-identical
        # trajectories (the inline allocator must solve every
        # reschedule — it cannot know one is about to be superseded):
        #
        #  * same-instant events (head time == now): zero-dt settles
        #    never read rates, so every reschedule in the burst
        #    supersedes the last and only the instant's *final*
        #    flow-set state needs solving;
        #  * armed "flow" events: while a fill is pending, every flow
        #    event in the heap is stale — arming only ever happens at
        #    delivery, so any armed event predates (and was superseded
        #    by) the epoch bump that marked the fill pending. Its
        #    handler is an epoch-mismatch no-op that settles nothing.
        #
        # Only a *foreign* strictly-later head (heartbeat, call, task
        # event — anything that may settle) or heap exhaustion forces
        # delivery.
        kern = sim.kernel
        heap = kern._heap
        fabric = sim.fabric

        def pause(f=fabric, h=heap, k=kern):
            if not f._fill_pending:
                return False
            if not h:
                return True
            head = h[0]
            return head[0] > k.now and head[2] != "flow"

        self.pause = pause


class LockstepExecutor:
    """Drives a cell list through the lockstep protocol. ``gang_size``
    bounds concurrent lanes (memory: each lane is a full simulator);
    ``use_jax=False`` forces the scalar deferred path, the oracle of the
    equivalence tests."""

    def __init__(self, *, gang_size: int = 64, use_jax: bool = True):
        self.gang_size = max(1, int(gang_size))
        self.use_jax = bool(use_jax)
        self.stats = LockstepStats()

    def run(self, specs: Sequence[CellSpec]) -> Dict[str, MetricRow]:
        """Execute every cell; returns ``{cell key: metrics}`` sorted
        by canonical key, exactly the shape ``SweepEngine.run`` results
        take. Families without a lockstep builder fall back to the
        scalar ``run_cell`` path inline."""
        t0 = time.perf_counter()
        st = self.stats
        results: Dict[str, MetricRow] = {}
        batchable: List[CellSpec] = []
        for spec in specs:
            if spec.family in LOCKSTEP_BUILDERS:
                batchable.append(spec)
            else:
                results[spec.key()] = run_cell(spec)
                st.n_fallback += 1
                st.n_cells += 1
        solver = None
        if self.use_jax and batchable:
            from repro.sweep.vmap_fill import BatchedFillSolver
            # pad_batch = gang size: pending lanes per epoch never
            # exceed the gang, so the batch dim (like the class/link
            # floors) stays one constant jit shape for the whole run
            solver = BatchedFillSolver(pad_batch=self.gang_size)
            st.used_jax = True
        # Dozens of live simulators mean a large stable object graph;
        # at the default gen0 threshold (~700 allocations) the
        # collector re-scans it constantly — ~20% of the executor's
        # wall time, measured. Collect once, then raise the threshold
        # for the drive; restored (with a final sweep) on exit.
        thresh = gc.get_threshold()
        gc.collect()
        gc.set_threshold(max(thresh[0], 100_000), *thresh[1:])
        try:
            self._drive(batchable, results, solver)
        finally:
            gc.set_threshold(*thresh)
            gc.collect()
            if solver is not None:
                st.batches = solver.n_batches
                st.platform = solver.platform
                solver.close()
        st.wall_s = time.perf_counter() - t0
        return {k: results[k] for k in sorted(results)}

    def _drive(self, specs: Sequence[CellSpec],
               results: Dict[str, MetricRow], solver) -> None:
        st = self.stats
        queue = list(specs)
        queue.reverse()          # pop() keeps submission order
        backend = DeferredFillBackend()
        gang: List[_Lane] = []
        while queue or gang:
            # refill: keep the gang (and therefore the batches) full
            while queue and len(gang) < self.gang_size:
                spec = queue.pop()
                builder = LOCKSTEP_BUILDERS[spec.family]
                sim, finish = builder(spec)
                sim.begin()
                if sim.fabric is None:
                    raise RuntimeError(
                        f"lockstep builder for {spec.family!r} built a "
                        "simulator without a fabric")
                sim.fabric.fill_backend = backend
                gang.append(_Lane(spec.key(), sim, finish))
            # epoch: advance every lane to its next fill (or further)
            pending: List[_Lane] = []
            for lane in gang:
                fabric = lane.fabric
                assert not fabric.fill_pending, \
                    "lane resumed with an undelivered fill"
                lane.end = lane.sim.step(pause=lane.pause)
                if fabric.fill_pending:
                    pending.append(lane)
            st.epochs += 1
            # barrier: one batched solve for the whole epoch
            if pending:
                t1 = time.perf_counter()
                if solver is not None:
                    # tiny problems go scalar: below ~INLINE_C classes
                    # the inline recompute beats the batched path's
                    # fixed per-problem cost (pack + dispatch + apply),
                    # and padding them into the batch would only
                    # stretch its while_loop
                    batched = []
                    for lane in pending:
                        if len(lane.fabric._order) <= INLINE_C:
                            lane.fabric.solve_fill_inline()
                            st.inline_small += 1
                        else:
                            batched.append(lane)
                    if batched:
                        sols = solver.solve(
                            [l.fabric.fill_problem() for l in batched])
                        for lane, (row, dt) in zip(batched, sols):
                            # apply_fill converts to plain floats
                            # itself; numpy scalars never touch
                            # progress arithmetic
                            lane.fabric.apply_fill(row, dt_next=dt)
                else:
                    for lane in pending:
                        lane.fabric.solve_fill_inline()
                st.fill_s += time.perf_counter() - t1
                st.problems += len(pending)
            # retire drained lanes (their last fill, if any, was just
            # delivered above, so finalize's settle sees solved rates)
            still: List[_Lane] = []
            for lane in gang:
                if lane.sim._drained():
                    results[lane.key] = lane.finish(
                        lane.sim.finish(lane.end))
                    st.n_cells += 1
                elif (len(lane.sim.kernel) == 0
                      and not lane.fabric.fill_pending):
                    raise RuntimeError(
                        f"lockstep deadlock: cell {lane.key} has an "
                        "empty event heap but unfinished work")
                else:
                    still.append(lane)
            gang = still
