"""Batched max-min progressive fill: the fabric allocator's O(pods^2)
inner loops as a ``jax.vmap``-over-seeds kernel.

The class-aggregated allocator (``repro.sim.network``) spends its
arithmetic in two places: the progressive-filling recompute (pick the
most-constrained link, fix every class crossing it, debit) and the
per-class completion fronts (next completion = min over classes of
``(target - vdone) / rate``). Both are dense arithmetic over O(P^2)
flow-equivalence classes and O(P) links — exactly the shape ``vmap``
batches well: one fill problem is a handful of small arrays, and a
32-seed sweep evaluates hundreds of *independent* problems.

This module holds the accelerator path and its retained pure-Python
twin (the same pattern as ``network_reference``):

  * :func:`fill_reference` — scalar progressive filling + front math on
    one snapshot, mirroring ``NetworkFabric._recompute``/``_reschedule``
    arithmetic operation-for-operation. Equivalence tests hold it
    **bit-identical** to the rates the live allocator recorded.
  * :func:`batched_fill` — the same algorithm as a jitted
    ``vmap(lax.while_loop)`` over a padded batch. Every float64 travels
    as its bit pattern and the kernel's arithmetic is integer-only IEEE
    (:mod:`repro.sweep.exact_f64`), because a TPU emulates f64 with
    pairs of f32 and rounds differently. The kernel is held
    **bit-identical** to the scalar path — rates, per-class etas and
    ``dt_next`` — by ``tests/test_sweep_vmap.py`` and the bench_sweep
    claim checks over real contention-sweep snapshots (captured via
    ``FabricConfig.capture_fills``).

Problems come as the snapshot dicts ``NetworkFabric`` records:

    {"links":   [[tag, idx, cap], ...],          # sorted by link key
     "classes": [{"path": [[tag, idx], ...], "cap": c, "n": k,
                  "vdone": v, "target": t-or-None, "rate": r}, ...],
     "dt_next": seconds-or-None}                 # scalar outputs

``rate`` and ``dt_next`` are what the live allocator computed — the
ground truth the kernels are held against.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64, lax

from repro.compile_cache import enable_compile_cache
from repro.sweep import exact_f64 as xf

_INF = float("inf")


# --------------------------------------------------------- reference --
def fill_reference(snapshot: dict) -> dict:
    """Scalar progressive filling + completion-front math on one
    snapshot — the pure-Python path, arithmetic-identical to
    ``NetworkFabric._recompute`` (same shares, same tie-breaks, same
    ``max(0, rem - k * rate)`` debits, same division order)."""
    links = [((tag, idx), float(cap))
             for tag, idx, cap in snapshot["links"]]
    classes = snapshot["classes"]
    caps = dict(links)
    rem = dict(caps)
    nuse = {k: 0 for k in caps}
    paths = []
    for c in classes:
        path = tuple((tag, idx) for tag, idx in c["path"])
        paths.append(path)
        for link in path:
            nuse[link] += c["n"]
    unfixed = set(range(len(classes)))
    # fill_key = (cap, ("~cap", sig)) with sig = (path, cap): "~cap"
    # is a constant prefix, so the order reduces to (cap, sig)
    cap_order = sorted(unfixed,
                       key=lambda i: (classes[i]["cap"],
                                      (paths[i], classes[i]["cap"])))
    users = {k: [i for i in range(len(classes)) if k in paths[i]]
             for k in caps}
    rates = [0.0] * len(classes)
    ci = 0
    while unfixed:
        best_key = None
        best_link = None
        for link, n in nuse.items():
            if n == 0:
                continue
            key = (rem[link] / n, link)
            if best_key is None or key < best_key:
                best_key, best_link = key, link
        while ci < len(cap_order) and cap_order[ci] not in unfixed:
            ci += 1
        best_cls = None
        if ci < len(cap_order):
            i = cap_order[ci]
            fill_key = (classes[i]["cap"],
                        ("~cap", (paths[i], classes[i]["cap"])))
            if best_key is None or fill_key < best_key:
                best_key, best_link, best_cls = fill_key, None, i
        rate = best_key[0]
        fixed = ([best_cls] if best_cls is not None else
                 [i for i in users[best_link] if i in unfixed])
        dec: Dict[tuple, int] = {}
        for i in fixed:
            rates[i] = rate
            unfixed.discard(i)
            for link in paths[i]:
                dec[link] = dec.get(link, 0) + classes[i]["n"]
        for link, k in dec.items():
            nuse[link] -= k
            rem[link] = max(0.0, rem[link] - k * rate)
    etas = [( (c["target"] - c["vdone"]) / r
              if r > 0.0 and c["target"] is not None else None)
            for c, r in zip(classes, rates)]
    finite = [e for e in etas if e is not None]
    return {"rates": rates, "etas": etas,
            "dt_next": min(finite) if finite else None}


# ----------------------------------------------------------- packing --
class PackedProblems:
    """A batch of snapshots padded to uniform (C, L): the array form
    both kernels consume. Padded links carry zero members and +inf
    capacity; padded classes have n=0 and start pre-fixed."""

    __slots__ = ("caps", "members", "n", "fcap", "vdone", "target",
                 "n_classes", "n_links")

    def __init__(self, snapshots: Sequence[dict]):
        S = len(snapshots)
        # floors of 1: a zero-class/zero-link snapshot (or an empty
        # batch) still packs to valid arrays — its lanes are all
        # padding, which the kernel resolves to rate 0 / eta inf
        self.n_links = L = max(
            1, max((len(s["links"]) for s in snapshots), default=0))
        self.n_classes = C = max(
            1, max((len(s["classes"]) for s in snapshots), default=0))
        self.caps = np.full((S, L), _INF)
        self.members = np.zeros((S, C, L), bool)
        self.n = np.zeros((S, C), np.int32)
        self.fcap = np.full((S, C), _INF)
        self.vdone = np.zeros((S, C))
        self.target = np.full((S, C), _INF)
        for si, snap in enumerate(snapshots):
            link_idx = {}
            for li, (tag, idx, cap) in enumerate(snap["links"]):
                link_idx[(tag, idx)] = li
                self.caps[si, li] = cap
            for cj, c in enumerate(snap["classes"]):
                for link in ((tag, idx) for tag, idx in c["path"]):
                    self.members[si, cj, link_idx[link]] = True
                self.n[si, cj] = c["n"]
                self.fcap[si, cj] = c["cap"]
                self.vdone[si, cj] = c["vdone"]
                if c["target"] is not None:
                    self.target[si, cj] = c["target"]


# ------------------------------------------------------- jax kernel ---
def _fill_one(caps, members, n, fcap):
    """One progressive fill as dense arithmetic, on float64 bit patterns
    (``exact_f64``): ``caps`` (L,) and ``fcap`` (C,) are uint64
    patterns, ``members`` (C, L) bool and ``n`` (C,) int32 member
    counts; returns the (C,) rate patterns. Links are indexed in
    sorted-link-key order, so ``argmin``'s first-minimum rule IS the
    allocator's lexicographic ``(share, link_key)`` tie-break; class
    caps lose exact ties against real links (strict ``<``), mirroring
    the ``("~cap", sig)`` sentinel sort.

    Two deviations from the literal scalar loop, both provably
    bit-identical:

    * a cap win fixes **every** unfixed class whose cap equals the
      winning ``cap_min`` at once, not one per round. The scalar
      allocator fixes them on consecutive rounds — in between, the
      links those classes cross keep ``rem/nuse > cap`` (debiting
      ``k`` members at rate ``cap`` preserves the inequality), so
      no link can snatch a round in the middle; and the combined
      debit equals the sequential ones exactly
      (``max(0, rem - (k1+k2)r)`` == two chained ``max(0, .-kr)``
      steps, including when the clamp engages). Collapsing the
      rounds turns uncontended problems from O(C) iterations into
      O(distinct caps).
    * per-link member counts are carried in the loop state as
      integers and debited instead of recomputed each round.
    """
    C = members.shape[0]
    inf = jnp.asarray(xf.INF, xf.U64)
    fixed = n <= 0            # padded classes never participate
    rates = jnp.zeros((C,), xf.U64)
    nuse0 = jnp.sum(jnp.where(members, n[:, None], 0), axis=0)

    def cond(state):
        fixed, _, _, _ = state
        return jnp.any(~fixed)

    def body(state):
        fixed, rem, rates, nuse = state
        share_l = jnp.where(nuse > 0, xf.div(rem, xf.from_int(nuse)),
                            inf)
        li = jnp.argmin(share_l)             # first min = key order
        link_share = share_l[li]
        cap_key = jnp.where(~fixed, fcap, inf)
        cap_min = jnp.min(cap_key)
        cap_wins = cap_min < link_share
        share = jnp.where(cap_wins, cap_min, link_share)
        newly = jnp.where(cap_wins, cap_key == cap_min,
                          (~fixed) & members[:, li])
        rates = jnp.where(newly, share, rates)
        fixed = fixed | newly
        k_l = jnp.sum(jnp.where(members & newly[:, None], n[:, None], 0),
                      axis=0)
        # rem = max(0, rem - k * share), as the scalar allocator rounds
        # it: the product first, then the difference
        debit = xf.mul(xf.from_int(k_l), share)
        rem = jnp.where(k_l > 0,
                        jnp.where(debit >= rem, jnp.zeros_like(rem),
                                  xf.sub(rem, debit)), rem)
        return fixed, rem, rates, nuse - k_l

    _, _, rates, _ = lax.while_loop(cond, body,
                                    (fixed, caps, rates, nuse0))
    return rates


@functools.lru_cache(maxsize=None)
def _jitted_fill():
    """The one jitted entry: a vmapped fill over a padded batch.
    ``members`` (S, C, L) is bool and ``n`` (S, C) int32; caps (S, L),
    fcap and remaining (S, C) are float64 arrays passed as their uint64
    bit patterns. ``remaining`` is each class's front ``target -
    vdone``, subtracted host-side, inf where no front is armed. Returns the patterns of the rates and
    per-class etas (S, C) and of ``dt_next`` (S,), the earliest eta.
    ``min`` over etas is exact and ``now + min(etas) == min(now +
    eta_i)`` (addition of a common term is monotone), so a rearm from
    ``dt_next`` is bit-identical to the scalar ``_arm`` scan."""
    def one(caps, members, n, fcap, remaining):
        rates = _fill_one(caps, members, n, fcap)
        finite = (remaining & ((1 << 63) - 1)) < xf.INF
        live = (rates != 0) & finite
        etas = jnp.where(live, xf.signed_div(remaining, rates),
                         jnp.asarray(xf.INF, xf.U64))
        dt = xf.from_order_key(jnp.min(xf.order_key(etas)))
        return rates, etas, dt
    return jax.jit(jax.vmap(one))


def _bits(a: np.ndarray) -> np.ndarray:
    return a.view(np.uint64)


def batched_fill(snapshots: Sequence[dict]) -> dict:
    """Evaluate a batch of fill problems on the jax kernel. Returns
    ``{"rates": (S, C), "etas": (S, C), "dt_next": (S,)}`` numpy
    float64 arrays (padded lanes hold rate 0 / eta inf)."""
    enable_compile_cache()
    p = PackedProblems(snapshots)
    remaining = p.target - p.vdone
    with enable_x64(True):
        out = _jitted_fill()(_bits(p.caps), p.members, p.n,
                             _bits(p.fcap), _bits(remaining))
        rates, etas, dt = (np.asarray(x).view(np.float64) for x in out)
    return {"rates": rates, "etas": etas, "dt_next": dt}


# ------------------------------------------------------ live solver ---
def _next_pow2(x: int) -> int:
    """Smallest power of two >= max(1, x) — the shape-bucketing grid."""
    return 1 << max(0, (x - 1).bit_length())


def _ceil_mult(x: int, q: int) -> int:
    """Smallest multiple of ``q`` >= max(1, x) — the live solver's
    padding grid. Finer than pow2 (a 17-link problem pads to 24, not
    32): each padded element costs real flops every while_loop round,
    while an extra distinct shape only costs one cached compile."""
    return q * max(1, -(-x // q))


class BatchedFillSolver:
    """Persistent batched solver for *live* fill problems (the PR 9
    lockstep executor's engine). Differences from :func:`batched_fill`,
    all in service of the per-epoch hot path:

    * consumes the dense problem dicts ``NetworkFabric.fill_problem()``
      emits (arrays already in allocator order) instead of snapshot
      dicts, and returns one rates row per problem in that same order;
    * holds ``enable_x64`` open for its lifetime — entering the context
      per call costs ~50x the solve itself on small batches;
    * solves each epoch's problems in **one** kernel call, padded to
      the batch max (C, L) on a multiples-of-(16, 8) grid with the
      batch dim padded to ``pad_batch`` lanes. Padding is inert in
      every kernel reduction, so each problem's result is bit-exact
      regardless of batch composition, while per-call dispatch — the
      dominant cost at live batch sizes — is paid once per epoch and
      the distinct-shape set XLA ever compiles stays at a handful;
    * enables the persistent compilation cache so cold processes reuse
      compiles across runs.

    Use as a context manager (or call :meth:`close`) to restore the
    global x64 state."""

    def __init__(self, *, pad_batch: int = 64, pad_classes: int = 48,
                 pad_links: int = 24):
        self.pad_batch = _next_pow2(pad_batch)
        self.pad_classes = max(1, int(pad_classes))
        self.pad_links = max(1, int(pad_links))
        enable_compile_cache()
        self._x64 = enable_x64(True)
        self._x64.__enter__()
        self._open = True
        self.n_batches = 0
        self.n_problems = 0
        #: platform of the devices the last batch's outputs were on
        self.platform = ""
        # reusable pack buffers for the (almost always unique) padded
        # shape; {shape: arrays} plus the dirty-row count to reset.
        # Only the latest shape is retained.
        self._bufs: Dict[Tuple[int, int, int], tuple] = {}
        self._dirty_rows: Dict[Tuple[int, int, int], int] = {}

    def close(self) -> None:
        if self._open:
            self._open = False
            self._x64.__exit__(None, None, None)

    def __enter__(self) -> "BatchedFillSolver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def solve(self, problems: Sequence[dict]
              ) -> List[Tuple[np.ndarray, float]]:
        """Solve a batch of ``fill_problem()`` dicts; returns, per
        problem, ``(rates, dt_next)`` — the per-member rate of each
        class in the problem's own class order (shape ``(C_i,)``,
        float64) and the seconds to the earliest completion under
        those rates (``inf`` when no class arms one). ``dt_next`` is
        bit-identical to the scalar ``_arm`` scan, so
        ``apply_fill(rates, dt_next=dt)`` rearms without its per-class
        Python loop."""
        if not problems:
            return []
        # One call for the whole epoch, padded to the batch's max
        # shape on a multiples-of-(16, 8) grid. Padding is *inert* in
        # every reduction — padded links carry inf capacity and no
        # members (never the argmin winner), padded classes start
        # fixed with inf cap keys and inf etas — so a problem's rates
        # and dt are bit-identical under any padding, batch
        # composition included. The grid exists purely to bound the
        # distinct-shape set XLA ever sees: each fresh shape costs a
        # compile (~300ms, persistent-cached) plus a once-per-process
        # cache deserialize (~25ms) that dwarfs thousands of warm
        # calls (~200us) — fewer, coarser shapes beat tighter padding.
        # The pad_* floors make the shape *constant* for a whole run at
        # typical sizes (one compile, one per-process cache load —
        # every first-call-per-shape costs ~60-160ms, an order of
        # magnitude above thousands of warm calls); the ceil_mult
        # escape hatches keep outsized problems correct.
        S = len(problems)
        PC = max(self.pad_classes,
                 _ceil_mult(max(p["n"].shape[0] for p in problems), 16))
        PL = max(self.pad_links,
                 _ceil_mult(max(p["caps"].shape[0] for p in problems),
                            8))
        # S fluctuates every epoch; unpadded it would put the batch
        # size in the jit shape. Padding lanes are all-fixed (n=0)
        # and add no while_loop rounds.
        PS = max(self.pad_batch, _ceil_mult(S, 16))
        bufs = self._bufs.get((PS, PC, PL))
        if bufs is None:
            bufs = (np.full((PS, PL), _INF),        # caps
                    np.zeros((PS, PC, PL), bool),   # members
                    np.zeros((PS, PC), np.int32),   # n
                    np.full((PS, PC), _INF),        # fcap
                    np.full((PS, PC), _INF))        # remaining
            self._bufs = {(PS, PC, PL): bufs}
        caps, members, n, fcap, remaining = bufs
        # restore the pad values the previous call's problems overwrote
        # (rows dirty up to the previous real-lane count). Reuse beats
        # fresh np.full/np.zeros per call: the reset touches S_prev
        # rows, a fresh build allocates and fills all PS.
        dirty = self._dirty_rows.get((PS, PC, PL), 0)
        if dirty:
            caps[:dirty] = _INF
            members[:dirty] = False
            n[:dirty] = 0
            fcap[:dirty] = _INF
            remaining[:dirty] = _INF
        self._dirty_rows = {(PS, PC, PL): S}
        for si, p in enumerate(problems):
            C = p["n"].shape[0]
            L = p["caps"].shape[0]
            caps[si, :L] = p["caps"]
            members[si, :C, :L] = p["members"]
            n[si, :C] = p["n"]
            fcap[si, :C] = p["fcap"]
            remaining[si, :C] = p["remaining"]
        rates, _, dts = _jitted_fill()(_bits(caps), members, n,
                                       _bits(fcap), _bits(remaining))
        self.platform = rates.device.platform
        rates = np.asarray(rates).view(np.float64)
        dts = np.asarray(dts).view(np.float64)
        out: List[Tuple[np.ndarray, float]] = [
            (rates[si, :problems[si]["n"].shape[0]], float(dts[si]))
            for si in range(S)]
        self.n_batches += 1
        self.n_problems += S
        return out


def batched_fill_reference(snapshots: Sequence[dict]) -> dict:
    """The pure-Python loop in the batched API shape — the serial
    baseline of the kernel microbench."""
    S = len(snapshots)
    C = max(1, max((len(s["classes"]) for s in snapshots), default=0))
    rates = np.zeros((S, C))
    etas = np.full((S, C), _INF)
    dt = np.full((S,), _INF)
    for i, snap in enumerate(snapshots):
        ref = fill_reference(snap)
        for j, (r, e) in enumerate(zip(ref["rates"], ref["etas"])):
            rates[i, j] = r
            if e is not None:
                etas[i, j] = e
        if ref["dt_next"] is not None:
            dt[i] = ref["dt_next"]
    return {"rates": rates, "etas": etas, "dt_next": dt}


def contention_snapshots(algo: str = "joss-t",
                         scenario: str = "oversub8", *,
                         n_jobs: int = 12, seed_index: int = 0,
                         hosts_per_pod: Tuple[int, ...] = (8, 8),
                         limit: int = 256) -> List[dict]:
    """The equivalence corpus: real fill problems captured from one
    contention-sweep cell (``FabricConfig.capture_fills``). The cell is
    the same construction as ``repro.sweep.cells``'s
    ``fabric_contention`` family — seed re-derived from the cell key —
    so the corpus is deterministic and cheap to regenerate anywhere."""
    from repro.core.joss import make_algorithm
    from repro.sim.cluster_sim import SimConfig, Simulator
    from repro.sim.network import FabricConfig
    from repro.sim.workloads import (fabric_links, make_cluster,
                                     profiling_prelude, small_workload)
    from repro.sweep.cells import WAN_OVERSUB, CellSpec, make_params
    spec = CellSpec("fabric_contention", algo, scenario, seed_index,
                    make_params(hosts_per_pod=hosts_per_pod,
                                n_jobs=n_jobs))
    seed = spec.sim_seed()
    links = fabric_links(hosts_per_pod,
                         wan_oversub=WAN_OVERSUB[scenario])
    cluster = make_cluster(hosts_per_pod, links=links)
    jobs = small_workload(cluster, seed=seed, n_jobs=n_jobs)
    for j in jobs:
        j.submit_time = 0.0
    algorithm = make_algorithm(algo, cluster)
    if hasattr(algorithm, "registry"):
        for j in profiling_prelude(cluster):
            algorithm.registry.record(j, j.true_fp)
    cfg = SimConfig(fabric=FabricConfig(completion_log=False,
                                        capture_fills=limit))
    sim = Simulator(cluster, algorithm, jobs, config=cfg, seed=seed)
    sim.run()
    return sim.fabric.fill_snapshots
