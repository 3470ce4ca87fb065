"""Equivalence of the batched ``jax.vmap`` progressive-fill kernel with
the scalar allocator (PR 8 satellite): the pure-Python reference must be
**bit-identical** to the rates the live allocator recorded, the batched
kernel bit-identical to the reference (rates, per-class etas and
``dt_next``), and padding must never let one problem leak into
another."""
import numpy as np
import pytest

from repro.sweep import vmap_fill as vf


@pytest.fixture(scope="module")
def corpus():
    """Real fill problems captured from one contended cell."""
    snaps = vf.contention_snapshots("joss-t", "oversub8", limit=80)
    assert len(snaps) >= 20, "capture seam produced too few problems"
    return snaps


# ------------------------------------------------- scalar reference --
def test_reference_bit_identical_to_live_allocator(corpus):
    for snap in corpus:
        ref = vf.fill_reference(snap)
        recorded = [c["rate"] for c in snap["classes"]]
        assert ref["rates"] == recorded      # bit-identical floats
        if snap["dt_next"] is None:
            assert ref["dt_next"] is None
        else:
            assert ref["dt_next"] == pytest.approx(snap["dt_next"],
                                                   rel=1e-12)


def test_reference_even_split_on_one_link():
    snap = {"links": [["wan", 0, 10.0]],
            "classes": [{"path": [["wan", 0]], "cap": 100.0, "n": 1,
                         "vdone": 0.0, "target": 5.0},
                        {"path": [["wan", 0]], "cap": 100.0, "n": 1,
                         "vdone": 2.5, "target": 5.0}]}
    ref = vf.fill_reference(snap)
    assert ref["rates"] == [5.0, 5.0]
    assert ref["dt_next"] == 0.5             # (5 - 2.5) / 5


def test_reference_class_cap_beats_link_share():
    snap = {"links": [["wan", 0, 10.0]],
            "classes": [{"path": [["wan", 0]], "cap": 2.0, "n": 1,
                         "vdone": 0.0, "target": 4.0},
                        {"path": [["wan", 0]], "cap": 100.0, "n": 1,
                         "vdone": 0.0, "target": None}]}
    ref = vf.fill_reference(snap)
    # the capped class fixes at 2; the survivor takes the remaining 8
    assert ref["rates"] == [2.0, 8.0]
    assert ref["dt_next"] == 2.0             # only the finite target


# ---------------------------------------------------- batched kernel --
def test_batched_fill_bit_close_with_identical_orderings(corpus):
    batch = vf.batched_fill(corpus)
    ref = vf.batched_fill_reference(corpus)
    assert batch["rates"].shape == ref["rates"].shape
    for key in ("rates", "etas", "dt_next"):
        assert np.array_equal(batch[key], ref[key]), key


def test_padding_never_leaks_across_problems(corpus):
    """Mixed-shape batches pad every problem to the widest (C, L); a
    problem's row must not depend on what it is batched with."""
    sizes = {len(s["classes"]) for s in corpus}
    assert len(sizes) > 1, "corpus is uniform; padding untested"
    full = vf.batched_fill(corpus)
    for i in (0, len(corpus) // 2, len(corpus) - 1):
        alone = vf.batched_fill([corpus[i]])
        c = len(corpus[i]["classes"])
        assert np.array_equal(alone["rates"][0, :c], full["rates"][i, :c])
        assert alone["dt_next"][0] == full["dt_next"][i]


def test_padded_lanes_stay_inert(corpus):
    batch = vf.batched_fill(corpus)
    for i, snap in enumerate(corpus):
        c = len(snap["classes"])
        assert np.all(batch["rates"][i, c:] == 0.0)
        assert np.all(np.isinf(batch["etas"][i, c:]))


def test_batched_reference_matches_scalar(corpus):
    ref = vf.batched_fill_reference(corpus)
    for i, snap in enumerate(corpus):
        one = vf.fill_reference(snap)
        c = len(snap["classes"])
        assert list(ref["rates"][i, :c]) == one["rates"]


# ----------------------------------------- degenerate packed inputs --
def test_packed_zero_class_snapshot_is_all_padding():
    """A snapshot with links but no classes (an idle fabric) packs to
    the floor shape with every lane inert: n=0, inf caps, no members."""
    snap = {"links": [["wan", 0, 10.0]], "classes": []}
    p = vf.PackedProblems([snap])
    assert p.n_classes == 1 and p.n_links == 1
    assert np.all(p.n == 0.0)
    assert np.all(np.isinf(p.fcap)) and np.all(np.isinf(p.target))
    assert np.all(p.members == 0.0)
    assert vf.fill_reference(snap) == {"rates": [], "etas": [],
                                       "dt_next": None}


def test_packed_empty_batch_has_floor_shapes():
    p = vf.PackedProblems([])
    assert p.caps.shape == (0, 1) and p.n.shape == (0, 1)
    assert p.members.shape == (0, 1, 1)


def test_batched_fill_zero_class_snapshot_resolves_inert():
    out = vf.batched_fill([{"links": [["wan", 0, 10.0]],
                            "classes": []}])
    assert np.all(out["rates"] == 0.0)
    assert np.all(np.isinf(out["etas"]))
    assert np.all(np.isinf(out["dt_next"]))


def _single_flow_snap():
    # one class, one member flow, crossing one link: rate is the
    # whole link (cap doesn't bind), eta = (target - vdone) / rate
    return {"links": [["wan", 0, 6.0]],
            "classes": [{"path": [["wan", 0]], "cap": 100.0, "n": 1,
                         "vdone": 1.0, "target": 4.0}]}


def _all_capped_snap():
    # every class's own cap undercuts its link share: the fill fixes
    # all of them at cap and the link is left slack
    return {"links": [["wan", 0, 100.0]],
            "classes": [{"path": [["wan", 0]], "cap": 2.0, "n": 2,
                         "vdone": 0.0, "target": 8.0},
                        {"path": [["wan", 0]], "cap": 3.0, "n": 1,
                         "vdone": 1.0, "target": None}]}


def test_reference_single_flow_class():
    ref = vf.fill_reference(_single_flow_snap())
    assert ref["rates"] == [6.0]
    assert ref["dt_next"] == 0.5              # (4 - 1) / 6


def test_reference_all_capped_classes():
    ref = vf.fill_reference(_all_capped_snap())
    assert ref["rates"] == [2.0, 3.0]
    assert ref["dt_next"] == 4.0              # (8 - 0) / 2


def test_batched_fill_degenerate_snapshots_match_reference():
    """Zero-class, single-flow and all-capped problems through one
    mixed batch: each row bit-identical to its scalar reference, the empty
    row fully inert."""
    snaps = [{"links": [["wan", 0, 10.0]], "classes": []},
             _single_flow_snap(), _all_capped_snap()]
    out = vf.batched_fill(snaps)
    refb = vf.batched_fill_reference(snaps)
    assert np.array_equal(out["rates"], refb["rates"])
    assert np.array_equal(out["dt_next"], refb["dt_next"])
    assert np.all(out["rates"][0] == 0.0)


# ------------------------------------------------------ live solver --
def _problem(snapshot):
    """A ``fill_problem()``-shaped dict from a snapshot (same packing
    the fabric does, including ``remaining = target - vdone``)."""
    p = vf.PackedProblems([snapshot])
    C = max(1, len(snapshot["classes"]))
    L = max(1, len(snapshot["links"]))
    return {"caps": p.caps[0, :L], "members": p.members[0, :C, :L],
            "n": p.n[0, :C], "fcap": p.fcap[0, :C],
            "remaining": p.target[0, :C] - p.vdone[0, :C]}


def test_solver_matches_reference_on_corpus(corpus):
    with vf.BatchedFillSolver() as solver:
        sols = solver.solve([_problem(s) for s in corpus])
    assert len(sols) == len(corpus)
    for snap, (rates, dt) in zip(corpus, sols):
        ref = vf.fill_reference(snap)
        c = len(snap["classes"])
        assert rates.shape == (max(1, c),)
        assert list(rates[:c]) == ref["rates"]
        if ref["dt_next"] is None:
            assert np.isinf(dt)
        else:
            assert dt == ref["dt_next"]


def test_solver_results_independent_of_batch_composition(corpus):
    """The solver's padding-inertness claim is *bit*-exact: a problem
    solved alone, in a small batch, or in the full epoch batch returns
    identical bytes — batch composition can never perturb a lane."""
    probs = [_problem(s) for s in corpus]
    with vf.BatchedFillSolver() as solver:
        full = solver.solve(probs)
        for i in (0, len(probs) // 2, len(probs) - 1):
            alone = solver.solve([probs[i]])[0]
            assert np.array_equal(alone[0], full[i][0])
            assert (alone[1] == full[i][1]
                    or (np.isinf(alone[1]) and np.isinf(full[i][1])))
        assert solver.n_batches == 4 and solver.n_problems > len(probs)


def test_solver_degenerate_problems():
    """Zero-class / single-flow / all-capped problems through the live
    solver in one batch."""
    empty = {"links": [["wan", 0, 10.0]], "classes": []}
    snaps = [empty, _single_flow_snap(), _all_capped_snap()]
    with vf.BatchedFillSolver() as solver:
        sols = solver.solve([_problem(s) for s in snaps])
        assert solver.solve([]) == []
    (r0, dt0), (r1, dt1), (r2, dt2) = sols
    assert np.all(r0 == 0.0) and np.isinf(dt0)   # padding lane only
    assert list(r1) == [6.0] and dt1 == 0.5
    assert list(r2) == [2.0, 3.0] and dt2 == 4.0
