"""The trace reduction: busy time as the union of device operations,
idle gaps named by the host span open in them, all-to-all time, and the
reduction of a small trace recorded on a TPU v5e."""
import glob
import json
import os

import pytest

from bench import spec, trace

FIXTURES = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "fixtures",
                                         "*.trace.json")))


def _trace(device_ops, host, n_dev=1):
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "main", "events": host}]}]
    for d in range(n_dev):
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": [("jit_step(1)", 0, 1e9)]},
            {"name": trace.OPS_LINE, "events": device_ops[d]}]})
    planes.append({"name": "/device:CUSTOM:Megascale Trace", "lines": [
        {"name": trace.OPS_LINE, "events": [("x", 0, 1e9)]}]})
    return {"planes": planes}


def test_union_merges_overlaps_and_gaps_fill_the_rest():
    busy = trace.union([(5, 8), (0, 2), (1, 3), (7, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert trace.covered(trace.clip(busy, 2, 6)) == 2


def test_busy_idle_and_gap_attribution():
    ops = [[("%fusion.1 = u32[8] fusion(%a)", 100, 200),
            ("%sort.0 = u32[8] sort(%fusion.1)", 250, 250),
            ("%fusion.2 = u32[8] fusion(%all-to-all.3)", 900, 50)]]
    host = [("bench.window", 100, 1000), ("bench.job", 100, 600),
            ("bench.compare", 700, 400), ("PjitFunction(f)", 600, 300)]
    r = trace.reduce(_trace(ops, host))
    assert r["window_s"] == pytest.approx(1e-6)
    assert len(r["devices"]) == 1           # the CUSTOM plane is no chip
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["idle_pct"][0] == pytest.approx(55.0)
    # an operand named all-to-all is no all-to-all
    assert r["devices"][0]["all_to_all_s"] == 0
    assert dict(r["device_ops"])["sort.0"] == pytest.approx(250e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.job"] == pytest.approx(200e-9)      # 500..700
    assert gaps["bench.compare"] == pytest.approx(350e-9)  # 700..900, 950..1100


def test_all_to_all_and_idle_per_device():
    ops = [[("%all-to-all.1 = u32[4] all-to-all(%x)", 0, 300),
            ("%fusion = u32[4] fusion(%all-to-all.1)", 300, 100)],
           [("%all-to-all-start = u32[4] all-to-all-start(%x)", 0, 100)]]
    host = [("bench.window", 0, 1000), ("bench.step", 0, 1000)]
    r = trace.reduce(_trace(ops, host, n_dev=2))
    assert [d["all_to_all_s"] for d in r["devices"]] == pytest.approx(
        [300e-9, 100e-9])
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["busy_s_total"] == pytest.approx(500e-9)
    assert r["idle_pct"] == pytest.approx([60.0, 90.0])
    assert dict(r["idle_gaps"]) == {"bench.step": pytest.approx(600e-9)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(_trace([[]], []))


@pytest.mark.parametrize("path", FIXTURES,
                         ids=[os.path.basename(p) for p in FIXTURES])
def test_recorded_trace(path):
    """A small trace recorded on a TPU v5e by ``jax.profiler`` around a
    driver's window, cut to its device operations and bench spans, with
    the reduction the chip printed for it."""
    with open(path) as f:
        recorded = json.load(f)
    with open(path.replace(".trace.json", ".reduced.json")) as f:
        want = json.load(f)
    got = trace.reduce(recorded)
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert len(got["devices"]) == want["n_devices"]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert [n for n, _ in got["device_ops"]] == want["top_ops"]
    a2a = [d["all_to_all_s"] for d in got["devices"]]
    assert (max(a2a) > 0) == want["has_all_to_all"]
