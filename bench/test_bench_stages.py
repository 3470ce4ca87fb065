"""The stage reduction: device time by ``mr.*`` stage, the bounds of the
host-device clock offset, idle gaps put down to host spans only where the
offset cannot move them, the readers of the stage metrics, and the
reduction of a scoped trace recorded on a TPU v5e."""
import glob
import json
import os
import time

import pytest

from bench import spec, stages, trace

SCOPED = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "fixtures",
                                       "*_scoped.trace.json")))
TABLES = {"WC": {"fusion": "gather", "sort.0": "sort", "fusion.2": "segment",
                 "reduce-window": "unscoped"},
          "SC": {"fusion.9": "map", "sort.0": "sort", "fusion": "gather"}}


def _trace(modules, ops, host):
    """One device with programs ``(name, start, dur, run_id)`` and ops,
    and one host thread of ``(name, start, dur, args)``."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": stages.MODULES_LINE,
             "events": [m[:3] for m in modules],
             "args": [{"run_id": m[3]} for m in modules]},
            {"name": trace.OPS_LINE, "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [h[:3] for h in host],
             "args": [h[3] for h in host]}]}]}


def _two_jobs(delta=0.0, launch=True):
    """WC on [1000, 2000) and SC on [2500, 3000) of the device clock; the
    host's spans lie ``delta`` later, with dispatch 100 before each
    program, the job span closing 50 after it, launches 20 before and
    completions 10 after."""
    modules = [("jit_wc", 1000, 1000, 7), ("jit_sc", 2500, 500, 8)]
    ops = [("%fusion = u32[8] fusion(%a)", 1000, 400),
           ("%sort.0 = u32[8] sort(%fusion)", 1400, 300),
           ("%fusion.2 = u32[8] fusion(%x)", 1700, 200),
           ("%reduce-window = u32[8] reduce-window(%y)", 1900, 50),
           ("%copy.7 = u32[8] copy(%y)", 1950, 50),
           ("%fusion.9 = u32[8] fusion(%a)", 2500, 100),
           ("%sort.0 = u32[8] sort(%fusion.9)", 2600, 100),
           ("%fusion = u32[8] fusion(%sort.0)", 2700, 300)]
    d = delta
    host = [("bench.window", 800 + d, 2400, {}),
            ("bench.job", 850 + d, 1200, {"job": "WC"}),
            ("mr.dispatch", 900 + d, 60, {"job": "WC"}),
            ("bench.job", 2350 + d, 700, {"job": "SC"}),
            ("mr.dispatch", 2400 + d, 60, {"job": "SC"})]
    if launch:
        host += [(stages.LAUNCH, 980 + d, 5, {"run_id": 7}),
                 (stages.COMPLETE, 2010 + d, 5, {"run_id": 7}),
                 (stages.LAUNCH, 2480 + d, 5, {"run_id": 8}),
                 (stages.COMPLETE, 3010 + d, 5, {"run_id": 8})]
    return _trace(modules, ops, host)


def test_stage_of_takes_the_innermost_mr_scope():
    assert stages.stage_of("jit(f)/mr.segment/scatter-add") == "segment"
    assert stages.stage_of("jit(f)/mr.map/mr.sort/jit(argsort)/sort") == (
        "sort")
    assert stages.stage_of("jit(f)/mr.shuffle/all_to_all") == "unscoped"
    assert stages.stage_of("reduce_window_sum") == "unscoped"


def test_stage_table_reads_every_instruction_of_the_hlo_text():
    hlo = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation (p: u32[8]) -> u32[8] {",
        '  ROOT %g.1 = u32[8] gather(%p), metadata={op_name="jit(f)/'
        'mr.gather/gather"}',
        "}",
        "ENTRY %main (t: s32[8]) -> u32[8] {",
        '  %sort.0 = u32[8] sort(%t), metadata={op_name="jit(f)/mr.sort/'
        'jit(argsort)/sort" source_file="e.py"}',
        "  %reduce-window = u32[8] reduce-window(%t), window={size=8}",
        '  ROOT %fusion = u32[8] fusion(%sort.0), kind=kCustom, '
        'calls=%fused_computation, metadata={op_name="jit(f)/mr.gather/'
        'gather"}',
        "}"])
    assert stages.stage_table(hlo) == {
        "g.1": "gather", "sort.0": "sort", "reduce-window": "unscoped",
        "fusion": "gather"}


def test_device_time_by_stage_and_job():
    r = stages.reduce(_two_jobs(), TABLES)
    # the window [800, 3200) holds both programs whole
    assert r["stage_s"] == pytest.approx({
        "map": 100e-9, "sort": 400e-9, "gather": 700e-9,
        "segment": 200e-9, "unscoped": 100e-9})
    assert sum(r["stage_s"].values()) == pytest.approx(
        trace.reduce(_two_jobs())["busy_s_total"])
    # copy.7 is in no table: unscoped, and counted as unmatched
    assert r["unmatched_s"] == pytest.approx(50e-9)
    assert r["device_ms_by_job"] == {
        "WC": pytest.approx({"runs": 1, "gather": 400e-6, "sort": 300e-6,
                             "segment": 200e-6, "unscoped": 100e-6}),
        "SC": pytest.approx({"runs": 1, "map": 100e-6, "sort": 100e-6,
                             "gather": 300e-6})}
    assert r["dispatch_us"] == pytest.approx([0.06, 0.06])


def test_stage_time_is_clipped_to_the_window():
    t = _two_jobs()
    host = t["planes"][1]["lines"][0]
    host["events"][0] = ("bench.window", 1500, 1100)   # [1500, 2600)
    r = stages.reduce(t, TABLES)
    assert r["stage_s"]["gather"] == 0       # WC's ran before, SC's after
    assert r["stage_s"]["sort"] == pytest.approx(200e-9)   # 1500..1700
    assert r["stage_s"]["map"] == pytest.approx(100e-9)


@pytest.mark.parametrize("delta", [-150.0, 0.0, 33.0, 150.0])
def test_clock_bounds_contain_a_planted_offset(delta):
    r = stages.reduce(_two_jobs(delta), TABLES)
    lo, hi = r["clock_offset_us"]
    assert lo <= delta * 1e-3 <= hi
    # launches 20 before each program, completions 10 after its end
    assert (lo, hi) == pytest.approx(((delta - 20) * 1e-3,
                                      (delta + 10) * 1e-3))
    # the harness's spans alone: dispatch 100 before, job close 50 after
    r = stages.reduce(_two_jobs(delta, launch=False), TABLES)
    assert r["clock_offset_us"] == pytest.approx(
        [(delta - 100) * 1e-3, (delta + 50) * 1e-3])


def test_idle_pieces_near_span_edges_are_uncertain():
    """Device idle: [800, 1000), [2000, 2500), [3000, 3200). With the
    offset in [-20, 10], a host boundary at h moves over [h-10, h+20] of
    the device clock; only the rest is put down to a span."""
    r = stages.reduce(_two_jobs(), TABLES)
    idle = dict(r["idle_by_span"])
    window = trace.reduce(_two_jobs())["window_s"]
    busy = trace.reduce(_two_jobs())["busy_s"]
    assert sum(idle.values()) == pytest.approx(window - busy)
    # boundaries: job WC 850, 2050; dispatch 900, 960; job SC 2350, 3050;
    # dispatch 2400, 2460; each leaves 30 uncertain inside a gap
    assert idle[stages.UNCERTAIN] == pytest.approx(8 * 30e-9)
    # [920, 950) and [2420, 2450)
    assert idle["mr.dispatch"] == pytest.approx(60e-9)
    # [870, 890), [980, 1000), [2000, 2040), [2370, 2390), [2480, 2500),
    # [3000, 3040)
    assert idle["bench.job"] == pytest.approx(160e-9)
    # [800, 840), [2070, 2340), [3070, 3200)
    assert idle["(no span)"] == pytest.approx(440e-9)


def test_no_offset_fits_leaves_idle_unsplit():
    t = _two_jobs()
    host = t["planes"][1]["lines"][0]
    # SC's completion before its program ends on the device clock
    i = host["events"].index((stages.COMPLETE, 3010, 5))
    host["events"][i] = (stages.COMPLETE, 2950, 5)
    r = stages.reduce(t, TABLES)
    lo, hi = r["clock_offset_us"]
    assert lo > hi and r["idle_by_span"] is None


def test_the_cut_trace_reduces_as_the_whole():
    t = _two_jobs()
    t["planes"][0]["lines"].append(
        {"name": "Async XLA Ops", "events": [("%slice-start", 0, 9e3)]})
    t["planes"][1]["lines"][0]["events"].append(("PjitFunction(f)", 0, 9e3))
    t["planes"][1]["lines"][0]["args"].append({})
    small = stages.cut(t, TABLES)
    assert small["stage_tables"]["WC"] == {
        "fusion": "gather", "sort.0": "sort", "fusion.2": "segment",
        "reduce-window": "unscoped"}
    assert "fusion.9" in small["stage_tables"]["SC"]
    assert trace.reduce(small) == trace.reduce(t)
    assert stages.reduce(small, small["stage_tables"]) == stages.reduce(
        t, TABLES)


RECORD = {"counters": {"blocks": 4},
          "trace": {"stage_s": {"map": 0.04, "sort": 0.4, "gather": 2.0,
                                "segment": 1.2, "unscoped": 0.08},
                    "dispatch_us": [300.0, 500.0, 410.0]}}


@pytest.mark.parametrize("metric,value", [
    ("mr.map_ms_per_block", 10.0), ("mr.sort_ms_per_block", 100.0),
    ("mr.gather_ms_per_block", 500.0), ("mr.segment_ms_per_block", 300.0),
    ("mr.dispatch_us_per_job", 410.0)])
def test_stage_metric_readers(metric, value):
    read = spec.load_reader(metric)
    assert read(RECORD) == pytest.approx(value)
    assert read({"counters": {"blocks": 4}}) is None
    # the parent's traced record has no stage fields
    assert read({"counters": {"blocks": 4}, "trace": {"busy_s": 1.0}}) is (
        None)
    assert [m["name"] for m in stages.METRICS].count(metric) == 1


def test_a_traced_cpu_run_keeps_job_arguments_and_dispatch_spans(tmp_path):
    """The driver's window traced on the CPU: ``load`` keeps each job
    span's and dispatch span's job, one dispatch span per job."""
    import jax
    from bench.tiny import tiny_cell
    from bench.window import Window
    cell = tiny_cell("mr-puma5-1chip")
    rec = cell.driver(cell, seed=2 ** 33 + 7, seconds=0.01,
                      window=Window(str(tmp_path)),
                      devices=jax.devices()[:1], t_start=time.perf_counter())
    assert rec["correct"]
    t = stages.load(str(tmp_path))
    host = [(n, a) for n, s, d, a in stages._events(t, device=False)]
    jobs = [a["job"] for n, a in host if n == "bench.job"]
    assert jobs == list(cell.traffic["jobs"])
    assert [a["job"] for n, a in host if n == stages.DISPATCH_SPAN] == jobs
    assert len(stages.reduce(t, {})["dispatch_us"]) == len(jobs)


@pytest.mark.parametrize("path", SCOPED,
                         ids=[os.path.basename(p) for p in SCOPED])
def test_recorded_scoped_trace(path):
    """Five jobs at a small block, traced on a TPU v5e by ``python3 -m
    bench.stages --fixture``, with both reductions the chip printed."""
    with open(path) as f:
        recorded = json.load(f)
    with open(path.replace(".trace.json", ".reduced.json")) as f:
        want = json.load(f)["stages"]
    got = stages.reduce(recorded, recorded["stage_tables"])
    busy = trace.reduce(recorded)["busy_s_total"]
    assert got["stage_s"] == pytest.approx(want["stage_s"])
    assert sum(got["stage_s"].values()) == pytest.approx(busy)
    assert got["unmatched_s"] == 0
    assert set(got["device_ms_by_job"]) == {"WC", "SC", "II", "Grep",
                                            "Permu"}
    assert got["dispatch_us"] == pytest.approx(want["dispatch_us"])
    lo, hi = got["clock_offset_us"]
    assert [lo, hi] == pytest.approx(want["clock_offset_us"])
    assert lo <= hi
    assert dict(got["idle_by_span"]) == pytest.approx(
        dict(want["idle_by_span"]))
