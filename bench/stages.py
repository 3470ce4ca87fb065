"""The MapReduce engine's device time by stage, the host-device clock
offset, and idle gaps put down to the host span over them.

    python3 -m bench.stages --workload <cell> --seed <n> --seconds <s>
        [--fixture <dir>]

runs the cell as ``bench.run --trace 1`` does and prints the same result
line, with the metrics of :data:`METRICS` added, ``clock_offset_us`` and
``device_ms_by_job`` among its counters, and ``stages`` and
``idle_by_span`` in its breakdown. ``--fixture`` runs it at a small
block instead and writes the trace, cut to what the reductions read, and
both reductions to ``<dir>/mr_local_v5e_scoped.{trace,reduced}.json``.

The engine names its stages with ``jax.named_scope`` (``mr.map``,
``mr.sort``, ``mr.gather``, ``mr.segment``); a stage is the innermost
``mr.*`` component of an operation's ``op_name``. The TPU's trace does
not carry ``op_name`` on its ``XLA Ops`` events, so each job's compiled
HLO text gives an ``{operation: stage}`` table (:func:`stage_table`). An
operation belongs to the program (``XLA Modules`` event) it starts in,
and a program to the job whose ``bench.job`` span overlaps it most.

:func:`load` reads the trace into :mod:`bench.trace`'s plain form, which
:func:`bench.trace.reduce` also reads, and keeps beside each line's
events the arguments of :data:`KEPT_ARGS` (``"args"``, one dict per
event, on lines where any event has one). :func:`reduce` works on that
form and the tables alone, so it is checked on a recorded trace.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

from bench import spec, trace

STAGES = ("map", "sort", "gather", "segment")
#: operations whose op_name holds no stage, such as those XLA makes
UNSCOPED = "unscoped"
#: idle time whose host span depends on where in its bounds the offset is
UNCERTAIN = "clock_uncertain"
#: the host span ``local_mapreduce`` opens around its dispatch
DISPATCH_SPAN = "mr.dispatch"
JOB_SPAN = "bench.job"
MODULES_LINE = "XLA Modules"
#: the TPU runtime's host events that enqueue a program, and that run
#: once the device has finished it; each carries the program's run_id
LAUNCH = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
KEPT_ARGS = ("job", "run_id")

#: per-layer metrics read from :func:`reduce`'s output
METRICS = [{"name": f"mr.{s}_ms_per_block", "unit": "ms"} for s in STAGES] + [
    {"name": "mr.dispatch_us_per_job", "unit": "us"}]

FIXTURE = "mr_local_v5e_scoped"
#: the block a fixture is recorded at: 256 Ki slots, 1 MiB of text
FIXTURE_BLOCK = {"slots": 1 << 18, "block_bytes": 1 << 20}

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` in :mod:`bench.trace`'s form,
    with the kept arguments of each line's events under ``"args"``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        for line in plane.lines:
            events, args = [], []
            for e in line.events:
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns)))
                args.append({k: v for k, v in e.stats if k in KEPT_ARGS})
            out = {"name": line.name, "events": events}
            if any(args):
                out["args"] = args
            lines.append(out)
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def stage_of(op_name: str) -> str:
    """The innermost ``mr.<stage>`` component of an ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part.startswith("mr.") and part[3:] in STAGES:
            return part[3:]
    return UNSCOPED


def stage_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction: stage}`` for every instruction of a compiled
    program's HLO text; one without ``op_name`` is unscoped."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            table[m.group(1)] = stage_of(op.group(1)) if op else UNSCOPED
    return table


def _events(trace_: dict, device: bool, line_name: Optional[str] = None):
    """``(name, start, duration, args)`` of the device's or the host's
    planes, on the line named ``line_name`` if given."""
    for p in trace_["planes"]:
        if trace.is_device_plane(p["name"]) != device:
            continue
        for line in p["lines"]:
            if line_name is not None and line["name"] != line_name:
                continue
            args = line.get("args") or [{}] * len(line["events"])
            for (n, s, d), a in zip(line["events"], args):
                yield n, s, d, a


def _overlap(s0, e0, s1, e1) -> float:
    return max(0.0, min(e0, e1) - max(s0, s1))


def _innermost(spans, t: float) -> str:
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "(no span)"


def reduce(trace_: dict, tables: Dict[str, Dict[str, str]]) -> dict:
    """Device seconds per stage inside the window, summed over devices;
    ms per run of each job and stage; the bounds of the clock offset; idle
    seconds by host span; and the ``mr.dispatch`` spans' durations.

    The offset ``delta`` maps device time to host time (host = device +
    delta). A program starts after its dispatch span and its launch
    begin, and ends before its job span closes and its completion
    callbacks start: each such pair bounds ``delta``, and the bounds are
    the tightest over the window's programs. A piece of an idle gap is
    put down to the innermost ``bench.*`` or ``mr.*`` span over it only
    where that holds for every ``delta`` within the bounds; the rest is
    :data:`UNCERTAIN`."""
    host = list(_events(trace_, device=False))
    wins = [(s, d) for n, s, d, _ in host if n == trace.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    w0, wd = max(wins, key=lambda w: w[1])
    w1 = w0 + wd
    jobs = [(s, s + d, a.get("job")) for n, s, d, a in host
            if n == JOB_SPAN]
    dispatch = [(s, s + d) for n, s, d, _ in host if n == DISPATCH_SPAN]
    # should a program have several launches or completions, the
    # earliest launch and the latest completion are the ones sure to bound
    by_run: Dict[str, Dict[str, float]] = {}
    for n, s, d, a in host:
        if n in (LAUNCH, COMPLETE) and "run_id" in a:
            ev = by_run.setdefault(str(a["run_id"]), {})
            ev[n] = (min if n == LAUNCH else max)(ev.get(n, s), s)

    stage_s = {k: 0.0 for k in STAGES + (UNSCOPED,)}
    by_job: Dict[str, Dict[str, float]] = {}
    runs: Dict[str, int] = {}
    unmatched = 0.0
    lo, hi = [], []
    for p in trace_["planes"]:
        if not trace.is_device_plane(p["name"]):
            continue
        one = {"planes": [p]}
        modules = sorted(((s, s + d, a) for _, s, d, a in
                          _events(one, True, MODULES_LINE)),
                         key=lambda m: m[:2])
        job_of = []
        for s, e, a in modules:
            job = max(jobs, key=lambda j: _overlap(s, e, j[0], j[1]),
                      default=None)
            if job and not _overlap(s, e, job[0], job[1]):
                job = None
            job_of.append(job[2] if job else None)
            if not job or e <= w0 or s >= w1:
                continue
            runs[job[2]] = runs.get(job[2], 0) + 1
            hi.append(job[1] - e)
            lo.extend(ds - s for ds, de in dispatch
                      if job[0] <= ds and de <= job[1])
            ev = by_run.get(str(a.get("run_id")), {})
            if LAUNCH in ev:
                lo.append(ev[LAUNCH] - s)
            if COMPLETE in ev:
                hi.append(ev[COMPLETE] - e)
        starts = [m[0] for m in modules]
        for name, s, d, _ in _events(one, True, trace.OPS_LINE):
            t = _overlap(s, s + d, w0, w1) * 1e-9
            if t <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            job = job_of[i] if i >= 0 and s < modules[i][1] else None
            table = tables.get(job, {})
            op = trace.op_name(name)
            if op not in table:
                unmatched += t
            stage = table.get(op, UNSCOPED)
            stage_s[stage] += t
            if job is not None:
                row = by_job.setdefault(job, {})
                row[stage] = row.get(stage, 0.0) + t
    ms_by_job = {j: {"runs": runs[j], **{k: 1e3 * v / runs[j]
                                          for k, v in row.items()}}
                 for j, row in by_job.items() if runs.get(j)}
    out = {"stage_s": stage_s, "unmatched_s": unmatched,
           "device_ms_by_job": ms_by_job,
           "dispatch_us": [1e-3 * (e - s) for s, e in dispatch
                           if w0 <= s < w1],
           "clock_offset_us": None, "idle_by_span": None}
    if not lo or not hi:
        return out
    lo_ns, hi_ns = max(lo), min(hi)
    out["clock_offset_us"] = [1e-3 * lo_ns, 1e-3 * hi_ns]
    if lo_ns > hi_ns:
        return out   # no single offset fits: the clocks drift apart
    spans = [(n, s, d) for n, s, d, _ in host if n != trace.WINDOW_SPAN
             and (n.startswith("bench.") or n.startswith("mr."))]
    # device instants whose host span depends on the offset
    unsure = trace.union([(t - hi_ns, t - lo_ns) for _, s, d in spans
                          for t in (s, s + d)])
    busy = trace.union([(s, s + d) for _, s, d, _ in
                        _events(trace_, True, trace.OPS_LINE)])
    idle: Dict[str, float] = {}
    for gap in trace.gaps(trace.clip(busy, w0, w1), w0, w1):
        sure = trace.gaps(trace.clip(unsure, *gap), *gap)
        for a, b in sure:
            label = _innermost(spans, (a + b) / 2 + lo_ns)
            idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
        left = (gap[1] - gap[0]) - trace.covered(sure)
        if left > 0:
            idle[UNCERTAIN] = idle.get(UNCERTAIN, 0.0) + left * 1e-9
    out["idle_by_span"] = sorted(idle.items(), key=lambda kv: -kv[1])
    return out


def cut(trace_: dict, tables: Dict[str, Dict[str, str]]) -> dict:
    """What :func:`bench.trace.reduce` and :func:`reduce` read of a
    trace: the devices' programs and operations (each named by its
    instruction alone) and the host's spans, launches and completions."""
    keep = (LAUNCH, COMPLETE)
    planes = []
    for p in trace_["planes"]:
        device = trace.is_device_plane(p["name"])
        lines = []
        for line in p["lines"]:
            if device and line["name"] not in (MODULES_LINE,
                                               trace.OPS_LINE):
                continue
            args = line.get("args") or [{}] * len(line["events"])
            ev = [((trace.op_name(n) if line["name"] == trace.OPS_LINE
                    else n), s, d, a)
                  for (n, s, d), a in zip(line["events"], args)
                  if device or n in keep or n.startswith(("bench.", "mr."))]
            if ev:
                out = {"name": line["name"],
                       "events": [e[:3] for e in ev]}
                if any(e[3] for e in ev):
                    out["args"] = [e[3] for e in ev]
                lines.append(out)
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    ran = {trace.op_name(n)
           for n, *_ in _events(trace_, True, trace.OPS_LINE)}
    return {"planes": planes,
            "stage_tables": {j: {op: s for op, s in t.items() if op in ran}
                             for j, t in tables.items()}}


def compiled_tables(jobs: List[str], slots: int, device) -> Dict[str, dict]:
    """Each job's stage table, from the program ``local_mapreduce``
    compiles for one block of ``slots`` int32 tokens and lengths.

    JAX's persistent cache keys a program without its metadata, so the
    program that ran may have been compiled from a source that names no
    stage: each is compiled anew here, past both of JAX's caches."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from repro.mapreduce.engine import _local_mapreduce
    from repro.mapreduce.jobs import JOBS
    block = jax.ShapeDtypeStruct((slots,), jnp.int32,
                                 sharding=SingleDeviceSharding(device))
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    # JAX decides once whether to use its persistent cache: reset that too
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return {j: stage_table(_local_mapreduce.lower(
            JOBS[j], block, block).compile().as_text()) for j in jobs}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", help="record a small-block fixture here")
    args = ap.parse_args(argv)
    from bench import run
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    if args.fixture:
        cell.config = dict(cell.config, block=FIXTURE_BLOCK)
    cell.per_layer = cell.per_layer + METRICS
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from bench import device
    from bench.window import Window
    device.use_compile_cache()
    try:
        devs = device.chips(cell.chips)
        peaks = device.peaks(devs[0].device_kind)
    except (device.NoChip, KeyError) as e:
        print(f"bench.stages: {e}", file=sys.stderr)
        return 2
    record = cell.driver(cell, seed=args.seed, seconds=args.seconds,
                         window=Window(run.TRACE_DIR), devices=devs,
                         t_start=t_start)
    t = time.perf_counter()
    jobs = list(cell.traffic["jobs"])
    tables = compiled_tables(jobs, cell.config["block"]["slots"], devs[0])
    tables_s = time.perf_counter() - t
    full = load(run.TRACE_DIR)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    st = reduce(full, tables)
    record["trace"] = {**trace.reduce(full), **st}
    record["peaks"] = peaks
    record["counters"]["clock_offset_us"] = st["clock_offset_us"]
    record["counters"]["device_ms_by_job"] = st["device_ms_by_job"]
    line = run.result_line(cell, record, device.describe(devs), True)
    line["breakdown"]["stages"] = st["stage_s"]
    line["breakdown"]["idle_by_span"] = st["idle_by_span"]
    print(f"window {record['window_s']:.6f} s; stage tables "
          f"{tables_s:.3f} s; unmatched ops {st['unmatched_s']:.6f} s; "
          f"dispatch median "
          f"{statistics.median(st['dispatch_us'] or [0]):.1f} us",
          file=sys.stderr)
    if args.fixture:
        small = cut(full, tables)
        want = trace.reduce(full)
        if (trace.reduce(small) != want
                or reduce(small, small["stage_tables"]) != st):
            raise RuntimeError("the cut trace reduces otherwise")
        os.makedirs(args.fixture, exist_ok=True)
        base = os.path.join(args.fixture, FIXTURE)
        with open(base + ".trace.json", "w") as f:
            json.dump(small, f, separators=(",", ":"))
        with open(base + ".reduced.json", "w") as f:
            json.dump({
                "recorded": f"{devs[0].device_kind}, jax.profiler, "
                            f"python3 -m bench.stages --fixture, "
                            f"{FIXTURE_BLOCK['slots']} slots",
                "window_s": want["window_s"], "busy_s": want["busy_s"],
                "n_devices": len(want["devices"]),
                "top_ops": [n for n, _ in want["device_ops"]],
                "has_all_to_all": any(d["all_to_all_s"] > 0
                                      for d in want["devices"]),
                "stages": st}, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
