"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program's ``src/``. The cell's configuration names the driver that
sets it up, measures it for ``--seconds`` and compares what it produced
with the plain reference. ``--trace 1`` records the window with the
profiler and reports the per-layer metrics instead of the end-to-end
ones. Without a TPU, or with fewer chips than the cell needs, it exits
with code 2 before printing any result. The last line of standard output
is one JSON object; the last lines of standard error give each number
compared beside its limit.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

from bench import spec

#: where a traced run's profile is written and read back, then removed
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, record: dict, dev: dict, traced: bool) -> dict:
    """The contract's JSON object, with the compared numbers last."""
    from bench.checks import as_result
    if traced:
        metrics = spec.read_per_layer(cell, record)
        tr = record["trace"]
        dev = {**dev, "busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    else:
        metrics = {m["name"]: {"value": float(
            record["setup_s"] if m["name"] == "setup_s"
            else record["e2e"][m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    out = {"correct": record["correct"], "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics,
           "device": {**dev, "memory_peak_bytes": record["memory_peak_bytes"]},
           "window": {"seconds": record["window_s"],
                      **record["counters"]["programs_in_window"]}}
    out["counters"] = {k: v for k, v in record["counters"].items()
                       if k != "latency_ms"}
    if traced:
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = as_result(record["checks"])
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    bench = spec.load_benchmark()
    try:
        cell = spec.resolve(bench, args.workload)
    except KeyError:
        print(f"bench.run: no cell {args.workload!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import repro  # noqa: F401  (the program; absent, this fails first)
    from bench import device
    from bench.window import Window
    device.use_compile_cache()
    t_imported = time.perf_counter()
    try:
        devs = device.chips(cell.chips)
        peaks = device.peaks(devs[0].device_kind)
    except (device.NoChip, KeyError) as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    t_chips = time.perf_counter()
    window = Window(TRACE_DIR if args.trace else None)
    record = cell.driver(cell, seed=args.seed, seconds=args.seconds,
                         window=window, devices=devs, t_start=t_start)
    # set-up by phase: imports, reaching the chip, then the driver's own
    phases = {"imports_s": t_imported - t_start,
              "chips_s": t_chips - t_imported, **record["setup_phases"]}
    phases["other_s"] = record["setup_s"] - sum(phases.values())
    record["counters"]["setup_phases"] = phases
    if args.trace:
        from bench import trace
        record["trace"] = trace.reduce(trace.load(TRACE_DIR))
        record["peaks"] = peaks
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    line = result_line(cell, record, device.describe(devs), bool(args.trace))
    new = record["counters"]["programs_in_window"]
    print("setup " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()),
          file=sys.stderr)
    print(f"window {record['window_s']:.3f} s; programs compiled in it "
          f"{new['compiled']}, loaded from the cache {new['loaded']}",
          file=sys.stderr)
    for c in record["checks"]:
        print(f"check {c['name']} {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
