"""MapReduce on a mesh: one job's blocks spread over every chip, its
records shuffled between them by ``all_to_all``.

Set-up builds the mesh from the cell's chips, makes ``block_sets`` sets
of ``blocks`` blocks from the seed on the chips, placed by
``NamedSharding(mesh, P(shard_axes))``, and runs each job once; the
record times each of those phases. The window is a closed loop with one
job outstanding: job ``i`` is ``jobs[i % J]`` over block set ``i % S``,
from dispatch to ``block_until_ready`` of its outputs, through
``repro.mapreduce.engine.mesh_mapreduce``. It ends at the first whole
rotation of the ``J x S`` jobs past the window's seconds.

The cell needs the mesh path as one jitted program
(``engine._mesh_mapreduce``); a program without it stops the run at
once, before any block is made.

The last output of each (set, job) stays on the chips. After the window
each reducer's keys and counts must equal the reference
(``bench/reference/shuffle.py``), and no job of the window may have
dropped a record. In a traced run the driver also reads each chip's
all-to-all time from the window's trace (``all_to_all_s``).

    python3 -m bench.drivers.mr_mesh --workload <cell> --seeds <n> [...]

runs the lower-precision control instead: the reference counting in
int16 stands in for the program's outputs and must read not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import lru_cache
from typing import Dict, List, Tuple

import numpy as np

from bench import mrcheck, spec, trace
from bench.checks import as_result, check, correct
from bench.corpus import block_key, make_blocks
from bench.device import CompileCounter, memory_peak_bytes
from bench.reference import shuffle
from bench.reference.mapreduce import EMPTY, emission
from bench.window import span


def layout_of(cell, n_devices: int) -> shuffle.Layout:
    """The cell's mesh, checked against its chips; blocks lie in device
    order, so the input is sharded over every mesh axis in order."""
    cfg = cell.config
    if list(cfg["shard_axes"]) != list(cfg["mesh"]["axes"]):
        raise ValueError("shard_axes must be the mesh's axes in order")
    lay = shuffle.Layout(cfg["mesh"]["shape"], cfg["mesh"]["axes"],
                         cfg["shuffle_axes"],
                         int(cell.traffic["blocks"]) // n_devices)
    if lay.n_devices != n_devices or (
            lay.per_device * n_devices != int(cell.traffic["blocks"])):
        raise ValueError(f"{cell.traffic['blocks']} blocks do not fill a "
                         f"{lay.shape} mesh of {n_devices} chips evenly")
    return lay


def run(cell, *, seed: int, seconds: float, window, devices,
        t_start: float) -> dict:
    import jax
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from repro.mapreduce import engine
    from repro.mapreduce.jobs import JOBS

    if not hasattr(engine, "_mesh_mapreduce"):
        # an eager shard_map compiles its body op by op on every call and
        # keeps every intermediate: on the v5e it ran 6 min and then out
        # of memory at this cell's size, so stop before the chip's memory
        raise RuntimeError("the program has no jitted mesh entry "
                           "(repro.mapreduce.engine._mesh_mapreduce)")
    config, traffic = cell.config, cell.traffic
    lay = layout_of(cell, len(devices))
    axes = tuple(config["mesh"]["axes"])
    mesh = Mesh(np.array(devices).reshape(lay.shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))
    opts = {"mesh": mesh, "shuffle_axes": tuple(config["shuffle_axes"]),
            "shard_axes": axes, "slack": int(config["slack"])}
    jobs: List[str] = list(traffic["jobs"])
    n_blocks, n_sets = int(traffic["blocks"]), int(traffic["block_sets"])
    compiles = CompileCounter()
    t = time.perf_counter()
    placed = NamedSharding(mesh, PartitionSpec(axes))
    sets = [make_blocks([block_key(seed, s * n_blocks + b)
                         for b in range(n_blocks)], config["block"],
                        config["corpus"], placed) for s in range(n_sets)]
    phases = {"blocks_s": time.perf_counter() - t}
    for name in jobs:
        t = time.perf_counter()
        jax.block_until_ready(engine.mesh_mapreduce(
            JOBS[name], *sets[0][:2], **opts))
        phases[f"warm_{name}_s"] = time.perf_counter() - t
    held: Dict[Tuple[int, str], tuple] = {}
    done: List[Tuple[int, str]] = []
    dropped: List = []
    latency: List[float] = []
    before = compiles.mark()
    rotation = len(jobs) * n_sets
    with window:
        i = 0
        while True:
            name, s = jobs[i % len(jobs)], i % n_sets
            with span("job", job=name, block=s):
                t = time.perf_counter()
                out = jax.block_until_ready(engine.mesh_mapreduce(
                    JOBS[name], *sets[s][:2], **opts))
                latency.append(time.perf_counter() - t)
            held[(s, name)] = out
            dropped.append(out[3])
            done.append((s, name))
            i += 1
            if (i % rotation == 0
                    and time.perf_counter() - window.t0 >= seconds):
                break
        window.close()
    in_window = compiles.since(before)
    peak = memory_peak_bytes(devices)
    traced = all_to_all(trace.load(window.trace_dir)) if (
        window.trace_dir) else {}
    got = {c: read_back(*out[:3]) for c, out in held.items()}
    lost = sum(int(np.asarray(d).sum()) for d in dropped)
    tokens = [np.asarray(tok) for tok, _, _ in sets]
    n_valid = [int(n.sum()) for _, _, n in sets]
    del held, out, sets, dropped
    with span("compare"):
        checks, ref = compare(got, tokens, lay, lost)
    e2e = {"mr_input_records_per_s":
           sum(n_valid[s] for s, _ in done) / window.seconds}
    e2e[traffic["latency_metric"]] = 1e3 * mrcheck.percentile(
        latency, int(traffic["latency_percentile"]))
    # the pack's buffer per destination: slack x an even share of a
    # block's records, per block on the chip; D - 1 of them leave it
    sent = {name: 8 * lay.n_devices * (lay.D - 1) * int(config["slack"])
            * -(-int(config["block"]["slots"]) * JOBS[name].cap_mult
                // lay.D) * lay.per_device for name in jobs}
    counters = {
        "jobs": len(done), "blocks": n_blocks * len(done),
        "valid_tokens": sum(n_valid[s] for s, _ in done),
        "least_bytes": sum(ref[c]["least_bytes"] for c in done),
        "shuffle_least_bytes": sum(ref[c]["off_chip"] for c in done),
        "cross_pod_least_bytes": sum(ref[c]["cross_pod"] for c in done),
        "shuffle_sent_bytes": sum(sent[name] for _, name in done),
        "records_dropped": lost,
        "programs_in_window": in_window,
        **traced,
        "latency_ms": [1e3 * x for x in latency]}
    return {
        "setup_s": window.t0 - t_start,
        "setup_phases": phases,
        "window_s": window.seconds,
        "e2e": e2e,
        "counters": counters,
        "memory_peak_bytes": peak,
        "attempted": len(done),
        "failed": 0,
        "checks": checks,
        "correct": correct(checks),
    }


def all_to_all(tr: dict) -> dict:
    """Each chip's device seconds in all-to-all operations inside the
    window, and the operations' names. XLA names the instruction of
    ``jax.lax.all_to_all`` ``all_to_all.<n>`` and its opcode
    ``all-to-all``; either prefix is taken."""
    wins = [(s, s + d) for p in tr["planes"]
            if not trace.is_device_plane(p["name"])
            for line in p["lines"] for n, s, d in line["events"]
            if n == trace.WINDOW_SPAN]
    if not wins:
        return {}
    w0, w1 = max(wins, key=lambda w: w[1] - w[0])
    per_chip, names = [], set()
    for p in tr["planes"]:
        if not trace.is_device_plane(p["name"]):
            continue
        t = 0.0
        for line in p["lines"]:
            if line["name"] != trace.OPS_LINE:
                continue
            for n, s, d in line["events"]:
                op = trace.op_name(n)
                if op.startswith(("all-to-all", "all_to_all")):
                    t += max(0.0, min(s + d, w1) - max(s, w0))
                    names.add(op)
        per_chip.append(t * 1e-9)
    return {"all_to_all_s": per_chip, "all_to_all_ops": sorted(names)}


@lru_cache(maxsize=8)
def _heads(width: int):
    import jax
    import jax.numpy as jnp

    def heads(keys, counts, n):
        slot = jnp.arange(keys.shape[1])
        tail = jnp.all((slot[None, :] < n[:, None]) | (keys == EMPTY),
                       axis=1)
        return keys[:, :width], counts[:, :width], tail
    return jax.jit(heads)


def read_back(keys, counts, n) -> tuple:
    """A job's padded outputs, read back as far as the fullest reducer
    reaches (``width``, a power of two), with each reducer's check that
    every slot past its ``n`` holds ``EMPTY``."""
    n = np.asarray(n)
    width = min(int(keys.shape[1]),
                1 << max(int(n.max(initial=0)), 1).bit_length())
    k, c, tail = _heads(width)(keys, counts, n)
    return np.asarray(k), np.asarray(c), n, np.asarray(tail)


def compare(got: Dict[Tuple[int, str], tuple], tokens: List[np.ndarray],
            lay: shuffle.Layout, dropped: int):
    """Checks of every held (set, job) output, reducer by reducer,
    against the reference, and each one's least bytes."""
    differing, worst = 0, 0
    ref = {}
    for (s, name), (keys, counts, n, tail) in sorted(got.items()):
        want = shuffle.reducer_outputs(name, tokens[s], lay)
        emitted = sum(len(emission(name, t)[0]) for t in tokens[s])
        ref[(s, name)] = {
            "least_bytes": mrcheck.least_bytes(
                int((tokens[s] >= 0).sum()), emitted,
                sum(len(k) for k, _ in want)),
            **shuffle.least_bytes(name, tokens[s], lay)}
        rows = min(len(n), lay.n_devices)
        differing += lay.n_devices - rows
        if rows < lay.n_devices:
            worst = max(worst, 1)
        for g in range(rows):
            wk, wc = want[g]
            if mrcheck.differs(keys[g], counts[g], int(n[g]), wk, wc) or (
                    not tail[g]):
                differing += 1
                worst = max(worst, abs(int(n[g]) - len(wk)), 1)
    if not got:
        differing = 1   # nothing came back to compare
    return ([check("outputs_differing", differing, 0),
             check("worst_unique_gap", worst, 0),
             check("records_dropped", dropped, 0)], ref)


def control(cell, seed: int) -> List[dict]:
    """The cell's checks with the program's outputs replaced by the
    reference counting in int16 (the engine counts in int32), on the
    cell's blocks made from ``seed`` on the chip."""
    n_blocks = int(cell.traffic["blocks"])
    lay = layout_of(cell, int(np.prod(cell.config["mesh"]["shape"])))
    tokens, got = [], {}
    for s in range(int(cell.traffic["block_sets"])):
        tok, _, _ = make_blocks([block_key(seed, s * n_blocks + b)
                                 for b in range(n_blocks)],
                                cell.config["block"], cell.config["corpus"])
        tokens.append(np.asarray(tok))
        for name in cell.traffic["jobs"]:
            out = shuffle.reducer_outputs(name, tokens[s], lay, np.int16)
            width = max(max(len(k) for k, _ in out), 1)
            keys = np.full((lay.n_devices, width), EMPTY, np.uint32)
            counts = np.zeros((lay.n_devices, width), np.int64)
            for g, (k, c) in enumerate(out):
                keys[g, :len(k)], counts[g, :len(c)] = k, c
            got[(s, name)] = (keys, counts,
                              np.array([len(k) for k, _ in out]),
                              np.ones(lay.n_devices, bool))
    return compare(got, tokens, lay, 0)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="the lower-precision control of a mesh cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from bench import device
    device.use_compile_cache()
    device.chips(1)
    for seed in args.seeds:
        checks = control(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct(checks),
                          "checks": as_result(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
