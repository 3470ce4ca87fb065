"""MapReduce on one chip: jobs in rotation over blocks kept on the device.

Set-up makes the blocks from the seed on the chip and runs each job once;
the record times each of those phases.
The window is a closed loop with one job outstanding: job ``i`` is
``jobs[i % J]`` on block ``i % B``, from dispatch to ``block_until_ready``
of its outputs, through ``repro.mapreduce.engine.local_mapreduce``. It
ends at the first whole rotation of the ``J`` jobs past the window's
seconds, so every run measures whole rotations.

The last output of each (block, job) stays on the device. After the
window they are read back and each must equal the numpy reference.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from bench import mrcheck
from bench.checks import check, correct
from bench.corpus import block_key, make_blocks
from bench.device import CompileCounter, memory_peak_bytes
from bench.window import span


def run(cell, *, seed: int, seconds: float, window, devices,
        t_start: float) -> dict:
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.mapreduce import engine
    from repro.mapreduce.jobs import JOBS

    config, traffic = cell.config, cell.traffic
    jobs: List[str] = list(traffic["jobs"])
    n_blocks = int(traffic["blocks"])
    compiles = CompileCounter()
    t = time.perf_counter()
    tok, lng, n_valid = make_blocks(
        [block_key(seed, b) for b in range(n_blocks)], config["block"],
        config["corpus"], SingleDeviceSharding(devices[0]))
    blocks = [(tok[b], lng[b]) for b in range(n_blocks)]
    phases = {"blocks_s": time.perf_counter() - t}
    for name in jobs:
        t = time.perf_counter()
        jax.block_until_ready(engine.local_mapreduce(JOBS[name], *blocks[0]))
        phases[f"warm_{name}_s"] = time.perf_counter() - t
    held: Dict[Tuple[int, str], tuple] = {}
    done: List[Tuple[int, str]] = []
    latency: List[float] = []
    before = compiles.mark()
    with window:
        i = 0
        while True:
            name, b = jobs[i % len(jobs)], i % n_blocks
            with span("job", job=name, block=b):
                t = time.perf_counter()
                out = jax.block_until_ready(
                    engine.local_mapreduce(JOBS[name], *blocks[b]))
                latency.append(time.perf_counter() - t)
            held[(b, name)] = out
            done.append((b, name))
            i += 1
            if (i % len(jobs) == 0
                    and time.perf_counter() - window.t0 >= seconds):
                break
        window.close()
    in_window = compiles.since(before)
    peak = memory_peak_bytes(devices)
    got = {c: tuple(np.asarray(x) for x in out) for c, out in held.items()}
    tokens = np.asarray(tok)
    del held, out, blocks, tok, lng
    with span("compare"):
        checks, least = compare(got, tokens)
    e2e = {"mr_input_records_per_s":
           sum(int(n_valid[b]) for b, _ in done) / window.seconds}
    e2e[traffic["latency_metric"]] = 1e3 * mrcheck.percentile(
        latency, int(traffic["latency_percentile"]))
    return {
        "setup_s": window.t0 - t_start,
        "setup_phases": phases,
        "window_s": window.seconds,
        "e2e": e2e,
        "counters": {"jobs": len(done), "blocks": len(done),
                     "valid_tokens": int(sum(n_valid[b] for b, _ in done)),
                     "least_bytes": sum(least[c] for c in done),
                     "programs_in_window": in_window,
                     "latency_ms": [1e3 * x for x in latency]},
        "memory_peak_bytes": peak,
        "attempted": len(done),
        "failed": 0,
        "checks": checks,
        "correct": correct(checks),
    }


def compare(got: Dict[Tuple[int, str], tuple], tokens: np.ndarray):
    """Checks of every held (block, job) output against the reference,
    and the least bytes of each (block, job)."""
    differing, worst = 0, 0
    least = {}
    for (b, name), (keys, counts, n) in sorted(got.items()):
        uk, uc, emitted = mrcheck.reference(name, tokens[b])
        least[(b, name)] = mrcheck.least_bytes(
            int((tokens[b] >= 0).sum()), emitted, len(uk))
        if mrcheck.differs(keys, counts, int(n), uk, uc):
            differing += 1
            worst = max(worst, abs(int(n) - len(uk)), 1)
    if not got:
        differing = 1   # nothing came back to compare
    return ([check("outputs_differing", differing, 0),
             check("worst_unique_gap", worst, 0)], least)
