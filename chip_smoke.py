"""Smoke run of the system's two device paths on a TPU.

    python chip_smoke.py            # one chip: fill kernel, lockstep sweep,
                                    # MapReduce on one 128 MiB block
    python chip_smoke.py --chips 4  # four chips: the mesh MapReduce only

One chip:

1. ``fill``: the batched fill kernel (``vmap_fill.batched_fill``) over the
   captured contention corpus (``contention_snapshots()``), held to the
   scalar ``fill_reference`` bit for bit.
2. ``lockstep``: the 120-cell ``fabric_contention`` gate matrix of
   ``benchmarks/bench_sweep.py`` (8 pods x 8 hosts, 24 jobs; 5 algorithms
   x 3 WAN scenarios x 8 seeds) through
   ``SweepEngine(store=None, backend="lockstep")``. The kernel must run on
   the TPU, every cell's metrics must equal scalar ``run_cell`` under
   ``==``, and the aggregate JSON must be byte-equal.
3. ``mapreduce``: ``local_mapreduce`` of each of the five jobs on one
   128 MiB block of the non-web corpus, against the numpy oracle
   (``repro.mapreduce.reference``).

Four chips:

4. ``mesh``: ``mesh_mapreduce`` of WordCount on a 2x2 (pod, data) mesh,
   one block per chip, with the pod-local shuffle (JoSS policy A) and the
   cross-pod shuffle. No record may be dropped, and each chip's reduced
   keys and counts must equal the oracle's for the keys it owns.

Every time printed is host wall time, compilation included. The last line
of stdout is one JSON object naming the device, printed only when every
phase passed. Without a TPU, or outside the repository, the script exits
non-zero before any result.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: seeds per (algorithm, scenario) of the lockstep gate matrix: 120 cells
LOCKSTEP_SEEDS = 8
#: the four-chip mesh: (pod, data)
MESH_SHAPE = (2, 2)


def log(msg: str) -> None:
    print(msg, flush=True)


def _ulps(a, b):
    """Distance in units in the last place between float64 arrays."""
    import numpy as np
    return np.abs(np.asarray(a, np.float64).view(np.int64)
                  - np.asarray(b, np.float64).view(np.int64))


def fill_phase() -> list:
    import numpy as np
    from repro.sweep import vmap_fill as vf
    snaps = vf.contention_snapshots()
    t0 = time.perf_counter()
    out = vf.batched_fill(snaps)
    wall = time.perf_counter() - t0
    errs = []
    bad = 0
    for i, snap in enumerate(snaps):
        ref = vf.fill_reference(snap)
        c = len(snap["classes"])
        want = np.asarray(ref["rates"], np.float64)
        got = out["rates"][i, :c]
        dt = np.inf if ref["dt_next"] is None else ref["dt_next"]
        if np.array_equal(got, want) and out["dt_next"][i] == dt:
            continue
        bad += 1
        if bad <= 5:
            j = int(np.argmax(_ulps(got, want))) if c else 0
            log(f"  fill problem {i} ({c} classes): class {j} rate "
                f"{got[j]!r} vs {want[j]!r} "
                f"({int(_ulps(got, want).max()) if c else 0} ulp); "
                f"dt_next {out['dt_next'][i]!r} vs {dt!r}")
    log(f"fill: {len(snaps)} captured problems, {bad} differ from "
        f"fill_reference; host wall {wall:.3f} s (compile included)")
    if bad:
        errs.append(f"{bad} of {len(snaps)} fill problems not "
                    "bit-identical to fill_reference")
    return errs


def lockstep_phase(platform: str = "tpu",
                   n_seeds: int = LOCKSTEP_SEEDS) -> list:
    from benchmarks.bench_sweep import lockstep_matrix
    from repro.sweep import SweepEngine, aggregate_json, run_cell
    specs = lockstep_matrix(n_seeds)
    engine = SweepEngine(store=None, backend="lockstep")
    t0 = time.perf_counter()
    res, _ = engine.run(specs)
    lock_s = time.perf_counter() - t0
    st = engine.lockstep_stats
    t0 = time.perf_counter()
    scalar = {s.key(): run_cell(s) for s in specs}
    scalar_s = time.perf_counter() - t0
    log(f"lockstep: {st.n_cells} cells, {st.epochs} epochs, "
        f"{st.batches} kernel batches, {st.problems} fill problems "
        f"({st.inline_small} solved inline), kernel outputs on "
        f"{st.platform!r}; host wall {lock_s:.2f} s (fill path "
        f"{st.fill_s:.2f} s, compile included), scalar run_cell "
        f"{scalar_s:.2f} s")
    errs = []
    if not st.used_jax or st.batches <= 0:
        errs.append(f"kernel not used (used_jax={st.used_jax}, "
                    f"batches={st.batches})")
    if st.platform != platform:
        errs.append(f"kernel outputs on {st.platform!r}, not {platform!r}")
    if set(res) != set(scalar):
        errs.append("lockstep lost or invented cells")
    diff = sorted(k for k in scalar if res.get(k) != scalar[k])
    if diff:
        first = diff[0]
        names = sorted(m for m in scalar[first]
                       if res.get(first, {}).get(m) != scalar[first][m])
        errs.append(f"{len(diff)} of {len(specs)} cells differ from "
                    f"run_cell; first {first} in {names}")
    if aggregate_json(res) != aggregate_json(scalar):
        errs.append("aggregate JSON is not byte-equal")
    return errs


def mapreduce_phase(platform: str = "tpu", **block_size) -> list:
    import jax
    import numpy as np
    from repro.mapreduce import JOBS, local_mapreduce
    from repro.mapreduce.jobs import EMPTY, block
    from repro.mapreduce.reference import emission, reduce_counts
    tok, lng = block(0, **block_size)
    log(f"mapreduce: one block of {int((tok >= 0).sum())} tokens, "
        f"{int(lng.sum())} bytes")
    dtok, dlng = jax.device_put(tok), jax.device_put(lng)
    errs = []
    for name in sorted(JOBS):
        t0 = time.perf_counter()
        k, v, n = jax.block_until_ready(
            local_mapreduce(JOBS[name], dtok, dlng))
        run_s = time.perf_counter() - t0
        on = k.device.platform
        k, v, n = np.asarray(k), np.asarray(v), int(n)
        t0 = time.perf_counter()
        records = emission(name, tok)
        keys, counts = reduce_counts(*records)
        ref_s = time.perf_counter() - t0
        ok = (on == platform and n == len(keys)
              and np.array_equal(k[:n], keys)
              and np.array_equal(v[:n].astype(np.int64), counts)
              and bool(np.all(k[n:] == EMPTY)))
        log(f"  {name}: {len(records[0])} records, {n} unique keys on "
            f"{on!r}, {'equal to' if ok else 'DIFFERENT from'} the numpy "
            f"oracle; host wall {run_s:.3f} s (compile included), "
            f"oracle {ref_s:.2f} s")
        if not ok:
            errs.append(f"{name} differs from the numpy oracle "
                        f"({n} vs {len(keys)} unique keys, on {on!r})")
    return errs


def mesh_phase(platform: str = "tpu", **block_size) -> list:
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.mapreduce import JOBS, mesh_mapreduce
    from repro.mapreduce.jobs import block
    from repro.mapreduce.reference import emission, reduce_counts
    n_pod, n_data = MESH_SHAPE
    n_dev = n_pod * n_data
    mesh = jax.make_mesh(MESH_SHAPE, ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    blocks = [block(seed, **block_size) for seed in range(n_dev)]
    tok = np.stack([b[0] for b in blocks])
    lng = np.stack([b[1] for b in blocks])
    per_chip = NamedSharding(mesh, P(("pod", "data")))
    dtok, dlng = jax.device_put(tok, per_chip), jax.device_put(lng, per_chip)
    records = [emission("WC", t) for t in tok]
    log(f"mesh: {n_pod}x{n_data} (pod, data), one block per chip, "
        f"{sum(len(r[0]) for r in records)} WordCount records")
    errs = []
    for label, shuffle in (("pod-local shuffle (policy A)", ("data",)),
                           ("cross-pod shuffle", ("pod", "data"))):
        t0 = time.perf_counter()
        out = jax.block_until_ready(mesh_mapreduce(
            JOBS["WC"], dtok, dlng, mesh, shuffle_axes=shuffle,
            shard_axes=("pod", "data")))
        run_s = time.perf_counter() - t0
        on = {d.platform for d in out[0].devices()}
        uk, uv, n, dropped = (np.asarray(x) for x in out)
        n_dest = n_data if shuffle == ("data",) else n_dev
        bad = []
        for g in range(n_dev):
            pod = g // n_data
            # reducer g owns key % n_dest == its index in the shuffle
            # group, over the blocks of the chips in that group
            srcs = (range(pod * n_data, (pod + 1) * n_data)
                    if shuffle == ("data",) else range(n_dev))
            keys = np.concatenate([records[s][0] for s in srcs])
            vals = np.concatenate([records[s][1] for s in srcs])
            own = keys % n_dest == g % n_dest
            ek, ec = reduce_counts(keys[own], vals[own])
            m = int(n[g])
            if not (m == len(ek) and np.array_equal(uk[g, :m], ek)
                    and np.array_equal(uv[g, :m].astype(np.int64), ec)):
                bad.append(g)
        log(f"  {label}: dropped {int(dropped.sum())}, unique keys per "
            f"chip {[int(x) for x in n]}, chips {bad or 'none'} differ "
            f"from the numpy oracle, outputs on {sorted(on)}; host wall "
            f"{run_s:.3f} s (compile included)")
        if int(dropped.sum()) or bad or on != {platform}:
            errs.append(f"{label}: dropped {int(dropped.sum())}, chips "
                        f"{bad} differ, outputs on {sorted(on)}")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh MapReduce on a 2x2 mesh")
    args = ap.parse_args(argv)
    # outside the repository this fails before any output
    import repro.sweep  # noqa: F401
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    phases = ([("mesh", mesh_phase)] if args.chips == 4 else
              [("fill", fill_phase), ("lockstep", lockstep_phase),
               ("mapreduce", mapreduce_phase)])
    failures = []
    for name, phase in phases:
        t0 = time.perf_counter()
        errs = phase()
        log(f"[{name}: {'FAILED' if errs else 'OK'}, host wall "
            f"{time.perf_counter() - t0:.1f} s]")
        failures += [f"{name}: {e}" for e in errs]
    if failures:
        for f in failures:
            print(f"chip_smoke: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
