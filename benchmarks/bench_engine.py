"""Beyond-paper: the JoSS reduce-placement insight measured on REAL jax
collectives. Two experiments on an 8-device (2-pod x 4) host mesh:

1. MapReduce shuffle scoping (policy A): shuffle over ('pod','data')
   (off-pod) vs shuffle over ('data',) only (pod-local reduce), measured
   as lowered-HLO collective wire bytes.
2. Gradient reduction: flat all-reduce over both axes vs hierarchical
   in-pod reduce-scatter + cross-pod all-reduce + in-pod all-gather
   (sharding/collectives.py), also measured from the lowered HLO.

Plus (PR 7, no devices needed): a per-event-kind timing profile of the
discrete-event kernel itself — ``ProfilingKernel`` swapped in via the
``Simulator._make_kernel`` seam times every handler and the dispatch
post-steps on a contended fabric run, showing where an event's wall
time actually goes (the denominator behind the telemetry overhead
envelope in ``bench_obs``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from benchmarks.common import table
from repro.launch.hlo_analysis import analyze_hlo


def _require_devices(n: int = 8) -> bool:
    return len(jax.devices()) >= n


def shuffle_scoping() -> list:
    from functools import partial
    from repro.mapreduce import JOBS, corpus, mesh_mapreduce
    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    spec = JOBS["WC"]
    toks, lens = [], []
    for s in range(8):
        t, l = corpus("non-web", 512, seed=s)
        toks.append(t)
        lens.append(l)
    toks = jnp.asarray(np.stack(toks))
    lens = jnp.asarray(np.stack(lens))
    rows = []
    for scope, axes in (("off-pod shuffle", ("pod", "data")),
                        ("pod-local shuffle (policy A)", ("data",))):
        lowered = jax.jit(
            partial(mesh_mapreduce, spec, mesh=mesh, shuffle_axes=axes,
                    shard_axes=("pod", "data"))
        ).lower(toks, lens)
        txt = lowered.compile().as_text()
        t = analyze_hlo(txt, 8)
        a2a = t.per_collective.get("all-to-all", 0.0)
        rows.append([scope, a2a / 1024, t.collective_bytes / 1024])
    return rows


def grad_reduction() -> list:
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.sharding.collectives import flat_psum, hierarchical_psum
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    g = jnp.zeros((1024, 64), jnp.float32)
    rows = []
    for name, fn in (("flat all-reduce", flat_psum),
                     ("hierarchical (JoSS reduce placement)",
                      hierarchical_psum)):
        f = jax.shard_map(partial(fn, data_axis="data", pod_axis="pod"),
                          mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
        txt = jax.jit(f).lower(g).compile().as_text()
        t = analyze_hlo(txt, 8)
        # pod-crossing bytes: collectives whose group spans pods use
        # group size 8 (vs 2 for in-pod) — report total + breakdown
        rows.append([name, t.collective_bytes / 1024,
                     {k: round(v / 1024, 1)
                      for k, v in t.per_collective.items()}])
    return rows


def kernel_profile(quick: bool = False) -> list:
    """Per-event-kind handler timing on a contended fabric run (pure
    CPU — no accelerator involved). Returns table rows sorted by total
    handler seconds, with the dispatch post-step as the last row."""
    from repro.core.joss import make_algorithm
    from repro.sim.cluster_sim import SimConfig, Simulator
    from repro.sim.engine import ProfilingKernel
    from repro.sim.network import FabricConfig
    from repro.sim.workloads import (fabric_links, make_cluster,
                                     small_workload)
    hpp = (8, 8) if quick else (32, 32)
    n_jobs = 24 if quick else 96
    cluster = make_cluster(hpp, links=fabric_links(hpp, wan_oversub=8.0),
                           map_slots=2, reduce_slots=2)
    jobs = small_workload(cluster, seed=11, n_jobs=n_jobs)
    for j in jobs:
        j.submit_time = 0.0
    algo = make_algorithm("joss-t", cluster)
    sim = Simulator(cluster, algo, jobs,
                    config=SimConfig(fabric=FabricConfig(log_limit=0)),
                    seed=11)
    sim._make_kernel = lambda: ProfilingKernel()
    res = sim.run()
    assert len(res.job_finish) == n_jobs
    k = sim.kernel
    total = sum(k.kind_s.values()) + k.post_step_s
    rows = []
    for kind in sorted(k.kind_s, key=lambda x: -k.kind_s[x]):
        s, n = k.kind_s[kind], k.kind_n[kind]
        rows.append([kind, n, f"{s * 1e3:.1f}", f"{s / n * 1e6:.1f}",
                     f"{s / total:.1%}"])
    n_steps = sum(n for kind, n in k.kind_n.items()
                  if kind not in k._self_stepping)
    rows.append(["(dispatch post-step)", n_steps,
                 f"{k.post_step_s * 1e3:.1f}",
                 f"{k.post_step_s / max(n_steps, 1) * 1e6:.1f}",
                 f"{k.post_step_s / total:.1%}"])
    return rows


def run(quick: bool = False) -> str:
    out = []
    out.append(table(
        "Event-kernel handler profile — contended fabric run "
        f"({'2x8' if quick else '2x32'} hosts, burst workload, "
        "ProfilingKernel via Simulator._make_kernel)",
        ["kind", "events", "total ms", "us/event", "share"],
        kernel_profile(quick)))
    if not _require_devices(8):
        return ("\n".join(out)
                + "\n\n## Engine collective measurements: SKIPPED "
                "(needs 8 devices; run via benchmarks.run)")
    rows = shuffle_scoping()
    out.append(table("JoSS policy A as collective scoping — shuffle "
                     "wire bytes (KiB, 8 devices)",
                     ["shuffle scope", "all-to-all KiB",
                      "total collective KiB"], rows))
    assert rows[1][2] <= rows[0][2], "pod-local shuffle must not move more"
    rows = grad_reduction()
    out.append(table("Gradient reduction: flat vs hierarchical "
                     "(wire KiB, 8 devices)",
                     ["schedule", "total KiB", "per-collective KiB"],
                     rows))
    return "\n".join(out)


if __name__ == "__main__":
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    print(run())
