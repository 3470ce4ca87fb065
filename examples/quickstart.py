"""Quickstart: the two layers of this framework in one minute.

  1. JoSS itself — classify + place a MapReduce job on a virtual cluster.
  2. The simulator — JoSS-T vs Hadoop FIFO on a reduced paper workload.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
# ---------------------------------------------------------------- 1. JoSS
from repro.core import Job, JossT, VirtualCluster
from repro.core.topology import HostId

cluster = VirtualCluster([3, 3])          # 2 pods ("datacenters") x 3 hosts
for i in range(6):
    cluster.place_shard(f"B{i}", [HostId(i % 2, i % 3)])
job = Job(name="WC", code_key="WC", input_type="web",
          shard_ids=[f"B{i}" for i in range(6)], shard_bytes=[128.0] * 6)

joss = JossT(cluster)
joss.registry.record(job, 1.04)           # profiled FP (paper Table 5)
joss.submit(job)
plan = joss.plan_of(job)
print(f"[1] policy {plan.policy}: map tasks -> pods "
      f"{plan.map_assignment}, reduce -> pod {plan.reduce_pod}")

# ----------------------------------------------------------- 2. simulator
from repro.sim.experiment import run_comparison

res = run_comparison("small", n_jobs=20, algos=("joss-t", "fifo"))
for name, s in res.items():
    print(f"[2] {name:7s} inter-pod traffic = {s.int_mb:8.0f} MB, "
          f"WC off-pod map rate = {s.map_locality['WC'].off_cen:.2f}")
