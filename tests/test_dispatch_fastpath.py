"""The O(1) indexed scheduling fast path must be behaviour-identical to the
retained naive reference (repro.core.reference): equivalence over randomized
workloads for every algorithm, plus unit tests for the TaskQueue internals
(tombstone removal, locality-index updates, the ready-reduce transition) and
the simulator's backlog-gated dispatch."""
import random

import pytest

from repro.core.assigners import JTA, TTA, fifo_pick_map
from repro.core.job import Job, MapTask, ReduceTask
from repro.core.joss import make_algorithm
from repro.core.queues import ClusterQueues, TaskQueue
from repro.core.reference import (ReferenceJTA, ReferenceTTA,
                                  make_reference_algorithm,
                                  reference_fifo_pick_map)
from repro.core.topology import HostId, VirtualCluster
from repro.sim.cluster_sim import SimConfig, Simulator
from repro.sim.workloads import (make_cluster, mixed_workload,
                                 profiling_prelude, small_workload)

ALGOS = ("joss-t", "joss-j", "fifo", "fair", "capacity")


# --------------------------------------------------------------- helpers --
def random_cluster_and_jobs(seed: int, n_jobs: int = 12):
    """A random topology + workload with replicated shards (the paper uses
    1 replica; replication > 1 exercises the multi-replica index paths)."""
    rng = random.Random(seed)
    k = rng.randint(2, 4)
    cluster = VirtualCluster([rng.randint(2, 6) for _ in range(k)])
    hosts = [h.hid for h in cluster.hosts()]
    jobs = []
    for j in range(n_jobs):
        m = rng.randint(1, 10)
        sids = [f"s{seed}/{j}/{b}" for b in range(m)]
        for s in sids:
            reps = rng.sample(hosts, rng.randint(1, min(3, len(hosts))))
            cluster.place_shard(s, reps)
        jobs.append(Job(
            name=f"j{j}", code_key=f"code{j % 4}", input_type="web",
            shard_ids=sids, shard_bytes=[128.0] * m,
            n_reducers=rng.randint(1, 3),
            true_fp=rng.choice([0.1, 0.6, 1.0, 3.0]),
            submit_time=rng.random() * 60.0))
    return cluster, jobs


def run_sim(factory, name, cluster_jobs_seed, config=None):
    cluster, jobs = random_cluster_and_jobs(cluster_jobs_seed)
    idx = {j.job_id: i for i, j in enumerate(jobs)}
    algo = factory(name, cluster)
    if hasattr(algo, "registry"):
        # warm FP registry for half the job codes: exercises both the
        # FIFO-profiling path and the policy A/B/C paths
        for j in jobs:
            if j.code_key in ("code0", "code1"):
                algo.registry.record(j, j.true_fp)
    res = Simulator(cluster, algo, jobs, config=config, seed=7).run()
    seq = [((log.task.tid[0], idx[log.task.tid[1]], *log.task.tid[2:]),
            (log.host.pod, log.host.index), log.start, log.finish,
            log.locality, log.bytes_local, log.bytes_pod, log.bytes_offpod)
           for log in res.task_logs]
    metrics = (res.wtt, res.int_bytes, res.pod_bytes,
               sorted((idx[k], v) for k, v in res.job_finish.items()))
    return metrics, seq


def mk_map(job_id, index, shard):
    return MapTask(job_id, index, shard, 128)


# ------------------------------------------------- equivalence properties --
@pytest.mark.parametrize("name", ALGOS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_simulation_equivalence_randomized(name, seed):
    """Indexed and reference stacks produce identical SimResult metrics AND
    identical per-task assignment sequences on randomized workloads."""
    fast_metrics, fast_seq = run_sim(make_algorithm, name, seed)
    ref_metrics, ref_seq = run_sim(make_reference_algorithm, name, seed)
    assert fast_metrics == ref_metrics
    assert fast_seq == ref_seq


@pytest.mark.parametrize("workload", ("small", "mixed"))
@pytest.mark.parametrize("name", ALGOS)
def test_simulation_equivalence_paper_workload(name, workload):
    """Same check on the paper's workloads: Table 6's small jobs and
    Table 7's mix of 1, 5 and 12 GB jobs (policies A/B/C). JoSS is
    profiled first; the baselines keep no FP registry."""
    def run(factory):
        cluster = make_cluster((4, 4))
        jobs = (small_workload(cluster, seed=5, n_jobs=12)
                if workload == "small" else mixed_workload(cluster, seed=5))
        idx = {j.job_id: i for i, j in enumerate(jobs)}
        algo = factory(name, cluster)
        if hasattr(algo, "registry"):
            for j in profiling_prelude(cluster):
                algo.registry.record(j, j.true_fp)
        res = Simulator(cluster, algo, jobs, seed=5).run()
        return (res.wtt, res.int_bytes, res.pod_bytes,
                [((log.task.tid[0], idx[log.task.tid[1]],
                   *log.task.tid[2:]), log.host, log.start)
                 for log in res.task_logs])
    assert run(make_algorithm) == run(make_reference_algorithm)


@pytest.mark.parametrize("assigner_pair", [(TTA, ReferenceTTA),
                                           (JTA, ReferenceJTA)])
def test_assigner_pick_sequence_equivalence(assigner_pair):
    """Drive indexed and reference assigners directly through a scripted
    sequence of slot offers (including JTA defer churn) and require the
    exact same pick sequence."""
    fast_cls, ref_cls = assigner_pair
    rng = random.Random(99)
    picks = []
    for cls in (fast_cls, ref_cls):
        rng2 = random.Random(42)
        cluster = VirtualCluster([3, 3])
        hosts = [h.hid for h in cluster.hosts()]
        queues = ClusterQueues(cluster)
        if not cls.needs_task_index:
            queues.set_map_task_indexing(False)
        assigner = cls(cluster, queues)
        tasks = []
        for j in range(6):
            for b in range(rng2.randint(1, 5)):
                sid = f"q/{j}/{b}"
                cluster.place_shard(sid, rng2.sample(hosts, 2))
                tasks.append(mk_map(j, b, sid))
        # two jobs through MQ_FIFO, the rest spread over pod queues
        for t in tasks:
            if t.job_id < 2:
                queues.mq_fifo.append(t)
            else:
                queues.pods[t.job_id % 2].mq0.append(t)
        seq = []
        for _ in range(3 * len(tasks)):
            hid = hosts[rng2.randrange(len(hosts))]
            got = assigner.next_map_task(hid)
            seq.append(None if got is None else (got.job_id, got.index))
        picks.append(seq)
    assert picks[0] == picks[1]
    assert any(p is not None for p in picks[0])


def test_fifo_pick_matches_reference_scan():
    """fifo_pick_map (indexed) == reference scan on a head job with mixed
    localities, including the no-replica fallback to the head task."""
    for case in range(20):
        out = []
        for pick in (fifo_pick_map, reference_fifo_pick_map):
            cluster = VirtualCluster([2, 2])
            hosts = [h.hid for h in cluster.hosts()]
            q = TaskQueue("t", cluster)
            rng2 = random.Random(1000 + case)
            for j in range(2):
                for b in range(rng2.randint(2, 6)):
                    sid = f"f/{case}/{j}/{b}"
                    if rng2.random() < 0.8:
                        cluster.place_shard(
                            sid, rng2.sample(hosts, rng2.randint(1, 2)))
                    q.append(mk_map(j, b, sid))
            seq = []
            while q:
                hid = hosts[rng2.randrange(len(hosts))]
                t = pick(q, hid, cluster)
                seq.append((t.job_id, t.index))
            out.append(seq)
        assert out[0] == out[1]


# -------------------------------------------------------- TaskQueue units --
def test_tombstone_removal_is_lazy_and_consistent():
    q = TaskQueue("t")
    tasks = [mk_map(1, i, f"s{i}") for i in range(5)]
    q.extend(tasks)
    q.remove(tasks[2])
    q.remove(tasks[0])
    assert len(q) == 3
    assert list(q) == [tasks[1], tasks[3], tasks[4]]
    assert q.peek() is tasks[1]          # tombstoned head purged
    assert q.popleft() is tasks[1]
    with pytest.raises(ValueError):
        q.remove(tasks[2])               # double-remove
    assert [q.popleft() for _ in range(2)] == [tasks[3], tasks[4]]
    assert len(q) == 0 and not q
    with pytest.raises(IndexError):
        q.popleft()


def test_locality_index_updates():
    cluster = VirtualCluster([2, 2])
    h00, h01, h10 = HostId(0, 0), HostId(0, 1), HostId(1, 0)
    cluster.place_shard("a", [h00])
    cluster.place_shard("b", [h01, h10])
    q = TaskQueue("t", cluster)
    ta, tb, tc = mk_map(1, 0, "a"), mk_map(1, 1, "b"), mk_map(1, 2, "nowhere")
    q.extend([ta, tb, tc])
    # host index
    assert q.peek_local(1, h00) is ta
    assert q.peek_local(1, h10) is tb
    assert q.peek_local(1, HostId(1, 1)) is None
    # pod index (multi-replica shard appears once per pod)
    assert q.peek_pod(1, 0) is ta
    assert q.peek_pod(1, 1) is tb
    # removal through one access path is visible through all others
    assert q.pick_local(1, h00) is ta
    assert q.peek_pod(1, 0) is tb        # ta gone from the pod index too
    assert q.pick_pod(1, 1) is tb
    assert q.peek_local(1, h10) is None
    # no-replica task is only reachable as job head
    assert q.peek_job_head(1) is tc
    assert q.pick_job_head(1) is tc
    assert q.head_job() is None and len(q) == 0


def test_head_job_follows_fifo_order():
    q = TaskQueue("t")
    a = [mk_map(7, i, f"a{i}") for i in range(2)]
    b = [mk_map(8, i, f"b{i}") for i in range(2)]
    q.extend(a)
    q.extend(b)
    assert q.head_job() == 7
    q.remove(a[0])
    q.remove(a[1])
    assert q.head_job() == 8             # job 7 drained


def test_ready_reduce_transition():
    q = TaskQueue("t")
    r1 = [ReduceTask(1, i) for i in range(2)]
    r2 = [ReduceTask(2, i) for i in range(2)]
    q.extend(r1)
    q.extend(r2)
    never = lambda t: False
    # nothing ready: neither predicate nor marks yield a task
    assert q.pick_ready(never) is None
    assert q.pick_ready(never, trust_marks=True) is None
    # later job becomes ready first
    q.mark_job_ready(2)
    assert q.pick_ready(never) is r2[0]
    assert q.pick_ready(never, trust_marks=True) is r2[1]
    # then the earlier job: enqueue order among ready jobs is preserved
    q.mark_job_ready(1)
    assert q.pick_ready(never, trust_marks=True) is r1[0]
    # marking is idempotent and drained jobs purge from the ready heap
    q.mark_job_ready(1)
    assert q.pick_ready(never) is r1[1]
    assert q.pick_ready(never, trust_marks=True) is None
    assert len(q) == 0


def test_ready_predicate_without_marks():
    """Pure-predicate readiness (no notifications) follows queue order."""
    q = TaskQueue("t")
    r1, r2 = ReduceTask(1, 0), ReduceTask(2, 0)
    q.extend([r1, r2])
    assert q.pick_ready(lambda t: t.job_id == 2) is r2
    assert q.pick_ready(lambda t: True) is r1


def test_cached_load_counters():
    cluster = VirtualCluster([2, 2])
    queues = ClusterQueues(cluster)
    ms = [mk_map(1, i, f"x{i}") for i in range(4)]
    rs = [ReduceTask(1, 0)]
    queues.pods[0].mq0.extend(ms[:3])
    queues.pods[1].mq0.append(ms[3])
    queues.pods[1].rq0.extend(rs)
    assert queues.pods[0].unprocessed() == 3
    assert queues.pods[1].unprocessed() == 2
    assert queues.map_backlog.n == 4 and queues.red_backlog.n == 1
    assert queues.total_pending() == 5
    assert queues.least_loaded_pod() == 1
    queues.pods[0].mq0.remove(ms[1])
    queues.pods[0].mq0.popleft()
    assert queues.pods[0].unprocessed() == 1
    assert queues.map_backlog.n == 2
    assert queues.least_loaded_pod() == 0


def test_legacy_int_constructor_and_opaque_payloads():
    """ClusterQueues(int) + arbitrary objects (policy unit-test idiom)."""
    queues = ClusterQueues(3)
    queues.pods[0].mq0.extend([object()] * 5)
    queues.pods[1].mq0.extend([object()] * 2)
    assert queues.least_loaded_pod() == 2
    assert queues.pods[0].unprocessed() == 5
    assert queues.total_pending() == 7


# ------------------------------------------------------- dispatch backlog --
def test_dispatch_backlog_gating_matches_naive_polling_counts():
    """The backlog-gated dispatcher completes the same jobs as the seed-style
    poll-everything dispatcher (assignment order may differ: host shuffles
    draw from the same stream at different times)."""
    for poll_all in (False, True):
        cluster, jobs = random_cluster_and_jobs(17)
        algo = make_algorithm("joss-t", cluster)
        cfg = SimConfig(poll_all_hosts=poll_all)
        res = Simulator(cluster, algo, jobs, config=cfg, seed=3).run()
        assert len(res.job_finish) == len(jobs)
        for j in res.jobs:
            assert j.done()


def test_map_less_job_reduces_become_ready():
    """A job with zero map tasks must open its shuffle gate at submit."""
    cluster = VirtualCluster([2, 2])
    job = Job(name="r-only", code_key="r", input_type="web",
              shard_ids=[], shard_bytes=[], n_reducers=2)
    algo = make_algorithm("fifo", cluster)
    res = Simulator(cluster, algo, [job], seed=1).run()
    assert job.done()
    assert len(res.task_logs) == 2
