"""Fig. 4 task scheduler + Fig. 5/6 assigners: FIFO-first profiling path,
round-robin over queues (starvation avoidance), TTA vs JTA semantics."""
import pytest

from repro.core import (FpRegistry, Job, JobKind, JossScheduler, JossT,
                        JossJ, TaskState, VirtualCluster)
from repro.core.topology import HostId


def cluster2(n=4):
    c = VirtualCluster([n, n])
    return c


def mk_job(cluster, m, fp, name, pod=0):
    sids = [f"{name}/B{i}" for i in range(m)]
    for i, s in enumerate(sids):
        cluster.place_shard(s, [HostId(pod, i % cluster.pods[pod].n_hosts)])
    return Job(name=name, code_key=name, input_type="web", shard_ids=sids,
               shard_bytes=[128.0] * m, true_fp=fp)


def test_unknown_jobs_go_to_fifo_queues():
    c = cluster2()
    sched = JossScheduler(c)
    j = mk_job(c, 3, 1.0, "new")
    rec = sched.submit(j)
    assert rec.kind is JobKind.UNKNOWN
    assert len(sched.queues.mq_fifo) == 3
    assert len(sched.queues.rq_fifo) == 1
    # after completion the FP is memoized and the next submit is planned
    sched.record_completion(j, 1.0)
    j2 = mk_job(c, 3, 1.0, "new")
    rec2 = sched.submit(j2)
    assert rec2.kind is JobKind.SMALL_MH
    assert rec2.plan is not None


def test_policy_c_creates_fresh_queues_and_rr_interleaves():
    """A large job must not starve later small jobs (policy C + RR)."""
    c = cluster2(4)  # N_avg = 4
    algo = JossT(c)
    algo.registry.record(mk_job(c, 1, 1.0, "big"), 1.0)
    algo.registry.record(mk_job(c, 1, 1.0, "small"), 1.0)
    big = mk_job(c, 12, 1.0, "big", pod=0)       # large: 12 > 4
    small = mk_job(c, 2, 1.0, "small", pod=0)    # small MH
    algo.submit(big)
    algo.submit(small)
    pq = algo.scheduler.queues.pods[0]
    assert len(pq.map_queues) >= 2          # fresh queue for the large job
    # pull 4 tasks from pod 0 host: RR must alternate big/small queues
    picked = [algo.next_map_task(HostId(0, 0)) for _ in range(4)]
    names = [p.job_id for p in picked if p is not None]
    assert big.job_id in names and small.job_id in names
    # small job's tasks are served before the big job drains
    first_small = names.index(small.job_id)
    assert first_small <= 2


def test_fifo_queue_served_first():
    c = cluster2()
    algo = JossT(c)
    known = mk_job(c, 2, 1.0, "known", pod=0)
    algo.registry.record(known, 1.0)
    algo.submit(known)
    unknown = mk_job(c, 2, 1.0, "unknown", pod=0)
    algo.submit(unknown)
    t = algo.next_map_task(HostId(0, 0))
    assert t.job_id == unknown.job_id  # MQ_FIFO first (Fig. 5 line 6)


def test_jta_prefers_local_then_defers():
    """JTA (Fig. 6) picks the host-local task of the head job even when it
    is not at the head of the queue; TTA takes the head."""
    c = cluster2(4)
    tta, jta = JossT(c), JossJ(c)
    for algo in (tta, jta):
        j = mk_job(c, 4, 1.0, f"job-{algo.name}", pod=0)
        algo.registry.record(j, 1.0)
        algo.submit(j)
        # host (0,2) holds shard B2 (placed round-robin i % 4)
        t = algo.next_map_task(HostId(0, 2))
        if algo.name == "joss-t":
            assert t is not None and t.index == 0     # head of queue
        else:
            assert t is not None and t.index == 2     # local pick


def test_reduce_task_gating():
    c = cluster2()
    algo = JossT(c)
    j = mk_job(c, 2, 3.0, "rh", pod=1)
    algo.registry.record(j, 3.0)
    algo.submit(j)
    # reduce not ready until all maps done
    ready_no = lambda t: False
    ready_yes = lambda t: True
    pod = algo.plan_of(j).reduce_pod
    assert algo.next_reduce_task(HostId(pod, 0), ready_no) is None
    assert algo.next_reduce_task(HostId(pod, 0), ready_yes) is not None


#: map situations of a host asking for work, on two pods of 4 VPSs: the
#: replica host of each of the job's shards (None: never placed), the
#: job's FP, and the offers in turn as (host, TTA's pick, JTA's pick),
#: a pick being the map task's index or None (no task given)
MAP_SITUATIONS = {
    # two shards on the asking host behind two pod-local ones (policy B)
    "host_local": ([(0, 1), (0, 2), (0, 0), (0, 0)], 1.0,
                   [((0, 0), 0, 2), ((0, 0), 1, 3), ((0, 0), 2, None),
                    ((0, 0), 3, 0)]),
    # a replica-less head task (sent to the reduce pod) before two
    # pod-local ones: JTA defers once per task, pod-local first
    "pod_local": ([None, (0, 1), (0, 2)], 1.0,
                  [((0, 0), 0, None), ((0, 0), 1, 1), ((0, 0), 2, None),
                   ((0, 0), None, 2), ((0, 0), None, None),
                   ((0, 0), None, 0)]),
    # reduce-heavy (policy A): pinned to the least-loaded pod 0 while
    # every shard lives in pod 1, whose hosts get nothing
    "remote": ([(1, 0), (1, 1)], 3.0,
               [((1, 0), None, None), ((0, 0), 0, None), ((0, 0), 1, 0),
                ((0, 0), None, None), ((0, 0), None, 1)]),
}


def placed_job(cluster, name, replicas, fp):
    sids = [f"{name}/B{i}" for i in range(len(replicas))]
    for s, rep in zip(sids, replicas):
        if rep is not None:
            cluster.place_shard(s, [HostId(*rep)])
    return Job(name=name, code_key=name, input_type="web", shard_ids=sids,
               shard_bytes=[128.0] * len(sids), true_fp=fp)


@pytest.mark.parametrize("situation", [*MAP_SITUATIONS, "reduce_ready"])
@pytest.mark.parametrize("algo_cls", [JossT, JossJ])
def test_assigner_serves_a_host_in_the_papers_order(algo_cls, situation):
    """Fig. 5 (TTA) takes the head of the queue at once; Fig. 6 (JTA)
    takes the head job's host-local task, else defers the host one
    heartbeat before a pod-local task, else before the head task. Both
    give the first ready reduce task of the pod's queue in queue order."""
    c = cluster2(4)
    algo = algo_cls(c)
    if situation == "reduce_ready":
        first, second = (placed_job(c, f"r{i}-{algo.name}", [(1, i)], 1.0)
                         for i in range(2))
        for j in (first, second):
            algo.registry.record(j, 1.0)
            algo.submit(j)
            assert algo.plan_of(j).reduce_pod == 1
        ready = set()

        def pick(host):
            t = algo.next_reduce_task(HostId(*host),
                                      lambda t: t.job_id in ready)
            return None if t is None else t.job_id

        assert pick((1, 0)) is None
        ready.add(second.job_id)
        assert pick((1, 0)) == second.job_id
        ready.add(first.job_id)
        assert pick((0, 0)) is None          # reduces stay in pod 1
        assert pick((1, 0)) == first.job_id
        assert pick((1, 0)) is None
        return
    replicas, fp, offers = MAP_SITUATIONS[situation]
    job = placed_job(c, f"{situation}-{algo.name}", replicas, fp)
    algo.registry.record(job, fp)
    algo.submit(job)
    assert algo.plan_of(job).policy == ("A" if fp > 2 else "B")
    got, want = [], []
    for host, tta, jta in offers:
        t = algo.next_map_task(HostId(*host))
        assert t is None or t.job_id == job.job_id
        got.append(None if t is None else t.index)
        want.append(tta if algo_cls is JossT else jta)
    assert got == want
