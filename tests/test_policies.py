"""§4.2 scheduling policies, including the paper's Fig. 3 worked example."""
import random

import pytest

from repro.core import (Job, VirtualCluster, policy_a, policy_b, policy_c)
from repro.core.queues import ClusterQueues
from repro.core.topology import HostId


def fig3_cluster_and_job():
    """Fig. 3 exactly as the paper's §4.2.2 walkthrough implies:

    cen1: {B1, B2, B4} | cen2: {B1, B2, B3, B5} | cen3: {B3, B4, B5, B6}
    (B6's two replicas both live inside cen3, on different VPSs.)
    """
    cluster = VirtualCluster([2, 2, 2])
    reps = {
        "B1": [(0, 0), (1, 0)], "B2": [(0, 0), (1, 1)],
        "B3": [(1, 0), (2, 0)], "B4": [(0, 1), (2, 0)],
        "B5": [(1, 1), (2, 1)], "B6": [(2, 0), (2, 1)],
    }
    for sid, hids in reps.items():
        cluster.place_shard(sid, [HostId(p, i) for p, i in hids])
    job = Job(name="Y", code_key="Y", input_type="web",
              shard_ids=["B1", "B2", "B3", "B4", "B5", "B6"],
              shard_bytes=[128.0] * 6, n_reducers=2)
    return cluster, job


def test_policy_b_matches_fig3():
    cluster, job = fig3_cluster_and_job()
    plan = policy_b(job, cluster, ClusterQueues(3))
    by_shard = dict(zip(job.shard_ids, plan.map_assignment))
    # paper: cen2 takes the largest unique set {B1,B2,B3,B5} first ...
    assert [by_shard[b] for b in ("B1", "B2", "B3", "B5")] == [1, 1, 1, 1]
    # ... then cen3 takes the remaining {B4, B6} (cen1 has only {B4} left)
    assert [by_shard[b] for b in ("B4", "B6")] == [2, 2]
    # all reduce tasks go to the pod with most unique blocks: cen2
    assert plan.reduce_pod == 1
    assert plan.policy == "B" and not plan.new_queues


def test_policy_a_least_loaded():
    cluster, job = fig3_cluster_and_job()
    queues = ClusterQueues(3)
    queues.pods[0].mq0.extend([object()] * 5)
    queues.pods[1].mq0.extend([object()] * 2)
    # pod 2 empty -> least loaded
    plan = policy_a(job, cluster, queues)
    assert set(plan.map_assignment) == {2}
    assert plan.reduce_pod == 2
    assert plan.policy == "A"


def test_policy_c_same_placement_new_queues():
    cluster, job = fig3_cluster_and_job()
    b = policy_b(job, cluster, ClusterQueues(3))
    c = policy_c(job, cluster, ClusterQueues(3))
    assert c.map_assignment == b.map_assignment
    assert c.reduce_pod == b.reduce_pod
    assert c.new_queues and not b.new_queues


def test_policy_b_replica_less_shard_falls_back():
    cluster = VirtualCluster([2, 2])
    cluster.place_shard("B0", [HostId(0, 0)])
    job = Job(name="z", code_key="z", input_type="web",
              shard_ids=["B0", "GONE"], shard_bytes=[128.0, 128.0])
    plan = policy_b(job, cluster, ClusterQueues(2))
    assert len(plan.map_assignment) == 2
    assert plan.map_assignment[0] == 0      # replica-backed
    assert plan.map_assignment[1] in (0, 1)  # fallback is deterministic


# -- policies A/B/C against an enumeration oracle -----------------------------
#: unprocessed tasks queued at pod c: pod 0 has the fewest, so policy A
#: must skip it when it is hostless; pods 2 and 4 tie
POD_LOAD = [0, 3, 1, 2, 1]


def _layout_cluster(k, replication, layout):
    """k pods of 2-3 hosts, 2k+3 shards with ``replication`` replicas on
    distinct random hosts. ``hostless``: every host of pod 0 is returned
    (its replicas go with it). ``lost``: the job also reads a shard that
    was never placed, and one placed shard loses every replica to host
    departures whose pods are then refilled with fresh hosts."""
    rng = random.Random(100 * k + 10 * replication + len(layout))
    cluster = VirtualCluster([2 + c % 2 for c in range(k)])
    hosts = [h.hid for h in cluster.hosts()]
    sids = [f"B{i}" for i in range(2 * k + 3)]
    for s in sids:
        cluster.place_shard(s, rng.sample(hosts, replication))
    if layout == "hostless":
        for h in list(cluster.pods[0].hosts):
            cluster.remove_host(h.hid)
    elif layout == "lost":
        for hid in list(cluster.shard_replicas["B1"]):
            if cluster.has_host(hid):
                cluster.remove_host(hid)
                cluster.add_host(hid.pod)
        sids.append("never-placed")
    job = Job(name="P", code_key="P", input_type="web", shard_ids=sids,
              shard_bytes=[128.0] * len(sids), n_reducers=2)
    return cluster, job


def _oracle_reduce_pod(cluster, job):
    """The live pod holding the most unique shards of the job, counted
    from the replica host lists; ties to the lowest pod id."""
    held = {c: 0 for c in range(cluster.k) if cluster.pods[c].hosts}
    for s in set(job.shard_ids):
        for c in {h.pod for h in cluster.shard_replicas.get(s, ())}:
            held[c] += 1
    best = max(held.values())
    return min(c for c, n in held.items() if n == best)


@pytest.mark.parametrize("layout", ["healthy", "hostless", "lost"])
@pytest.mark.parametrize("replication", [1, 2, 3])
@pytest.mark.parametrize("k", [2, 3, 4, 5])
def test_policies_match_the_enumeration_oracle(k, replication, layout):
    cluster, job = _layout_cluster(k, replication, layout)
    queues = ClusterQueues(cluster)
    for c in range(k):
        queues.pods[c].mq0.extend([object()] * POD_LOAD[c])
    live = [c for c in range(k) if cluster.pods[c].hosts]
    want_a = min(live, key=lambda c: (queues.pods[c].unprocessed(), c))
    want_reduce = _oracle_reduce_pod(cluster, job)
    assert layout != "hostless" or 0 not in (want_a, want_reduce)

    a = policy_a(job, cluster, queues)
    assert queues.least_loaded_pod() == want_a
    assert a.map_assignment == [want_a] * job.m
    assert a.reduce_pod == want_a and a.policy == "A"

    b = policy_b(job, cluster, queues)
    c = policy_c(job, cluster, queues)
    assert (b.map_assignment, b.reduce_pod) == (c.map_assignment,
                                                c.reduce_pod)
    assert (a.new_queues, b.new_queues, c.new_queues) == (False, False, True)
    assert b.reduce_pod == want_reduce
    lost = set()
    for sid, pod in zip(job.shard_ids, b.map_assignment):
        pods = {h.pod for h in cluster.shard_replicas.get(sid, ())}
        if pods:
            assert pod in pods, (sid, pod, pods)
        else:
            lost.add(sid)
            assert pod == want_reduce, (sid, pod)
        # the first largest set is the reduce pod's: it keeps its shards
        if want_reduce in pods:
            assert pod == want_reduce, (sid, pod)
        assert cluster.pods[pod].hosts, (sid, pod)
    if layout == "healthy":
        assert not lost
    elif layout == "lost":
        assert {"B1", "never-placed"} <= lost
