"""§4.1 job classification: Eq. 3 (RH/MH), Eq. 4 (small/large), the FP
registry (Fig. 4 lines 1-6), and the web/non-web input classifier."""
import pytest
pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (FpRegistry, Job, JobClassifier, JobKind,
                        VirtualCluster, classify_input_type)
from repro.core.classifier import best_threshold
from repro.sim.workloads import BLOCK_MB, PAPER_BENCHMARKS


def mk_job(m, fp=1.0, name="j", input_type="web"):
    return Job(name=name, code_key=name, input_type=input_type,
               shard_ids=[f"{name}/B{i}" for i in range(m)],
               shard_bytes=[128.0] * m, true_fp=fp)


def test_unknown_until_profiled():
    cluster = VirtualCluster([15, 15])
    reg = FpRegistry()
    clf = JobClassifier(cluster, reg)
    job = mk_job(8, fp=3.0)
    assert clf.classify(job) is JobKind.UNKNOWN
    reg.record(job, 3.0)
    assert clf.classify(mk_job(8, name="j")) is JobKind.SMALL_RH


def test_eq3_rh_vs_mh_boundary():
    """td = k/(k-1) = 2 for two pods; FP just above/below classifies RH/MH."""
    cluster = VirtualCluster([15, 15])
    reg = FpRegistry()
    clf = JobClassifier(cluster, reg)
    for fp, expect in ((2.01, JobKind.SMALL_RH), (2.0, JobKind.SMALL_MH),
                       (1.2, JobKind.SMALL_MH)):
        name = f"job{fp}"
        j = mk_job(8, fp=fp, name=name)
        reg.record(j, fp)
        assert clf.classify(j) is expect, fp


def test_eq4_small_vs_large():
    cluster = VirtualCluster([15, 15])   # N_avg_VPS = 15
    reg = FpRegistry()
    clf = JobClassifier(cluster, reg)
    small = mk_job(15, name="s")
    large = mk_job(16, name="l")
    for j in (small, large):
        reg.record(j, 1.0)
    assert clf.classify(small) is JobKind.SMALL_MH
    assert clf.classify(large) is JobKind.LARGE


@given(m=st.integers(1, 100), fp=st.floats(0, 10),
       pods=st.lists(st.integers(1, 40), min_size=2, max_size=6))
@settings(max_examples=200, deadline=None)
def test_classification_total(m, fp, pods):
    """Every profiled job lands in exactly one of the three classes."""
    cluster = VirtualCluster(pods)
    reg = FpRegistry()
    clf = JobClassifier(cluster, reg)
    j = mk_job(m, fp=fp, name=f"j{m}_{fp}")
    reg.record(j, fp)
    kind = clf.classify(j)
    n_avg = sum(pods) / len(pods)
    if m <= n_avg:
        assert kind in (JobKind.SMALL_MH, JobKind.SMALL_RH)
        assert (kind is JobKind.SMALL_RH) == (fp > cluster.k /
                                              (cluster.k - 1))
    else:
        assert kind is JobKind.LARGE


def test_fp_registry_running_average_and_storage():
    reg = FpRegistry()
    j = mk_job(4, name="wc")
    reg.record(j, 1.0)
    reg.record(j, 2.0)
    assert reg.fp_of(j) == pytest.approx(1.5)
    assert reg.storage_bytes == 20  # one record, ~20 bytes (paper §6.3)


def test_input_type_classifier():
    web = "<page><title>X</title><revision><text>hello</text></revision>"
    txt = "the quick brown fox jumps over the lazy dog " * 20
    assert classify_input_type(web) == "web"
    assert classify_input_type(txt) == "non-web"
    assert classify_input_type("") == "non-web"


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("gb", [1, 12])
@pytest.mark.parametrize("name", sorted(PAPER_BENCHMARKS))
def test_paper_jobs_classify_as_eq3_and_eq4_say(name, gb, k):
    """Each Table 5 job at a Table 7 size (1 GB: 8 maps, 12 GB: 96) on k
    pods of 15 VPSs. Eq. 4: small iff m <= N_avg_VPS. Eq. 3: RH iff the
    policy-B worst case TR2 = (k-1)/k * S_map * FP (Eq. 6) exceeds the
    policy-A worst case TR1 = S_map (Eq. 5), i.e. FP > td = k/(k-1)."""
    bench = PAPER_BENCHMARKS[name]
    m = int(gb * 1024 / BLOCK_MB)
    assert m == {1: 8, 12: 96}[gb]
    vps = [15] * k
    cluster = VirtualCluster(vps)
    reg = FpRegistry()
    clf = JobClassifier(cluster, reg)
    job = Job(name=name, code_key=name, input_type=bench.input_type,
              shard_ids=[f"{name}/{gb}/B{i}" for i in range(m)],
              shard_bytes=[BLOCK_MB] * m, true_fp=bench.fp)
    small = m <= sum(vps) / k
    s_map = job.s_map
    rh = (k - 1) / k * s_map * bench.fp > s_map
    assert best_threshold(k) == k / (k - 1)
    assert rh == (bench.fp > best_threshold(k))
    # first sighting: a small job goes through the FIFO queues unprofiled
    assert clf.classify(job) is (JobKind.UNKNOWN if small else JobKind.LARGE)
    reg.record(job, bench.fp)
    want = (JobKind.LARGE if not small
            else JobKind.SMALL_RH if rh else JobKind.SMALL_MH)
    assert clf.classify(job) is want
