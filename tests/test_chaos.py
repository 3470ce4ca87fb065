"""Chaos engineering layer (PR 10 tentpole): deterministic fault
campaigns, the injection taxonomy (correlated pod outages, gray ramps,
disk-slow episodes, link faults, hung tasks), and the adaptive
timeout/quarantine response loop.

The contract under test mirrors every prior subsystem's: pay-for-play
(attached-but-calm is bit-identical to the committed goldens), per-seed
determinism (injection and decision logs are sha-stable), and graceful
degradation (every job finishes no matter what the campaign does). The
same-tick ordering matrix at the bottom is the PR's race test: a chaos
injection, a churn kill and its near-zero-notice warning land at the
same instant for all five algorithms, twice, and must replay the exact
same trajectory.
"""
import pytest

from benchmarks.bench_chaos import GATE, _calm_subsystems, _full_sig, \
    _mk, chaos_probe
from repro.chaos import (ChaosConfig, ChaosEvent, ChaosSubsystem,
                         ResponseConfig, ResponseSubsystem, build_campaign)
from repro.core.joss import make_algorithm
from repro.elastic import ChurnConfig, ChurnModel, ElasticEngine, FixedFleet
from repro.sim.cluster_sim import SimConfig, Simulator
from repro.sim.golden import case_key, golden_cases, load_golden, \
    run_case, signature_hash
from repro.sim.network import FabricConfig
from repro.sim.workloads import fabric_links, make_cluster, small_workload

ALGOS = ("joss-t", "joss-j", "fifo", "fair", "capacity")


# --------------------------------------------------------------- helpers --
def _run(algo_name, campaign, chaos_kw=None, resp=None, *,
         hosts_per_pod=(4, 4), n_jobs=12, seed=11, config_kw=None,
         elastic=None):
    """One run with an explicit (pinned) campaign. Returns the result;
    the simulator stays reachable as ``res.sim`` is not a thing, so the
    few tests that need post-run overlay state keep their own handle."""
    cluster, jobs, algo = _mk(algo_name, hosts_per_pod, n_jobs, seed)
    chaos = ChaosSubsystem(ChaosConfig(seed=0, **(chaos_kw or {})),
                           campaign=campaign)
    subs = [chaos]
    if resp is not None:
        subs.append(ResponseSubsystem(resp))
    sim = Simulator(cluster, algo, jobs,
                    config=SimConfig(**(config_kw or {})), seed=seed,
                    elastic=elastic, subsystems=tuple(subs))
    res = sim.run()
    assert len(res.job_finish) == len(jobs)
    return res, sim


def _actions(log):
    return [entry[1] for entry in log]


def _times(log, action):
    return [entry[0] for entry in log if entry[1] == action]


# ----------------------------------------------------- campaign sampling --
def test_build_campaign_deterministic_sorted_and_counted():
    cfg = ChaosConfig(seed=3, n_outages=2, n_gray=3, n_disk=1, n_link=1,
                      n_partition=1, n_hung=2)
    a = build_campaign(cfg)
    assert a == build_campaign(cfg)                    # pure in the config
    assert len(a) == cfg.n_events == 10
    assert [e.draw for e in sorted(a, key=lambda e: e.draw)] == list(range(10))
    assert all(x.time <= y.time or (x.time, x.draw) < (y.time, y.draw)
               for x, y in zip(a, a[1:]))
    assert [(e.time, e.draw) for e in a] == \
        sorted((e.time, e.draw) for e in a)
    assert all(0.0 <= e.time < cfg.horizon for e in a)
    b = build_campaign(ChaosConfig(seed=4, n_outages=2, n_gray=3, n_disk=1,
                                   n_link=1, n_partition=1, n_hung=2))
    assert a != b                                      # seed moves the draws


def test_empty_campaign_is_empty():
    assert build_campaign(ChaosConfig(seed=99)) == []


# --------------------------------------------- golden bit-identity (off) --
@pytest.mark.parametrize("algo,variant", golden_cases())
def test_calm_attached_layer_is_bit_identical_to_golden(algo, variant):
    """An attached chaos layer with an empty campaign plus an inert
    detector must not move a single event vs the committed goldens —
    the fault layer is pay-for-play like churn/fabric/telemetry."""
    res = run_case(algo, variant, subsystems=_calm_subsystems())
    assert signature_hash(res) == load_golden()[case_key(algo, variant)]
    assert res.n_chaos_events == 0 and res.n_timeouts == 0


# --------------------------------------------------- gray ramp episodes --
def test_gray_ramp_applies_steps_and_clears():
    res, sim = _run("fifo", [ChaosEvent(30.0, "gray", 5, 0)],
                    chaos_kw=dict(gray_factor=6.0, gray_s=120.0))
    log = res.chaos.log
    assert _actions(log) == ["gray_begin", "gray_step", "gray_clear"]
    t0, t1, t2 = (e[0] for e in log)
    assert (t0, t1, t2) == (30.0, 90.0, 150.0)         # full, half, recover
    assert log[0][-1] == 6.0 and log[1][-1] == 3.5     # (1 + f) / 2
    assert res.n_chaos_events == 1 and res.chaos.n_gray == 1
    assert not sim.dyn_slow                            # overlay fully cleared


def test_gray_episode_stretches_tasks_on_the_gray_host():
    """The overlay bites: tasks started on the gray host inside the
    full-factor window run exactly ``gray_factor`` times their calm
    duration; after the clear the host is back to full speed."""
    calm, _ = _run("fifo", [])
    gray, _ = _run("fifo", [ChaosEvent(30.0, "gray", 5, 0)],
                   chaos_kw=dict(gray_factor=8.0, gray_s=400.0))

    def durs(res, lo=0.0, hi=float("inf")):
        return sorted(l.finish - l.start for l in res.task_logs
                      if (l.host.pod, l.host.index) == (1, 1)
                      and lo <= l.start < hi)

    assert min(durs(gray, 30.0, 230.0)) == \
        pytest.approx(8.0 * min(durs(calm)))
    assert min(durs(gray, 430.0)) == pytest.approx(min(durs(calm)))


# ----------------------------------------------------- disk-slow episodes --
def test_disk_episode_logs_and_clears():
    res, sim = _run("fifo", [ChaosEvent(30.0, "disk", 2, 0)],
                    chaos_kw=dict(disk_factor=6.0, disk_s=150.0))
    assert _actions(res.chaos.log) == ["disk_begin", "disk_clear"]
    assert _times(res.chaos.log, "disk_clear") == [180.0]
    assert res.chaos.n_disk == 1 and not sim.dyn_disk


# ------------------------------------------------- correlated pod outages --
def test_pod_outage_kills_and_rejoins_whole_pod():
    res, sim = _run(
        "fifo", [ChaosEvent(50.0, "outage", 1, 0)],
        chaos_kw=dict(outage_gray_s=30.0, outage_gray_factor=6.0,
                      outage_down_s=90.0),
        n_jobs=8)
    cs = res.chaos
    acts = _actions(cs.log)
    assert acts[0] == "outage_begin"
    assert cs.n_outages == 1 and cs.n_killed_hosts == 4   # the whole pod
    assert acts.count("outage_kill") == acts.count("outage_rejoin") == 4
    # the prodrome precedes the kill by outage_gray_s, the rejoin lands
    # outage_down_s after it
    assert _times(cs.log, "outage_kill") == [80.0] * 4
    assert _times(cs.log, "outage_rejoin") == [170.0] * 4
    assert len(sim.all_hosts) == 8                        # fleet restored
    assert not sim.dyn_slow


def test_outage_vetoes_the_last_host():
    """The last-offerable-host veto (same discipline as the elastic
    engine): a single-host tenant survives a pod outage."""
    res, sim = _run("fifo", [ChaosEvent(20.0, "outage", 0, 0)],
                    chaos_kw=dict(outage_gray_s=10.0),
                    hosts_per_pod=(1,), n_jobs=3)
    assert res.chaos.n_killed_hosts == 0
    assert "outage_veto" in _actions(res.chaos.log)
    assert res.chaos.n_skipped == 1
    assert len(sim.all_hosts) == 1


# ----------------------------------------------------- link faults --------
def test_link_derate_and_partition_park_and_restore():
    """Fabric faults through ``set_derate``: a 25% derate and a full
    partition (zero capacity — flows park) both restore on schedule and
    the run still drains every job."""
    links = fabric_links((4, 4), wan_oversub=4.0)
    cluster = make_cluster((4, 4), links=links)
    jobs = small_workload(cluster, seed=11, n_jobs=12)
    algo = make_algorithm("fifo", cluster)
    chaos = ChaosSubsystem(
        ChaosConfig(seed=0, link_factor=0.25, link_s=60.0,
                    partition_s=45.0),
        campaign=[ChaosEvent(20.0, "link", 1, 0),
                  ChaosEvent(40.0, "partition", 2, 1)])
    res = Simulator(cluster, algo, jobs,
                    config=SimConfig(fabric=FabricConfig(
                        completion_log=False)),
                    seed=11, subsystems=(chaos,)).run()
    assert len(res.job_finish) == len(jobs)
    cs = res.chaos
    assert cs.n_link == 1 and cs.n_partition == 1
    acts = _actions(cs.log)
    assert acts.count("link_begin") == acts.count("link_end") == 1
    assert acts.count("partition_begin") == acts.count("partition_end") == 1
    assert _times(cs.log, "link_end") == [80.0]
    assert _times(cs.log, "partition_end") == [85.0]
    # the partition really zeroes the class
    pbegin = next(e for e in cs.log if e[1] == "partition_begin")
    assert pbegin[-1] == 0.0


def test_link_faults_skipped_in_per_stream_mode():
    """Per-stream (no-fabric) runs cannot express link faults: the
    campaign logs-and-skips instead of silently dropping."""
    res, _ = _run("fifo", [ChaosEvent(20.0, "link", 1, 0),
                           ChaosEvent(30.0, "partition", 0, 1)])
    assert res.n_chaos_events == 0
    assert res.chaos.n_skipped == 2
    assert _actions(res.chaos.log) == ["link_skip", "partition_skip"]


# ------------------------------------------------------------ hung tasks --
def test_hung_task_detection_beats_waiting_out_the_hang():
    """The pure gray failure: a hang frees no slot and fires no churn
    event. Detection-off waits out the full stall; the progress-based
    timeout kills and re-runs it much sooner."""
    campaign = [ChaosEvent(82.0, "hang", 1, 0)]
    kw = dict(chaos_kw=dict(hang_s=600.0))
    off, _ = _run("fifo", campaign, **kw)
    on, _ = _run("fifo", campaign, resp=ResponseConfig(grace=2.0), **kw)
    assert off.chaos.n_hung == on.chaos.n_hung == 1
    assert off.n_timeouts == 0 and on.n_timeouts >= 1
    assert on.wtt < off.wtt
    assert len(off.job_finish) == len(on.job_finish)   # both still finish


def test_surfacing_after_max_attempts_still_finishes_the_job():
    """After ``max_attempts`` timeouts the (task, index) pair is
    surfaced as a job-level failure and requeued one final unmonitored
    time — escalation never wedges the job."""
    res, _ = _run("fifo", [ChaosEvent(82.0, "hang", 1, 0)],
                  chaos_kw=dict(hang_s=5000.0),
                  resp=ResponseConfig(grace=2.0, max_attempts=1))
    rs = res.response
    assert rs.n_surfaced >= 1
    assert "surface" in _actions(rs.log)
    assert res.n_timeouts >= 1


def test_timeout_requeues_after_exponential_backoff():
    """The re-dispatch of a timed-out attempt lands exactly
    ``backoff_base * 2^(n-1)`` after the kill (capped)."""
    res = chaos_probe("joss-t", detect=True)
    rs = res.response
    by_pair = {}
    for e in rs.log:
        if e[1] == "timeout":
            by_pair.setdefault(e[2], []).append(("timeout", e[0], e[4]))
        elif e[1] in ("requeue", "requeue_moot"):
            by_pair.setdefault(e[2], []).append(("requeue", e[0], None))
    checked = 0
    for entries in by_pair.values():
        for (k1, t1, n), (k2, t2, _) in zip(entries, entries[1:]):
            if k1 == "timeout" and k2 == "requeue":
                assert t2 - t1 == pytest.approx(
                    min(120.0, 5.0 * 2.0 ** (n - 1)), abs=1e-6)
                checked += 1
    assert checked > 0


# ------------------------------------------- quarantine / probation -------
def test_gate_quarantine_excludes_host_from_offer_sets():
    """Between a host's quarantine and its re-admission no new task may
    start on it — the offer-set exclusion, asserted on the committed
    gate scenario's real trajectory."""
    res = chaos_probe("joss-t", detect=True)
    assert res.n_quarantined > 0
    windows = {}
    for e in res.response.log:
        if e[1] == "quarantine":
            windows.setdefault(e[2], []).append([e[0], float("inf")])
        elif e[1] == "readmit" and e[2] in windows:
            windows[e[2]][-1][1] = e[0]
    assert windows
    for log in res.task_logs:
        hkey = (log.host.pod, log.host.index)
        for lo, hi in windows.get(hkey, ()):
            assert not (lo < log.start < hi), \
                f"task started on quarantined host {hkey} at {log.start}"


def test_probation_readmits_at_reduced_health():
    """Direct drive of the health machinery: one quarantine, probation
    elapses mid-run, the host re-enters the offer sets at
    ``probation_health``."""
    cluster, jobs, algo = _mk("fifo", (2, 2), 8, 11)
    resp = ResponseSubsystem(ResponseConfig(quarantine_at=1.0,
                                            probation_s=50.0))
    sim = Simulator(cluster, algo, jobs, seed=11, subsystems=(resp,))
    sim.begin()
    hid = sorted(sim.all_hosts, key=lambda h: (h.pod, h.index))[0]
    resp._charge_host(hid, 0.0)
    assert hid in sim.quarantined
    assert hid not in sim.free_map_hosts and hid not in sim.free_red_hosts
    assert resp.summary.n_quarantined == 1
    res = sim.finish(sim.step())
    assert len(res.job_finish) == len(jobs)
    assert resp.summary.n_readmitted == 1
    assert hid not in sim.quarantined
    # re-admitted at probation_health; clean finishes can only refund
    assert resp.health[hid] <= 0.5 + 1e-9


def test_quarantine_vetoes_the_last_offerable_host():
    cluster, jobs, algo = _mk("fifo", (1, 1), 4, 11)
    resp = ResponseSubsystem(ResponseConfig(quarantine_at=1.0))
    sim = Simulator(cluster, algo, jobs, seed=11, subsystems=(resp,))
    sim.begin()
    h0, h1 = sorted(sim.all_hosts, key=lambda h: (h.pod, h.index))
    resp._charge_host(h0, 0.0)
    assert h0 in sim.quarantined
    resp._charge_host(h1, 0.0)
    assert h1 not in sim.quarantined       # never blacklist the last host
    assert resp.summary.n_vetoed == 1
    assert "quarantine_veto" in _actions(resp.summary.log)


@pytest.mark.parametrize("name,expect", [("joss-t", 1), ("fifo", 0)])
def test_pod_wide_quarantine_triggers_joss_degradation(name, expect):
    """Quarantining a whole pod fires the JoSS ``pod_degraded`` hook
    (queued work re-buckets to healthy pods); algorithms without the
    hook are untouched — and both still finish every job."""
    cluster, jobs, algo = _mk(name, (2, 2), 8, 11)
    resp = ResponseSubsystem(ResponseConfig(quarantine_at=1.0,
                                            probation_s=1e9))
    sim = Simulator(cluster, algo, jobs, seed=11, subsystems=(resp,))
    sim.begin()
    for hid in sorted((h for h in sim.all_hosts if h.pod == 0),
                      key=lambda h: (h.pod, h.index)):
        resp._charge_host(hid, 0.0)
    assert resp.summary.n_quarantined == 2
    assert resp.summary.n_pods_degraded == expect
    res = sim.finish(sim.step())
    assert len(res.job_finish) == len(jobs)   # pod 1 absorbs everything


# --------------------------------------------- the committed gate claims --
@pytest.mark.parametrize("name", ALGOS)
def test_detection_cuts_wtt_and_reexec_on_the_gate(name):
    """The acceptance criterion, standalone per algorithm: on the
    committed hostile-campaign gate, the timeout+quarantine loop beats
    detection-off on WTT and re-executions with every job finishing."""
    off = chaos_probe(name, detect=False)
    on = chaos_probe(name, detect=True)
    assert on.wtt < off.wtt
    assert on.n_reexec < off.n_reexec
    assert on.n_timeouts > 0 and on.n_quarantined > 0


def test_gate_runs_are_deterministic_per_seed():
    a = chaos_probe("joss-j", detect=True)
    b = chaos_probe("joss-j", detect=True)
    assert a.chaos.signature() == b.chaos.signature()
    assert a.response.signature() == b.response.signature()
    assert _full_sig(a) == _full_sig(b)


# --------------------- same-tick chaos vs churn vs notice (the satellite) --
def _collision_point(seed):
    """Deterministic same-instant collision: read the churn model's
    pre-sampled preempt kill times (the trace is workload-independent)
    and pin chaos injections at exactly those floats. ``preempt_notice``
    of 1e-9 places the notice essentially *at* the kill, so notice
    delivery, the kill itself and the chaos op all land in one tick."""
    churn_kw = dict(spot_fraction=0.5, spot_preempt_rate=6.0,
                    preempt_notice=1e-9, horizon=1500.0)
    cluster = make_cluster((4, 4))
    cfg = ChurnConfig(seed=seed + 1, **churn_kw)
    _, events = ChurnModel(cfg).initial_trace(cluster)
    kills = sorted(e.time for e in events if e.kind == "preempt")
    assert len(kills) >= 2, "collision scenario lost its churn kills"
    campaign = [ChaosEvent(kills[0], "gray", 3, 0),
                ChaosEvent(kills[0], "hang", 1, 1),
                ChaosEvent(kills[1], "outage", 0, 2)]
    return churn_kw, campaign


@pytest.mark.parametrize("name", ALGOS)
def test_same_tick_chaos_churn_notice_is_deterministic(name):
    """The race matrix: a gray ramp and a hang at the exact instant of
    one spot kill (plus its same-instant notice), a pod outage at the
    instant of another — for every algorithm, twice. The tie-break
    (kernel insertion order: churn before chaos before response) must
    replay bit-identically, and every job must still finish."""
    seed = 7
    churn_kw, campaign = _collision_point(seed)

    def once():
        cluster, jobs, algo = _mk(name, (4, 4), 16, seed)
        eng = ElasticEngine(cluster,
                            churn=ChurnConfig(seed=seed + 1, **churn_kw),
                            autoscaler=FixedFleet())
        chaos = ChaosSubsystem(
            ChaosConfig(seed=0, gray_factor=6.0, gray_s=120.0,
                        hang_s=300.0, outage_gray_s=60.0,
                        outage_down_s=120.0),
            campaign=campaign)
        resp = ResponseSubsystem(ResponseConfig(grace=2.0))
        res = Simulator(cluster, algo, jobs, seed=seed, elastic=eng,
                        subsystems=(chaos, resp)).run()
        assert len(res.job_finish) == len(jobs)
        return res

    a, b = once(), once()
    assert a.chaos.signature() == b.chaos.signature()
    assert a.response.signature() == b.response.signature()
    assert _full_sig(a) == _full_sig(b)
    # the collision genuinely happened: chaos fired and churn killed
    assert a.n_chaos_events >= 1
    assert a.n_host_losses >= 1
    chaos_times = {e[0] for e in a.chaos.log
                   if e[1] in ("gray_begin", "hang", "outage_begin")}
    kill_times = {round(t, 6) for t in
                  (e.time for e in _churn_kills(seed, churn_kw))}
    assert chaos_times & kill_times, \
        "no chaos op actually shared an instant with a churn kill"


def _churn_kills(seed, churn_kw):
    cluster = make_cluster((4, 4))
    _, events = ChurnModel(ChurnConfig(seed=seed + 1,
                                       **churn_kw)).initial_trace(cluster)
    return [e for e in events if e.kind == "preempt"]
