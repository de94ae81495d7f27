"""Tests for repro.sched: streaming engine, scenarios, telemetry, service."""
import math

import pytest

from repro.core import (FaultModel, PolicyPrioritizer, Simulator,
                        generate_trace, make_cluster, make_policy)
from repro.core.types import JobState
from repro.sched import (RollingTelemetry, SchedulerEngine, get_scenario,
                         jain_index, list_scenarios, run_scenario, run_stream)

# Golden aggregates recorded from the seed implementation (pre-engine
# Simulator.run_batch) on fixed seeds — the engine-backed path must stay
# bit-identical: (makespan, total_wait, gpu_seconds, decisions, milp_calls,
# backfills, restarts).  The MILP row's milp_calls is 64 under scipy 1.17:
# 27 of its solves are exact ties between spread and pack, HiGHS there
# resolves every tie to pack, and one tie the older HiGHS resolved to
# spread left a later job with a single candidate way (no solve); the
# makespan, wait and GPU-seconds are unchanged.
SEED_GOLDENS = {
    ("helios", 96, 0, "fcfs", "milp", True, False):
        (15713.6353051043, 21243.23142577523, 354981.51819661586,
         160, 64, 23, 0),
    ("helios", 96, 0, "sjf", "pack", False, False):
        (17240.76681510536, 33201.677919136404, 360452.05060567195,
         184, 0, 0, 0),
    ("philly", 64, 3, "fcfs", "pack", True, True):
        (204802.50966770164, 71493.66006047613, 6324307.354041935,
         377, 0, 11, 258),
    ("alibaba", 80, 5, "wfp3", "spread", True, False):
        (159707.73323363136, 18867.45225254594, 538229.1101009173,
         143, 0, 9, 0),
}


def _make_engine(spec, policy="fcfs", **kw):
    return SchedulerEngine(spec, PolicyPrioritizer(make_policy(policy)), **kw)


@pytest.mark.parametrize("key", sorted(SEED_GOLDENS, key=str))
def test_run_batch_matches_seed_goldens(key):
    """Simulator.run_batch (now an engine wrapper) is bit-identical to the
    pre-extraction event loop on fixed seeds."""
    trace, n, seed, policy, allocator, backfill, faults = key
    fm = FaultModel(mtbf_per_node=3 * 3600.0, repair_time=600.0, seed=1) \
        if faults else None
    jobs = generate_trace(trace, n, seed=seed)
    sim = Simulator(make_cluster(trace), allocator=allocator,
                    backfill=backfill, fault_model=fm)
    r = sim.run_batch([j.clone_pending() for j in jobs],
                      PolicyPrioritizer(make_policy(policy)))
    got = (r.makespan, r.total_wait, r.gpu_seconds_used, r.decisions,
           r.milp_calls, r.backfills, r.restarts)
    assert got == SEED_GOLDENS[key]


def test_streaming_resume_equals_drain(helios_jobs, helios_cluster):
    """Two step() calls produce exactly the same schedule as one drain()."""
    jobs = helios_jobs[:160]
    e1 = _make_engine(helios_cluster, allocator="pack")
    e1.submit([j.clone_pending() for j in jobs])
    e1.drain()

    e2 = _make_engine(helios_cluster, allocator="pack")
    e2.submit([j.clone_pending() for j in jobs])
    mid = jobs[80].submit_time
    e2.step(mid)
    snap = e2.snapshot()
    assert 0 < snap.num_completed < len(jobs)   # genuinely paused mid-stream
    e2.step(math.inf)

    f1 = {j.job_id: j.finish_time for j in e1.result().jobs}
    f2 = {j.job_id: j.finish_time for j in e2.result().jobs}
    assert f1 == f2
    assert e1.decisions == e2.decisions
    assert e1.backfills == e2.backfills


def test_incremental_submit_equals_upfront(helios_jobs, helios_cluster):
    """Feeding jobs in chunks (true streaming) changes nothing vs. upfront
    submission: arrivals only take effect at their event instant."""
    jobs = helios_jobs[:120]
    e1 = _make_engine(helios_cluster, allocator="pack")
    e1.submit([j.clone_pending() for j in jobs])
    e1.drain()

    e2 = _make_engine(helios_cluster, allocator="pack")
    clones = [j.clone_pending() for j in jobs]
    e2.submit(clones[:50])
    e2.step(clones[50].submit_time - 1.0)
    assert not e2.done
    e2.submit(clones[50:])
    e2.drain()

    f1 = {j.job_id: j.finish_time for j in e1.result().jobs}
    f2 = {j.job_id: j.finish_time for j in e2.result().jobs}
    assert f1 == f2


def test_engine_cluster_persists_across_submissions(helios_cluster):
    """The cluster is never reset between waves — running jobs survive."""
    wave1 = generate_trace("helios", 24, seed=21)
    e = _make_engine(helios_cluster, allocator="pack")
    e.submit([j.clone_pending() for j in wave1])
    e.drain()
    assert e.done and len(e.completed) == 24
    t_end = e.now
    wave2 = [j.clone_pending() for j in generate_trace("helios", 24, seed=22)]
    for j in wave2:
        j.job_id += 1000
        j.submit_time += t_end          # arrive after wave 1 drained
    e.submit(wave2)
    e.drain()
    assert len(e.completed) == 48
    assert e.result().makespan > t_end - e.t0 - 1e-6


def test_queue_window_configurable(helios_cluster):
    jobs = generate_trace("helios", 64, seed=13)
    narrow = _make_engine(helios_cluster, allocator="pack", queue_window=4)
    narrow.submit([j.clone_pending() for j in jobs])
    narrow.drain()
    assert narrow.queue_window == 4
    assert len(narrow.completed) == 64
    default = _make_engine(helios_cluster, allocator="pack")
    assert default.queue_window == 10 * 256


@pytest.mark.parametrize("name", list_scenarios())
def test_scenario_smoke(name):
    """Every registered scenario builds deterministically and streams a small
    run to completion with rolling telemetry."""
    sc = get_scenario(name)
    r1 = sc.build(32, seed=3)
    r2 = sc.build(32, seed=3)
    assert [j.submit_time for j in r1.jobs] == [j.submit_time for j in r2.jobs]
    assert all(r1.jobs[i].submit_time <= r1.jobs[i + 1].submit_time
               for i in range(len(r1.jobs) - 1))
    sr = run_scenario(name, num_jobs=32, seed=3, rescan_interval=300.0,
                      sample_interval=1800.0, allocator="pack")
    assert len(sr.batch.jobs) == 32
    assert all(j.state == JobState.COMPLETED for j in sr.batch.jobs)
    assert sr.telemetry.samples, "telemetry must emit at least one sample"
    last = sr.telemetry.samples[-1]
    assert 0.0 <= last.utilization <= 1.0
    assert 0.0 < last.vc_fairness <= 1.0


def test_scenario_registry():
    assert len(list_scenarios()) >= 5
    with pytest.raises(KeyError):
        get_scenario("no-such-scenario")


def test_flash_crowd_spikes_queue():
    """The flash-crowd scenario must actually pile up a queue."""
    sr = run_scenario("flash-crowd", num_jobs=96, seed=0,
                      rescan_interval=300.0, sample_interval=600.0,
                      allocator="pack")
    assert sr.telemetry.peak_queue_len() >= 5


def test_telemetry_rolls_and_integrates(helios_cluster):
    jobs = generate_trace("helios", 96, seed=8)
    tel = RollingTelemetry(window=2 * 3600.0, sample_interval=600.0)
    sr = run_stream(helios_cluster, [j.clone_pending() for j in jobs],
                    PolicyPrioritizer(make_policy("fcfs")),
                    allocator="pack", telemetry=tel, chunked_submit=True)
    assert tel.total_finished == 96
    assert len(tel.samples) >= 2
    for s in tel.samples:
        assert 0.0 <= s.utilization <= 1.0
        assert s.jct_p50 <= s.jct_p95 <= s.jct_p99
        assert s.wait_p50 <= s.wait_p95 <= s.wait_p99
    # rolling eviction: window never reports more than everything finished
    assert max(s.finished_in_window for s in tel.samples) <= 96
    assert sr.windows > 0


def test_jain_index_bounds():
    assert jain_index([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_index([1.0, 0.0, 0.0]) == pytest.approx(1.0)  # zeros excluded
    assert jain_index([3.0, 1.0]) < 1.0
    assert jain_index([]) == 1.0


def test_run_stream_matches_run_batch(helios_jobs, helios_cluster):
    """The windowed service driver equals batch drain exactly (window
    boundaries are unobservable to the schedule)."""
    jobs = helios_jobs[:96]
    sim = Simulator(helios_cluster, allocator="pack")
    rb = sim.run_batch([j.clone_pending() for j in jobs],
                       PolicyPrioritizer(make_policy("fcfs")))
    sr = run_stream(helios_cluster, [j.clone_pending() for j in jobs],
                    PolicyPrioritizer(make_policy("fcfs")),
                    rescan_interval=60.0, allocator="pack")
    fb = {j.job_id: j.finish_time for j in rb.jobs}
    fs = {j.job_id: j.finish_time for j in sr.batch.jobs}
    assert fb == fs
    assert rb.decisions == sr.batch.decisions


def test_sla_lane_scenario():
    """sla-mix: SLA users' jobs never wait longer than the worst best-effort
    job (the bypass lane schedules them first)."""
    sc = get_scenario("sla-mix")
    run = sc.build(64, seed=2)
    assert run.sla_users
    sr = run_scenario(run, allocator="pack", rescan_interval=300.0)
    sla = [j.wait_time for j in sr.batch.jobs if j.user in run.sla_users]
    other = [j.wait_time for j in sr.batch.jobs if j.user not in run.sla_users]
    if sla and other:
        assert max(sla) <= max(other) + 1e-6


def test_chunked_hop_feeds_arrivals_before_queued_events(helios_cluster):
    """Regression: a traffic gap larger than the rescan interval whose hopped
    window contains both an unfed arrival and a queued finish must process
    the arrival first (chunked service == upfront submission)."""
    from repro.core.types import Job

    def mk(i, submit, runtime):
        return Job(job_id=i, user=0, submit_time=submit, runtime=runtime,
                   est_runtime=runtime, num_gpus=2)

    # job0 finishes at t=5030; job1 arrives at t=5000 inside the same
    # 60s window reached by hopping over the [60, 4980] gap
    jobs = [mk(0, 0.0, 5030.0), mk(1, 5000.0, 100.0)]
    results = {}
    for chunked in (False, True):
        sr = run_stream(helios_cluster, [j.clone_pending() for j in jobs],
                        PolicyPrioritizer(make_policy("fcfs")),
                        rescan_interval=60.0, allocator="pack",
                        chunked_submit=chunked)
        results[chunked] = {j.job_id: (j.start_time, j.finish_time)
                            for j in sr.batch.jobs}
    assert results[False] == results[True]
    assert results[True][1][0] == pytest.approx(5000.0)  # starts on arrival


def test_chunked_scenario_service_equals_upfront():
    """diurnal has multi-window troughs: the chunked rescan driver must
    still equal upfront submission job-for-job."""
    sc = get_scenario("diurnal")
    run = sc.build(48, seed=7)
    fins = []
    for chunked in (False, True):
        sr = run_stream(run.spec, [j.clone_pending() for j in run.jobs],
                        PolicyPrioritizer(make_policy("fcfs")),
                        rescan_interval=60.0, allocator="pack",
                        chunked_submit=chunked)
        fins.append({j.job_id: j.finish_time for j in sr.batch.jobs})
    assert fins[0] == fins[1]


def test_naive_reference_matches_seed_golden():
    """The retained naive loop (``ReferenceEngine``, scalar scoring) is
    the seed implementation and must still hit the golden aggregates."""
    from repro.sched import reference_run

    key = ("helios", 96, 0, "fcfs", "milp", True, False)
    trace, n, seed, policy, allocator, backfill, _ = key
    jobs = generate_trace(trace, n, seed=seed)
    r = reference_run(make_cluster(trace), [j.clone_pending() for j in jobs],
                      PolicyPrioritizer(make_policy(policy), batch=False),
                      allocator=allocator, backfill=backfill).result()
    got = (r.makespan, r.total_wait, r.gpu_seconds_used, r.decisions,
           r.milp_calls, r.backfills, r.restarts)
    assert got == SEED_GOLDENS[key]


def test_pending_queue_stays_sorted():
    """Indexed-queue invariant: `pending` is sorted by (submit_time, job_id)
    after every step, including requeues from faults."""
    jobs = generate_trace("philly", 64, seed=3)
    fm = FaultModel(mtbf_per_node=3 * 3600.0, repair_time=600.0, seed=1)
    e = _make_engine(make_cluster("philly"), allocator="pack", fault_model=fm)
    e.submit([j.clone_pending() for j in jobs])
    checked = 0
    while e._events:
        e.step(e.next_event_time())
        keys = [(j.submit_time, j.job_id) for j in e.pending]
        assert keys == sorted(keys)
        checked += 1
    assert checked > 0 and e.done


def test_finish_index_mirrors_running_set():
    """Finish-time-index invariant: after every step — through starts,
    finishes, fault kills, and straggler rescales — `_finish_index` holds
    exactly the running set's (finish_time, job_id) pairs, sorted."""
    jobs = generate_trace("philly", 64, seed=3)
    fm = FaultModel(mtbf_per_node=3 * 3600.0, repair_time=600.0,
                    straggler_prob=0.3, straggler_slowdown=0.4, seed=1)
    e = _make_engine(make_cluster("philly"), allocator="pack", fault_model=fm)
    e.submit([j.clone_pending() for j in jobs])
    checked = 0
    while e._events:
        e.step(e.next_event_time())
        expect = sorted((rec[3], jid) for jid, rec in e.running.items())
        assert e._finish_index == expect
        checked += 1
    assert checked > 0 and e.done


def test_guard_raises_runtime_error(helios_cluster):
    """The runaway guard must be a RuntimeError (asserts vanish under
    `python -O`)."""
    jobs = generate_trace("helios", 32, seed=4)
    e = _make_engine(helios_cluster, allocator="pack")
    e.submit([j.clone_pending() for j in jobs])
    e._guard_budget = 3
    with pytest.raises(RuntimeError, match="stuck"):
        e.drain()


def test_fault_storm_restarts():
    sr = run_scenario("fault-storm", num_jobs=32, seed=1,
                      rescan_interval=600.0, allocator="pack")
    assert len(sr.batch.jobs) == 32
    assert sr.batch.restarts > 0
    assert sr.telemetry.samples[-1].requeues >= 0


# ------------------------------------------------------------ trace replay ----


def test_trace_replay_tiles_and_is_deterministic():
    """trace-replay adapts CSV rows through repro.core.trace: truncation
    below the fixture size, tiling above it (copies time-shifted past the
    span), sequential re-ids, and seed-independence (a replay has no RNG)."""
    from repro.sched.scenarios import replay_trace_jobs, _DEFAULT_TRACE_CSV

    base = get_scenario("trace-replay").build(12, seed=0)
    again = get_scenario("trace-replay").build(12, seed=99)
    assert [j.submit_time for j in base.jobs] == \
        [j.submit_time for j in again.jobs]       # seed is ignored
    assert [j.job_id for j in base.jobs] == list(range(12))

    tiled = replay_trace_jobs(_DEFAULT_TRACE_CSV, 100)
    assert len(tiled) == 100
    ts = [j.submit_time for j in tiled]
    assert ts == sorted(ts)
    # the second copy repeats the first, shifted by one period
    assert tiled[48].runtime == tiled[0].runtime
    assert tiled[48].submit_time > tiled[47].submit_time


def test_trace_replay_env_override(tmp_path, monkeypatch):
    """REPRO_TRACE_CSV points the registered scenario at an external trace
    (the tests/ fixture here) without touching the registry."""
    import os
    from repro.sched.scenarios import TRACE_CSV_ENV

    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "trace_replay.csv")
    monkeypatch.setenv(TRACE_CSV_ENV, fixture)
    run = get_scenario("trace-replay").build(24, seed=0)
    assert len(run.jobs) == 24
    assert all(j.gpu_type in ("P100", "any") for j in run.jobs)  # philly-ish
    sr = run_scenario(run, allocator="pack", rescan_interval=300.0)
    assert len(sr.batch.jobs) == 24
    assert all(j.state == JobState.COMPLETED for j in sr.batch.jobs)

    monkeypatch.setenv(TRACE_CSV_ENV, str(tmp_path / "missing.csv"))
    with pytest.raises(FileNotFoundError):
        get_scenario("trace-replay").build(8, seed=0)
