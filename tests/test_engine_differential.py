"""Differential equivalence: the engine vs. the retained naive reference.

The engine's decision loop (indexed pending queue, version-keyed
feasibility cache, scratch ClusterState reuse, batch scoring) must be
**bit-identical** to the seed's naive loop in ``repro.sched.reference``
(full re-sort + linear scans + scalar scoring) — same completion order, same
per-job start/finish times, same BatchResult aggregates — on every stream we
can throw at it."""
import math
import pickle

import numpy as np
import pytest
from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

from repro.core import (FaultModel, PolicyPrioritizer, make_cluster,
                        make_policy)
from repro.core.types import Job
from repro.sched import (QuotaPrioritizer, ReferenceEngine, SchedulerEngine,
                         get_scenario, list_scenarios, reference_run,
                         run_stream)


def _run(spec, jobs, policy, *, optimized, allocator="pack",
         fault_model=None, queue_window=None, backfill=True):
    pri = PolicyPrioritizer(make_policy(policy), batch=optimized)
    cls = SchedulerEngine if optimized else ReferenceEngine
    engine = cls(spec, pri, allocator=allocator, backfill=backfill,
                 fault_model=fault_model, queue_window=queue_window)
    engine.submit([j.clone_pending() for j in jobs])
    engine.run_until_complete()
    r = engine.result()
    return {
        "completion_order": [j.job_id for j in engine.completed],
        "times": {j.job_id: (j.start_time, j.finish_time, j.restarts)
                  for j in r.jobs},
        "agg": (r.makespan, r.total_wait, r.gpu_seconds_used, r.decisions,
                r.milp_calls, r.backfills, r.restarts),
    }


def _times(jobs):
    return {j.job_id: (j.start_time, j.finish_time) for j in jobs}


@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_differential_all_scenarios(name):
    """Random 200-job streams from every registered scenario: optimized and
    naive engines produce identical completion order and BatchResult."""
    run = get_scenario(name).build(200, seed=11)
    opt = _run(run.spec, run.jobs, "fcfs", optimized=True,
               fault_model=run.fault_model)
    ref = _run(run.spec, run.jobs, "fcfs", optimized=False,
               fault_model=run.fault_model)
    assert opt["completion_order"] == ref["completion_order"]
    assert opt["times"] == ref["times"]
    assert opt["agg"] == ref["agg"]


@pytest.mark.parametrize("policy", ["sjf", "wfp3", "unicep", "f1", "qssf",
                                    "slurm-mf"])
def test_differential_policies(policy):
    """Batch scoring must not perturb the schedule for any base policy."""
    run = get_scenario("steady").build(160, seed=3)
    opt = _run(run.spec, run.jobs, policy, optimized=True)
    ref = _run(run.spec, run.jobs, policy, optimized=False)
    assert opt == ref


def test_differential_milp_allocator():
    """The MILP path consumes cached candidate_ways / eligibility masks."""
    run = get_scenario("sku-skew").build(96, seed=5)
    opt = _run(run.spec, run.jobs, "fcfs", optimized=True, allocator="milp")
    ref = _run(run.spec, run.jobs, "fcfs", optimized=False, allocator="milp")
    assert opt == ref


def test_differential_narrow_window_and_service_driver():
    """Tiny ranking window forces heavy window churn on the indexed queue;
    the rescan-interval service driver must agree too."""
    run = get_scenario("flash-crowd").build(200, seed=9)
    sr = run_stream(run.spec, [j.clone_pending() for j in run.jobs],
                    PolicyPrioritizer(make_policy("fcfs")),
                    rescan_interval=60.0, allocator="pack", queue_window=8,
                    fault_model=run.fault_model, chunked_submit=True)
    ref = reference_run(run.spec, [j.clone_pending() for j in run.jobs],
                        PolicyPrioritizer(make_policy("fcfs"), batch=False),
                        allocator="pack", queue_window=8,
                        fault_model=run.fault_model)
    assert _times(sr.batch.jobs) == _times(ref.completed)


def _mk_stream(seed: int, n: int = 200) -> list[Job]:
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(90.0, n))
    jobs = []
    for i in range(n):
        rt = float(rng.lognormal(6.0, 1.5)) + 1.0
        jobs.append(Job(
            job_id=i, user=int(rng.integers(0, 12)),
            submit_time=float(t[i]), runtime=rt,
            est_runtime=rt * float(rng.uniform(0.5, 2.0)),
            num_gpus=int(rng.choice([1, 1, 2, 4, 8, 16])),
            gpu_type=str(rng.choice(["any", "any", "V100", "P100"])),
            vc=int(rng.integers(0, 4))))
    return jobs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_synthetic_streams(seed):
    """Fully synthetic random streams (SKU mix, noisy estimates, faults)."""
    spec = make_cluster("helios")
    fm = FaultModel(mtbf_per_node=6 * 3600.0, repair_time=900.0, seed=seed)
    jobs = _mk_stream(seed)
    opt = _run(spec, jobs, "fcfs", optimized=True, fault_model=fm)
    ref = _run(spec, jobs, "fcfs", optimized=False, fault_model=fm)
    assert opt == ref


@pytest.mark.parametrize("name", ["multi-tenant", "sla-mix"])
@pytest.mark.parametrize("policy", ["slurm-mf", "qssf"])
def test_differential_tenancy_wrapped_rank_window(name, policy):
    """Tenancy wrappers (SLA lane / VC-quota gate) now expose ``rank_window``
    serving engine-maintained field views (incl. the new user/vc arrays) to
    their base — the wrapped fields path must schedule bit-identically to
    the naive scalar path."""
    from repro.sched import wrap_tenancy

    run = get_scenario(name).build(160, seed=7)

    def pri(batch):
        return wrap_tenancy(PolicyPrioritizer(make_policy(policy),
                                              batch=batch),
                            run.sla_users, run.vc_quotas)
    sr = run_stream(run.spec, [j.clone_pending() for j in run.jobs],
                    pri(True), rescan_interval=60.0, allocator="pack",
                    fault_model=run.fault_model, chunked_submit=True)
    ref = reference_run(run.spec, [j.clone_pending() for j in run.jobs],
                        pri(False), allocator="pack",
                        fault_model=run.fault_model)
    assert _times(sr.batch.jobs) == _times(ref.completed)


def test_rank_window_fields_match_rank_for_all_policies():
    """Field-array scoring (incl. user/vc served from the indexed queue)
    must order every built-in policy's window identically to the per-job
    scalar path, including history-dependent state (fair-share usage, QSSF
    runtime history)."""
    from repro.core.policies import BASE_POLICIES
    from repro.core.prioritizer import WindowFields
    from repro.core.cluster import ClusterState

    run = get_scenario("multi-tenant").build(96, seed=13)
    jobs = run.jobs
    cluster = ClusterState(run.spec)
    now = jobs[-1].submit_time + 3600.0
    for policy in BASE_POLICIES:
        pa = PolicyPrioritizer(make_policy(policy), batch=True)
        pb = PolicyPrioritizer(make_policy(policy), batch=False)
        # warm history-dependent policies with identical finish streams
        for j in jobs[:32]:
            fin = j.clone_pending()
            fin.start_time, fin.finish_time = j.submit_time, \
                j.submit_time + j.runtime
            pa.observe_finish(fin)
            pb.observe_finish(fin)
        window = jobs[32:]
        fields = WindowFields.from_jobs(window)
        assert pa.rank_window(window, cluster, now, fields) == \
            pb.rank(window, cluster, now), policy


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.sampled_from(sorted(list_scenarios())),
       st.sampled_from(["fcfs", "sjf", "wfp3", "qssf"]))
def test_differential_property(seed, scenario, policy):
    """Hypothesis sweep: any (seed, scenario, policy) triple schedules
    identically on both engine paths."""
    run = get_scenario(scenario).build(64, seed=seed % 997)
    opt = _run(run.spec, run.jobs, policy, optimized=True,
               fault_model=run.fault_model)
    ref = _run(run.spec, run.jobs, policy, optimized=False,
               fault_model=run.fault_model)
    assert opt == ref


# ---- the reference loop through save_state / load_state -------------------


def _signature(engine):
    return (tuple(sorted((j.job_id, j.start_time, j.finish_time, j.restarts)
                         for j in engine.completed)),
            (engine.decisions, engine.milp_calls, engine.backfills))


def test_fast_blob_switched_off_loads_as_reference_engine():
    """The engine's saved state with ``optimized`` switched off and the
    cluster's cache disabled (what a reference replay does) restores as a
    ``ReferenceEngine`` and from there schedules exactly as a reference run
    over the whole stream."""
    run = get_scenario("fault-storm").build(160, seed=4)
    jobs = sorted(run.jobs, key=lambda j: j.submit_time)
    half = len(jobs) // 2
    fast = SchedulerEngine(run.spec, PolicyPrioritizer(make_policy("sjf")),
                           allocator="pack", fault_model=run.fault_model)
    fast.submit([j.clone_pending() for j in jobs[:half]])
    fast.step(jobs[half].submit_time - 1.0)
    assert fast.running and fast.decisions
    state = pickle.loads(fast.save_state())
    state["optimized"] = False
    state["cluster"].cache_enabled = False
    eng = SchedulerEngine.load_state(pickle.dumps(state))
    assert type(eng) is ReferenceEngine and eng._pindex is None
    eng.submit([j.clone_pending() for j in jobs[half:]])
    eng.run_until_complete()
    ref = reference_run(run.spec, [j.clone_pending() for j in jobs],
                        PolicyPrioritizer(make_policy("sjf"), batch=False),
                        allocator="pack", fault_model=run.fault_model)
    assert eng.done and _signature(eng) == _signature(ref)


def test_reference_engine_round_trips_as_itself():
    """A ``ReferenceEngine`` saved mid-stream restores as a
    ``ReferenceEngine`` (uncached cluster, no pending index) and finishes
    exactly as a run that was never cut."""
    run = get_scenario("fault-storm").build(120, seed=6)

    def fresh():
        eng = ReferenceEngine(run.spec,
                              PolicyPrioritizer(make_policy("fcfs"),
                                                batch=False),
                              allocator="pack", fault_model=run.fault_model)
        eng.submit([j.clone_pending() for j in run.jobs])
        return eng
    straight = fresh()
    straight.run_until_complete()
    cut = fresh()
    cut.step(math.inf, max_events=40)
    back = SchedulerEngine.load_state(cut.save_state())
    assert type(back) is ReferenceEngine and back._pindex is None
    assert not back.cluster.cache_enabled
    back.run_until_complete()
    assert back.done and _signature(back) == _signature(straight)


def test_fast_blob_reattaches_the_quota_gate():
    """A saved state that carries a quota gate restores with the gate
    attached as a hook: its usage follows every later start and finish."""
    run = get_scenario("multi-tenant").build(160, seed=3)
    pri = QuotaPrioritizer(PolicyPrioritizer(make_policy("fcfs")),
                           run.vc_quotas)
    eng = SchedulerEngine(run.spec, pri, allocator="pack",
                          fault_model=run.fault_model, hooks=(pri,))
    eng.submit([j.clone_pending() for j in run.jobs])
    eng.step(math.inf, max_events=30)
    back = SchedulerEngine.load_state(eng.save_state())
    gate = back.prioritizer
    assert gate is not pri and back.hooks == [gate]
    assert gate._usage, "the blob's usage travels with the gate"
    moves, last = 0, dict(gate._usage)
    while back.step(math.inf, max_events=1):
        expect = {}
        for job, *_ in back.running.values():
            expect[job.vc] = expect.get(job.vc, 0) + job.num_gpus
        assert gate._usage == expect
        moves += gate._usage != last
        last = dict(gate._usage)
    assert back.done and moves > 0
