"""Tests for repro.fed: routers, FederatedScheduler, fleet scenarios, and
the snapshot-hardening that keeps degenerate fleet members NaN-free."""
import json
import math
import os
import subprocess
import sys

import pytest
from conftest import REPO, SRC

from repro.core import PolicyPrioritizer, make_cluster, make_policy
from repro.core.types import Job
from repro.fed import (FederatedScheduler, FleetRun, ClusterInfo,
                       ClusterView, capable_clusters, get_fleet_scenario,
                       list_fleet_scenarios, list_routers, make_router,
                       merge_streams, run_fleet)
from repro.sched import (QuotaPrioritizer, SchedulerEngine, get_scenario,
                         list_scenarios, run_scenario, wrap_tenancy)
from repro.sched.engine import EngineSnapshot


def _mk_job(jid, gpus=1, gpu_type="any", submit=0.0, runtime=100.0):
    return Job(job_id=jid, user=0, submit_time=submit, runtime=runtime,
               est_runtime=runtime, num_gpus=gpus, gpu_type=gpu_type)


def _mk_view(idx, *, total=16, by_type=None, free=8, free_by_type=None,
             submitted=0, completed=0, pending=0, running=0):
    info = ClusterInfo(index=idx, name=f"c{idx}", total_gpus=total,
                       total_by_type=by_type or {"V100": total})
    snap = EngineSnapshot(
        now=0.0, submitted=submitted, num_pending=pending,
        num_running=running, num_completed=completed, free_gpus=free,
        utilization=0.0, fragmentation=0.0, decisions=0, milp_calls=0,
        backfills=0, restarts=0,
        free_gpus_by_type=free_by_type or {"V100": free})
    return ClusterView(info, snap)


# ------------------------------------------------------------------ routers ----


def test_capable_clusters_filters_and_degrades():
    views = [_mk_view(0, total=8, by_type={"P100": 8}),
             _mk_view(1, total=32, by_type={"V100": 32})]
    job = _mk_job(0, gpus=4, gpu_type="V100")
    assert capable_clusters(job, views) == [1]
    # nobody has A100: degrade to the largest overall cluster, never crash
    job = _mk_job(1, gpus=4, gpu_type="A100")
    assert capable_clusters(job, views) == [1]
    job = _mk_job(2, gpus=2, gpu_type="any")
    assert capable_clusters(job, views) == [0, 1]


def test_jsq_routes_to_shortest_queue():
    views = [_mk_view(0, submitted=10, completed=2),   # load 8
             _mk_view(1, submitted=5, completed=2),    # load 3
             _mk_view(2, submitted=9, completed=6)]    # load 3 (tie -> 1)
    assert make_router("jsq").route(_mk_job(0), views) == 1


def test_free_gpus_routes_to_most_free():
    views = [_mk_view(0, free=2), _mk_view(1, free=12), _mk_view(2, free=12)]
    assert make_router("free-gpus").route(_mk_job(0), views) == 1


def test_hash_router_deterministic_and_capable():
    views = [_mk_view(0, total=8, by_type={"P100": 8}),
             _mk_view(1, total=32, by_type={"V100": 32}),
             _mk_view(2, total=32, by_type={"V100": 32})]
    r = make_router("hash")
    picks = [r.route(_mk_job(i, gpus=1, gpu_type="V100"), views)
             for i in range(64)]
    assert picks == [r.route(_mk_job(i, gpus=1, gpu_type="V100"), views)
                     for i in range(64)]
    assert set(picks) <= {1, 2} and len(set(picks)) == 2   # spreads, capably


def test_sku_affinity_prefers_free_sku_then_falls_back():
    views = [
        _mk_view(0, total=16, by_type={"V100": 16}, free=8,
                 free_by_type={"V100": 8}),
        _mk_view(1, total=16, by_type={"V100": 8, "P100": 8}, free=12,
                 free_by_type={"V100": 2, "P100": 10}),
    ]
    r = make_router("sku-affinity")
    # V100 free on both, cluster 0 has more of the SKU despite fewer total
    assert r.route(_mk_job(0, gpus=4, gpu_type="V100"), views) == 0
    # nobody has 4 V100 free right now -> shortest queue among capable
    views[0].snap = _mk_view(0, free=1, free_by_type={"V100": 1},
                             submitted=9).snap
    views[1].snap = _mk_view(1, free=1, free_by_type={"V100": 1},
                             submitted=3).snap
    assert r.route(_mk_job(1, gpus=4, gpu_type="V100"), views) == 1


def test_weighted_random_deterministic_and_weighted():
    views = [_mk_view(0, total=4), _mk_view(1, total=60)]
    a = make_router("weighted-random", seed=7)
    b = make_router("weighted-random", seed=7)
    pa = [a.route(_mk_job(i), views) for i in range(200)]
    pb = [b.route(_mk_job(i), views) for i in range(200)]
    assert pa == pb
    assert pa.count(1) > pa.count(0)   # capacity-weighted


def test_make_router_unknown_name():
    with pytest.raises(KeyError, match="unknown router"):
        make_router("no-such-router")


# --------------------------------------------------- differential equivalence ----


@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_single_cluster_hash_identical_to_bare_engine(name):
    """Acceptance pin: a 1-cluster federation with the stateless hash router
    is bit-identical to a bare SchedulerEngine on every registered scenario
    (routing, per-job submission, and lockstep windows are unobservable)."""
    run = get_scenario(name).build(64, seed=5)
    if run.chaos is not None:
        # chaos applies at rescan-window edges, so the windowed service
        # loop (ChaosInjector) is the bare-engine reference here — the
        # fleet side wraps the same schedule in a FleetChaosInjector
        sr0 = run_scenario(run, allocator="pack", rescan_interval=60.0)
        eng = sr0.engine
        bare = {j.job_id: (j.start_time, j.finish_time, j.restarts)
                for j in sr0.batch.jobs}
    else:
        pri = wrap_tenancy(PolicyPrioritizer(make_policy("fcfs")),
                           run.sla_users, run.vc_quotas)
        hooks = (pri,) if isinstance(pri, QuotaPrioritizer) else ()
        eng = SchedulerEngine(run.spec, pri, allocator="pack",
                              fault_model=run.fault_model, hooks=hooks)
        eng.submit([j.clone_pending() for j in run.jobs])
        eng.drain()
        bare = {j.job_id: (j.start_time, j.finish_time, j.restarts)
                for j in eng.completed}

    sr = run_fleet(FleetRun.from_scenario(run), router="hash",
                   allocator="pack", rescan_interval=60.0)
    fed = {j.job_id: (j.start_time, j.finish_time, j.restarts)
           for j in sr.result.jobs}
    assert bare == fed
    assert eng.decisions == sr.result.per_cluster[0].decisions
    assert eng.backfills == sr.result.per_cluster[0].backfills


def test_single_cluster_milp_allocator_identical():
    """The equivalence holds through the MILP allocation path too."""
    run = get_scenario("steady").build(48, seed=2)
    eng = SchedulerEngine(run.spec, PolicyPrioritizer(make_policy("fcfs")),
                          allocator="milp")
    eng.submit([j.clone_pending() for j in run.jobs])
    eng.drain()
    sr = run_fleet(FleetRun.from_scenario(run), router="hash",
                   allocator="milp")
    assert {j.job_id: j.finish_time for j in eng.completed} == \
        {j.job_id: j.finish_time for j in sr.result.jobs}


def test_fed_drain_equals_windowed_lockstep():
    """With stateless routing the assignment is feed-order-invariant, so
    upfront submit + drain() must equal windowed lockstep stepping on a
    multi-cluster fleet (window edges are unobservable to the engines).
    Load-aware routers are *expected* to route differently under different
    rescan cadences — that is the point of streaming routing."""
    run = get_fleet_scenario("fleet-skewed-flash").build(90, seed=4)
    fed = FederatedScheduler(run.clusters, "hash",
                             fault_models=run.fault_models,
                             allocator="pack")
    fed.submit([j.clone_pending() for j in run.jobs])
    fed.drain()
    drained = {j.job_id: (j.start_time, j.finish_time)
               for j in fed.result().jobs}
    sr = run_fleet(run, router="hash", allocator="pack",
                   rescan_interval=120.0)
    windowed = {j.job_id: (j.start_time, j.finish_time)
                for j in sr.result.jobs}
    assert drained == windowed


# ------------------------------------------------------------ fleet behavior ----


@pytest.mark.parametrize("name", list_fleet_scenarios())
def test_fleet_scenario_smoke(name):
    """Every fleet scenario builds deterministically and streams to
    completion under every router with sane fleet metrics."""
    sc = get_fleet_scenario(name)
    r1, r2 = sc.build(30, seed=3), sc.build(30, seed=3)
    assert [j.submit_time for j in r1.jobs] == \
        [j.submit_time for j in r2.jobs]
    assert [j.job_id for j in r1.jobs] == list(range(len(r1.jobs)))
    sr = run_fleet(r1, router="jsq", allocator="pack",
                   rescan_interval=300.0)
    res = sr.result
    assert len(res.jobs) == 30
    assert sum(res.routed) == 30
    assert res.wait_p50 <= res.wait_p99
    assert res.jct_p50 <= res.jct_p99
    assert 0.0 <= res.utilization <= 1.0
    assert 0.0 < res.fairness <= 1.0
    assert all(tel is not None and tel.samples for tel in sr.telemetries)


def test_fleet_snapshot_aggregates():
    run = get_fleet_scenario("fleet-steady").build(36, seed=1)
    fed = FederatedScheduler(run.clusters, "jsq", allocator="pack",
                             fault_models=run.fault_models)
    fed.submit([j.clone_pending() for j in run.jobs])
    fed.step(fed.next_event_time() + 3600.0)
    snap = fed.snapshot()
    assert snap.submitted == 36
    assert sum(snap.routed) == 36
    assert snap.num_pending == sum(s.num_pending for s in snap.clusters)
    assert snap.free_gpus == sum(s.free_gpus for s in snap.clusters)
    assert 0.0 <= snap.utilization <= 1.0
    assert 0.0 < snap.fairness <= 1.0
    fed.drain()
    assert fed.done and fed.snapshot().num_completed == 36
    # every routed job is accounted to exactly one cluster
    assert sorted(fed.routes) == [j.job_id for j in sorted(
        run.jobs, key=lambda j: j.job_id)]


def test_jsq_spares_small_cluster_vs_hash():
    """On the size-skewed fleet, hash routes ~uniformly while jsq must shift
    load away from the small cluster toward the large one."""
    run = get_fleet_scenario("fleet-skewed-flash").build(300, seed=0)
    frac = {}
    for router in ("hash", "jsq"):
        sr = run_fleet(run, router=router, allocator="pack")
        frac[router] = sr.result.routed[0] / sum(sr.result.routed)
    assert frac["jsq"] < frac["hash"]


def test_sku_split_affinity_routes_sku_jobs_home():
    """In the A100-island fleet, every A100 job must land on the island and
    V100 jobs must land on the pool (capability filter + affinity)."""
    run = get_fleet_scenario("fleet-sku-split").build(80, seed=6)
    sr = run_fleet(run, router="sku-affinity", allocator="pack")
    fed = sr.fed
    by_id = {j.job_id: j for j in run.jobs}
    for jid, cluster in fed.routes.items():
        if by_id[jid].gpu_type == "A100":
            assert cluster == 0
        elif by_id[jid].gpu_type == "V100":
            assert cluster == 1


def test_degenerate_all_failed_cluster_cannot_nan_the_router():
    """Bugfix pin: a fleet member whose nodes have ALL failed must expose
    zero free GPUs and finite ratios, and every router must keep returning
    valid indices (no NaN propagation into routing or fleet aggregates)."""
    specs = (make_cluster("helios"), make_cluster("helios"))
    fed = FederatedScheduler(specs, "jsq", allocator="pack")
    dead = fed.engines[0].cluster
    for node in range(len(specs[0].nodes)):
        dead.fail_node(node)
    fed._refresh_views()
    dead_snap = fed.engines[0].snapshot()
    assert dead_snap.free_gpus == 0
    assert dead_snap.utilization == 0.0 and not math.isnan(dead_snap.utilization)
    assert dead_snap.fragmentation == 0.0
    snap = fed.snapshot()
    assert not math.isnan(snap.utilization) and not math.isnan(snap.fairness)
    for name in list_routers():
        idx = make_router(name, seed=1).route(_mk_job(3, gpus=2), fed._views)
        assert idx in (0, 1)
    # free-gpus must avoid the dead cluster outright
    assert make_router("free-gpus").route(_mk_job(4, gpus=2), fed._views) == 1


def test_merge_streams_unique_ids_and_order():
    a = [_mk_job(0, submit=5.0), _mk_job(1, submit=1.0)]
    b = [_mk_job(0, submit=3.0)]
    merged = merge_streams([a, b])
    assert [j.job_id for j in merged] == [0, 1, 2]
    assert [j.submit_time for j in merged] == [1.0, 3.0, 5.0]
    # inputs are cloned, not mutated
    assert a[0].job_id == 0 and b[0].job_id == 0


def test_federation_validates_inputs():
    with pytest.raises(ValueError, match="at least one cluster"):
        FederatedScheduler([], "jsq")
    with pytest.raises(ValueError, match="fault models"):
        FederatedScheduler([make_cluster("helios")], "jsq",
                           fault_models=[None, None])
    with pytest.raises(KeyError, match="unknown fleet scenario"):
        get_fleet_scenario("no-such-fleet")


# ----------------------------------------------------------------- tooling ----


def test_bench_federation_smoke(tmp_path):
    """The registered federation bench must run end-to-end in --smoke mode
    and emit a well-formed acceptance block (benches can't silently rot)."""
    json_path = tmp_path / "BENCH_federation.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_BENCH_FED_JOBS"] = "120"
    env["REPRO_BENCH_FED_JSON"] = str(json_path)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_federation", "--smoke"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    doc = json.loads(json_path.read_text())
    assert doc["bench"] == "federation" and doc["num_jobs"] == 120
    assert doc["scale"] == "smoke"
    acc = doc["acceptance"]
    assert "jsq_beats_hash" in acc and "sku_affinity_beats_hash" in acc
    for row in doc["results"].values():
        assert row["completed"] == 120
        for v in row.values():
            if isinstance(v, float):
                assert math.isfinite(v)


def test_bench_federation_registered():
    import benchmarks.run as brun
    assert "federation" in brun.MODULES
