"""Tests for repro.scale + the elastic-capacity plumbing underneath it:
ClusterState add/remove/cordon/retire drain semantics, cache invalidation
on capacity version bumps, engine reschedule, controller hysteresis /
cooldown / bounds / stall override, service- and federation-level
integration, and the disabled-autoscaler bit-identity pins."""
import json
import math
import os
import subprocess
import sys

import pytest
from conftest import REPO, SRC

from repro.core import (ClusterState, PolicyPrioritizer, make_cluster,
                        make_policy)
from repro.core.types import Job, NodeSpec
from repro.fed import FederatedScheduler, FleetRun, run_fleet
from repro.scale import (PoolSpec, QueuePressureAutoscaler,
                         TargetUtilizationAutoscaler, list_autoscalers,
                         make_autoscaler, pools_from_spec)
from repro.sched import (QuotaPrioritizer, SchedulerEngine, get_scenario,
                         list_scenarios, run_scenario, run_stream,
                         wrap_tenancy)


def mk_job(i, gpus=1, gpu_type="any", submit=0.0, runtime=1000.0):
    return Job(job_id=i, user=0, submit_time=submit, runtime=runtime,
               est_runtime=runtime, num_gpus=gpus, gpu_type=gpu_type)


def frozen_autoscaler(spec):
    """A controller that can never act: the band spans [0, 1] so the
    signal cannot leave it.  Attaching it must be unobservable."""
    return TargetUtilizationAutoscaler(pools_from_spec(spec),
                                       util_low=0.0, util_high=1.0)


# ----------------------------------------------------- cluster elasticity ----


def test_add_node_grows_capacity_and_placement():
    c = ClusterState(make_cluster("helios"), cache=True)
    total0, by0 = c.free_gpu_tallies()
    v0, tv0 = c.version, c.topo_version
    nid = c.add_node(NodeSpec(0, "A100", 8, 96, 768.0, 2.0))
    assert nid == 10 and len(c.spec.nodes) == 11
    assert c.spec.nodes[nid].node_id == nid
    assert c.version > v0 and c.topo_version > tv0
    total1, by1 = c.free_gpu_tallies()
    assert total1 == total0 + 8
    assert by1["A100"] == 8 and by1["V100"] == by0["V100"]
    # the new SKU is immediately placeable
    j = mk_job(0, gpus=8, gpu_type="A100")
    assert c.can_schedule_now(j)
    assert c.find_placement(j, "pack") == {nid: 8}


def test_remove_idle_node_retires_immediately():
    c = ClusterState(make_cluster("helios"), cache=True)
    total0, _ = c.free_gpu_tallies()
    assert c.remove_node(3) is True
    assert bool(c.retired[3]) and not bool(c.cordoned[3])
    total1, _ = c.free_gpu_tallies()
    assert total1 == total0 - int(c.total_gpus[3])
    assert c.provisioned_gpu_totals()[0] == total1
    # placement never lands on a retired node (fresh query + cached re-read)
    big = mk_job(0, gpus=int(c.total_gpus[3]), gpu_type="P100")
    for _ in range(2):
        pl = c.find_placement(big, "spread")
        assert pl is None or 3 not in pl
    with pytest.raises(ValueError, match="already retired"):
        c.remove_node(3)
    with pytest.raises(ValueError, match="no such node"):
        c.remove_node(99)


def test_remove_busy_node_cordons_then_auto_retires():
    c = ClusterState(make_cluster("helios"), cache=True)
    j = mk_job(0, gpus=4, gpu_type="P100")
    pl = c.find_placement(j, "pack")
    (node, _), = pl.items()
    c.allocate(j, pl)
    assert c.remove_node(node) is False          # busy -> draining
    assert bool(c.cordoned[node]) and not bool(c.retired[node])
    # still provisioned (the operator pays for it until it drains) ...
    assert c.provisioned_gpu_totals()[0] == int(c.total_gpus.sum())
    # ... but excluded from placement and the free tallies
    assert not c.eligible_mask("P100")[node]
    free, by = c.free_gpu_tallies()
    assert by["P100"] == int(c.free_gpus[c.sku_mask("P100")
                                         & c.placeable_mask()].sum())
    # draining completes on the last release: cordon -> retired
    c.release(j, pl)
    assert bool(c.retired[node]) and not bool(c.cordoned[node])
    assert c.provisioned_gpu_totals()[0] == \
        int(c.total_gpus.sum()) - int(c.total_gpus[node])


def test_uncordon_readmits_draining_node():
    c = ClusterState(make_cluster("helios"), cache=True)
    j = mk_job(0, gpus=2, gpu_type="V100")
    pl = c.find_placement(j, "pack")
    (node, _), = pl.items()
    c.allocate(j, pl)
    c.remove_node(node)
    assert not c.eligible_mask("V100")[node]
    c.uncordon_node(node)
    assert c.eligible_mask("V100")[node]
    c.release(j, pl)                              # no drain: not cordoned
    assert not bool(c.retired[node])


def test_capacity_bumps_invalidate_tallies_and_ratios():
    """Satellite pin: per-SKU free tallies and the memoized up-only ratios
    must invalidate on add_node/remove_node version bumps, not just
    fail/recover — a stale hit would route jobs onto vanished capacity."""
    c = ClusterState(make_cluster("helios"), cache=True)
    tallies0 = c.free_gpu_tallies()
    util0 = c.utilization(up_only=True)
    frag0 = c.fragmentation(up_only=True)
    assert c.free_gpu_tallies() is tallies0       # memoized within a version

    v = c.version
    c.remove_node(0)
    assert c.version > v
    t1 = c.free_gpu_tallies()
    assert t1 is not tallies0
    assert t1[0] == tallies0[0] - int(c.total_gpus[0])
    assert c.fragmentation(up_only=True) != frag0 or \
        c.utilization(up_only=True) == util0      # ratios recomputed, no stale

    # allocate everything on one SKU, then add a node of it: a stale
    # can_schedule_now=False must flip to True
    j = mk_job(1, gpus=8, gpu_type="V100")
    while c.can_schedule_now(j):
        c.allocate(j, c.find_placement(j, "pack"))
        j = mk_job(j.job_id + 1, gpus=8, gpu_type="V100")
    assert not c.can_schedule_now(j)
    util_before = c.utilization(up_only=True)
    c.add_node(NodeSpec(0, "V100", 8, 64, 512.0, 1.5))
    assert c.can_schedule_now(j)                  # stale False would be a bug
    assert c.free_gpu_tallies()[1]["V100"] >= 8
    assert c.utilization(up_only=True) < util_before


def test_retired_node_survives_fail_recover():
    """recover_node on a retired slot must not resurrect its capacity."""
    c = ClusterState(make_cluster("helios"), cache=True)
    c.remove_node(2)
    before = c.free_gpu_tallies()
    c.fail_node(2)
    c.recover_node(2)
    assert c.free_gpu_tallies() == before
    assert not c.eligible_mask("any")[2]


# ------------------------------------------------------------ engine level ----


def test_engine_drains_cordoned_node_and_places_elsewhere():
    spec = make_cluster("helios")
    eng = SchedulerEngine(spec, PolicyPrioritizer(make_policy("fcfs")),
                          allocator="pack")
    jobs = [mk_job(i, gpus=8, gpu_type="any", submit=float(i),
                   runtime=5000.0) for i in range(4)]
    eng.submit([j for j in jobs])
    eng.step(10.0)
    assert eng.snapshot().num_running == 4
    victim = next(iter(eng.running.values()))[1]  # placement of one job
    (node, _), = victim.items()
    assert eng.cluster.remove_node(node) is False
    assert eng.snapshot().cordoned == 1
    eng.drain()
    assert eng.done
    assert bool(eng.cluster.retired[node])        # drained after finish
    assert eng.snapshot().cordoned == 0
    assert eng.snapshot().total_gpus == \
        int(eng.cluster.total_gpus.sum()) - int(eng.cluster.total_gpus[node])


def test_reschedule_starts_starved_job_after_scale_up():
    spec = make_cluster("slurm-testbed")      # biggest node: 4 GPUs
    eng = SchedulerEngine(spec, PolicyPrioritizer(make_policy("fcfs")),
                          allocator="pack")
    eng.submit([mk_job(0, gpus=16, gpu_type="A100", runtime=100.0)])
    eng.drain()
    assert not eng.done and eng.next_event_time() == math.inf
    eng.cluster.add_node(NodeSpec(0, "A100", 16, 128, 1024.0, 2.0))
    eng.reschedule(at=50.0)
    assert eng.now == 50.0 and eng.snapshot().num_running == 1
    eng.drain()
    assert eng.done


def test_reschedule_refuses_to_skip_queued_events():
    spec = make_cluster("helios")
    eng = SchedulerEngine(spec, PolicyPrioritizer(make_policy("fcfs")),
                          allocator="pack")
    eng.submit([mk_job(0, gpus=1, submit=100.0, runtime=50.0)])
    with pytest.raises(RuntimeError, match="queued event"):
        eng.reschedule(at=1e9)


# -------------------------------------------------------------- controllers ----


def test_pools_from_spec_bounds():
    pools = pools_from_spec(make_cluster("helios"), min_frac=0.25)
    assert set(pools) == {"V100", "P100"}
    for p in pools.values():
        assert p.min_nodes == 2 and p.max_nodes == 5
        assert p.template.gpu_type == p.gpu_type
    grow = pools_from_spec(make_cluster("helios"), max_frac=1.5)
    assert all(p.max_nodes == 8 for p in grow.values())


def test_autoscaler_validation():
    with pytest.raises(ValueError, match="at least one pool"):
        TargetUtilizationAutoscaler({})
    pools = pools_from_spec(make_cluster("helios"))
    with pytest.raises(ValueError, match="util_low < util_high"):
        TargetUtilizationAutoscaler(pools, util_low=0.9, util_high=0.5)
    with pytest.raises(ValueError, match="wait_down_s < wait_up_s"):
        QueuePressureAutoscaler(pools, wait_up_s=10.0, wait_down_s=60.0)
    with pytest.raises(KeyError, match="unknown autoscaler"):
        make_autoscaler("no-such", make_cluster("helios"))
    assert list_autoscalers() == ["queue-pressure", "target-util"]


def _idle_engine(spec=None):
    spec = spec or make_cluster("helios")
    return SchedulerEngine(spec, PolicyPrioritizer(make_policy("fcfs")),
                           allocator="pack")


def test_target_util_hysteresis_band():
    eng = _idle_engine()
    pools = pools_from_spec(eng.spec)
    a = TargetUtilizationAutoscaler(pools, util_low=0.3, util_high=0.8,
                                    cooldown_s=0.0)
    # idle cluster: util 0 < low -> scale down (cordon/retire one node)
    ev = a.control(eng, 100.0)
    assert len(ev) == 1 and ev[0].action in ("retire", "cordon")
    # fill the cluster: util 1.0 > high -> scale up
    eng2 = _idle_engine()
    eng2.submit([mk_job(i, gpus=8, runtime=1e5, submit=0.0)
                 for i in range(12)])
    eng2.step(1.0)
    assert eng2.snapshot().utilization > 0.8
    a2 = TargetUtilizationAutoscaler(pools_from_spec(eng2.spec, max_frac=2.0),
                                     util_low=0.3, util_high=0.8,
                                     cooldown_s=0.0)
    ev2 = a2.control(eng2, 10.0)
    assert len(ev2) == 1 and ev2[0].action == "add"
    # mid-band: no action
    a3 = TargetUtilizationAutoscaler(pools, util_low=0.0, util_high=1.0,
                                     cooldown_s=0.0)
    assert a3.control(eng2, 20.0) == []


def test_cooldown_blocks_consecutive_actions():
    eng = _idle_engine()
    a = TargetUtilizationAutoscaler(pools_from_spec(eng.spec),
                                    util_low=0.5, util_high=0.9,
                                    cooldown_s=3600.0)
    assert len(a.control(eng, 0.0)) == 1
    assert a.control(eng, 1800.0) == []           # inside cooldown
    assert len(a.control(eng, 3700.0)) == 1       # cooldown expired


def test_bounds_respected():
    eng = _idle_engine()
    a = TargetUtilizationAutoscaler(
        pools_from_spec(eng.spec, min_frac=0.4),   # min 2 of 5 per pool
        util_low=0.9, util_high=0.95, cooldown_s=0.0)
    downs = 0
    for k in range(20):
        if not a.control(eng, float(k)):
            break
        downs += 1
    # 10 nodes, min 2 per SKU pool -> exactly 6 scale-downs then hold
    assert downs == 6
    for sku in ("V100", "P100"):
        assert a._active_count(eng.cluster, sku) == 2


def test_stall_override_ignores_cooldown_and_scales_up():
    eng = _idle_engine(make_cluster("slurm-testbed"))
    eng.submit([mk_job(0, gpus=64, gpu_type="P100", runtime=100.0)])
    eng.drain()
    assert not eng.done                            # unplaceable at 14 GPUs
    pools = {"P100": PoolSpec("P100", NodeSpec(0, "P100", 32, 128, 1024.0,
                                               1.0), 1, 4)}
    a = TargetUtilizationAutoscaler(pools, cooldown_s=1e12)
    ev = a.control(eng, 200.0, stalled=True)
    assert [e.action for e in ev] == ["add"]
    ev2 = a.control(eng, 300.0, stalled=True)     # still starved: 32 < 64
    assert [e.action for e in ev2] == ["add"]
    eng.drain()
    assert eng.done                                # 2x32 placed the gang


def test_scale_up_prefers_uncordon_over_add():
    eng = _idle_engine()
    jobs = [mk_job(i, gpus=8, runtime=1e5) for i in range(10)]
    eng.submit(jobs)
    eng.step(1.0)
    node = next(iter(eng.running.values()))[1]
    (nid, _), = node.items()
    eng.cluster.remove_node(nid)                  # cordons (busy)
    a = TargetUtilizationAutoscaler(pools_from_spec(eng.spec, max_frac=2.0),
                                    util_low=0.1, util_high=0.5,
                                    cooldown_s=0.0)
    ev = a.control(eng, 10.0)
    assert [e.action for e in ev] == ["uncordon"] and ev[0].node_id == nid
    assert not bool(eng.cluster.cordoned[nid])


def test_queue_pressure_scales_on_backlog():
    eng = _idle_engine()
    eng.submit([mk_job(i, gpus=8, runtime=1e5) for i in range(14)])
    eng.step(1.0)
    snap = eng.snapshot()
    assert snap.num_pending > 0 and snap.free_gpus == 0
    a = QueuePressureAutoscaler(pools_from_spec(eng.spec, max_frac=2.0),
                                cooldown_s=0.0)
    ev = a.control(eng, 10.0)
    assert len(ev) == 1 and ev[0].action == "add"
    assert "backlog" in ev[0].reason


# ------------------------------------------------------- service integration ----


def test_autoscaled_stream_cuts_provisioned_gpu_hours():
    """The headline behavior at test scale: on diurnal traffic a hysteresis
    controller completes every job with fewer provisioned GPU-hours than
    the static run, and the events/cost are visible in telemetry."""
    static = run_scenario("diurnal", num_jobs=220, seed=0, allocator="pack",
                          rescan_interval=300.0)
    assert len(static.batch.jobs) == 220
    run = get_scenario("diurnal").build(220, 0)
    asc = TargetUtilizationAutoscaler(
        pools_from_spec(run.spec, min_frac=0.25), util_low=0.6,
        util_high=0.85, max_pending_for_down=4, cooldown_s=1800.0)
    elastic = run_scenario(run, allocator="pack", rescan_interval=300.0,
                           autoscaler=asc)
    assert len(elastic.batch.jobs) == 220
    t_s, t_e = static.telemetry, elastic.telemetry
    assert t_e.provisioned_gpu_hours < t_s.provisioned_gpu_hours
    assert asc.events and t_e.scale_events == asc.events
    # the original spec must not have been mutated by scale-ups
    assert len(run.spec.nodes) == 10


def test_stalled_stream_scales_up_to_finish():
    """A scenario whose jobs exceed current capacity: the stall override
    must grow the cluster instead of ending the stream incomplete."""
    spec = make_cluster("slurm-testbed")
    jobs = [mk_job(0, gpus=2, gpu_type="P100", runtime=500.0, submit=0.0),
            mk_job(1, gpus=24, gpu_type="P100", runtime=500.0, submit=60.0)]
    pools = {"P100": PoolSpec("P100", NodeSpec(0, "P100", 8, 64, 512.0, 1.0),
                              1, 6)}
    asc = TargetUtilizationAutoscaler(pools, cooldown_s=1e12)
    sr = run_stream(spec, jobs, PolicyPrioritizer(make_policy("fcfs")),
                    allocator="pack", rescan_interval=60.0, autoscaler=asc)
    assert len(sr.batch.jobs) == 2                 # both completed
    assert any(e.action == "add" and "stall" in e.reason for e in asc.events)
    # without the controller the same stream ends incomplete
    sr0 = run_stream(spec, [j.clone_pending() for j in jobs],
                     PolicyPrioritizer(make_policy("fcfs")),
                     allocator="pack", rescan_interval=60.0)
    assert len(sr0.batch.jobs) == 1


# ------------------------------------------------- disabled == bit-identical ----


@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_disabled_autoscaler_bit_identical(name):
    """Acceptance pin: attaching a controller that never acts (and the
    spec-cloning plumbing that comes with ``autoscaler=...``) must be
    bit-identical to ``autoscaler=None`` on every registered scenario."""
    base = run_scenario(get_scenario(name).build(64, seed=5),
                        allocator="pack", rescan_interval=300.0)
    run = get_scenario(name).build(64, seed=5)
    frozen = run_scenario(run, allocator="pack", rescan_interval=300.0,
                          autoscaler=frozen_autoscaler(run.spec))
    a = {j.job_id: (j.start_time, j.finish_time, j.restarts)
         for j in base.batch.jobs}
    b = {j.job_id: (j.start_time, j.finish_time, j.restarts)
         for j in frozen.batch.jobs}
    assert a == b
    assert base.batch.decisions == frozen.batch.decisions
    assert base.batch.backfills == frozen.batch.backfills


@pytest.mark.parametrize("name", ["steady", "fault-storm", "multi-tenant",
                                  "trace-replay"])
def test_one_member_fed_frozen_autoscaler_identical_to_bare_engine(name):
    """1-member federation with a frozen controller == bare engine (the
    federation autoscaler plumbing is unobservable when disabled)."""
    run = get_scenario(name).build(48, seed=5)
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
                   allocator="pack", rescan_interval=60.0,
                   autoscaler_factory=lambda i, spec: frozen_autoscaler(spec))
    fed = {j.job_id: (j.start_time, j.finish_time, j.restarts)
           for j in sr.result.jobs}
    assert bare == fed


# ------------------------------------------------------ federation scaling ----


def test_fed_router_sees_scaled_capacity():
    """Satellite pin: after a member scales up past its static capacity,
    the capable-cluster filter (static ClusterInfo) must see the new
    totals — a job sized for the scaled member routes there instead of
    degrading to the bigger cluster."""
    small = make_cluster("slurm-testbed")    # 13 GPUs, biggest node 4
    big = make_cluster("helios")             # 80 GPUs
    pools = {"P100": PoolSpec("P100", NodeSpec(0, "P100", 32, 256, 2048.0,
                                               1.0), 1, 4)}
    asc = TargetUtilizationAutoscaler(pools, cooldown_s=1e12)
    fed = FederatedScheduler([small, big], "sku-affinity", allocator="pack",
                             autoscalers=[asc, None])
    info0 = fed.infos[0]
    assert info0.capacity_for("P100") == 8
    # grow the small member beyond its static capacity, tick the views
    fed.engines[0].cluster.add_node(pools["P100"].template)
    fed._refresh_views()
    assert fed.infos[0].capacity_for("P100") == 40
    assert fed.infos[0].total_gpus == 45
    # a 24-GPU P100 job is now capable only on the scaled member
    fed.submit([mk_job(7, gpus=24, gpu_type="P100", runtime=100.0)])
    assert fed.routes[7] == 0
    fed.drain()
    assert fed.done


def test_fed_autoscaler_validation():
    with pytest.raises(ValueError, match="autoscalers"):
        FederatedScheduler([make_cluster("helios")], "jsq",
                           autoscalers=[None, None])


# ----------------------------------------------------------------- tooling ----


def test_bench_autoscaling_smoke(tmp_path):
    """The registered autoscaling bench must run end-to-end in --smoke mode
    and emit a well-formed acceptance block."""
    json_path = tmp_path / "BENCH_autoscaling.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_BENCH_AUTOSCALE_JOBS"] = "150"
    env["REPRO_BENCH_AUTOSCALE_JSON"] = str(json_path)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_autoscaling", "--smoke"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    doc = json.loads(json_path.read_text())
    assert doc["bench"] == "autoscaling" and doc["num_jobs"] == 150
    assert doc["scale"] == "smoke"
    acc = doc["acceptance"]
    for scen in ("diurnal", "flash_crowd"):
        assert f"{scen}_cuts_gpu_hours" in acc
        assert f"{scen}_wait_within_band" in acc
    for row in doc["results"].values():
        assert row["completed"] == 150
        for v in row.values():
            if isinstance(v, float):
                assert math.isfinite(v)


def test_bench_autoscaling_registered():
    import benchmarks.run as brun
    assert "autoscaling" in brun.MODULES
