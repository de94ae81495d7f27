"""Tests for repro.predict: quantile MLP training, running-mean baseline,
est-anchored cold start, backfill reservation/overrun mechanics, MILP
duration weights, autoscaler forecasts, failover, kernel parity, and the
predictor-off / shadow-mode bit-identity pins."""
import numpy as np
import pytest

from repro.core import ClusterState, Job, choose_allocation, make_cluster
from repro.core.milp import _lookahead_weights
from repro.core.policies import make_policy
from repro.core.prioritizer import PolicyPrioritizer
from repro.core.types import ClusterSpec, NodeSpec
from repro.predict import (CONTEXT_NAMES, PREDICT_FEATURES, OverrunPolicy,
                           QuantileMLP, RunningMeanBaseline, RuntimePredictor)
from repro.predict.predictor import KERNEL_MAX_ROWS
from repro.sched import (SchedulerEngine, get_scenario, list_scenarios,
                         run_scenario)


def mk(i, gpus, runtime=100.0, est=None, submit=0.0, user=0):
    return Job(job_id=i, user=user, submit_time=submit, runtime=runtime,
               est_runtime=est if est is not None else runtime,
               num_gpus=gpus)


def _est_pri():
    return PolicyPrioritizer(make_policy("fcfs", use_estimates=True))


def _signature(engine):
    jobs = tuple(sorted(
        (j.job_id, round(j.submit_time, 6),
         round(j.first_start_time if j.first_start_time is not None else -1,
               6),
         round(j.finish_time if j.finish_time is not None else -1, 6),
         j.restarts)
        for j in engine.completed))
    return jobs, (engine.decisions, engine.milp_calls, engine.backfills,
                  engine.restarts, engine.bf_reservations,
                  engine.bf_overruns)


# ---------------------------------------------------------------- the model --


def test_untrained_predictor_reproduces_declared_estimate():
    """Zero-init head: before any training, p50 == p90 == est (no
    cold-start cliff when assist is on from the first job)."""
    p = RuntimePredictor(assist=True)
    jobs = [mk(1, 2, runtime=500.0, est=1234.0),
            mk(2, 4, runtime=50.0, est=60.0)]
    p50, p90 = p.predict_quantiles(jobs)
    assert np.allclose(p50, [1234.0, 60.0])
    assert np.allclose(p90, [1234.0, 60.0])


def test_quantile_heads_ordered_and_floored():
    p = RuntimePredictor(assist=True)
    rng = np.random.default_rng(7)
    for k in range(200):
        j = mk(k, int(rng.integers(1, 8)), est=1000.0,
               runtime=float(rng.lognormal(7.0, 1.0)), user=k % 5)
        p.on_submit(j, 0.0)
        p.on_finish(j, j.runtime)
    jobs = [mk(900 + i, 2, est=1000.0, user=i % 5) for i in range(8)]
    p50, p90 = p.predict_quantiles(jobs)
    assert (p90 >= p50).all()
    assert (p50 >= 1.0).all()


def test_sgd_learns_systematic_underestimate():
    """A cohort declaring 10% of true runtime: the trained p50 must move
    the anchor toward the truth and beat the raw estimate's error."""
    p = RuntimePredictor(assist=True, lr=0.05)
    rng = np.random.default_rng(3)
    for k in range(400):
        rt = float(rng.lognormal(8.0, 0.3))
        j = mk(k, int(rng.integers(1, 5)), runtime=rt, est=0.1 * rt,
               user=k % 4)
        p.on_submit(j, float(k))
        p.on_finish(j, float(k) + rt)
    probe = [mk(9000 + i, 2, runtime=3000.0, est=300.0, user=i % 4)
             for i in range(16)]
    p50, _ = p.predict_quantiles(probe)
    # est error |300 - 3000| = 2700; trained prediction must close most
    assert np.abs(p50 - 3000.0).mean() < 1500.0
    assert p.mape() < p.baseline_mape() or p.mape() < 0.5


def test_running_mean_baseline_buckets_and_fallbacks():
    b = RunningMeanBaseline()
    assert b.predict(mk(1, 2, est=700.0)) == 700.0       # empty: est anchor
    b.observe(mk(2, 2, runtime=100.0, user=1), 100.0)
    b.observe(mk(3, 2, runtime=300.0, user=1), 300.0)
    assert b.predict(mk(4, 2, user=1)) == pytest.approx(200.0)  # key mean
    # unseen user falls back to the global mean, not the estimate
    assert b.predict(mk(5, 2, user=9, est=9999.0)) == pytest.approx(200.0)
    # same user, very different gpu bucket -> global mean too
    assert b.predict(mk(6, 64, user=1)) == pytest.approx(200.0)


def test_prequential_errors_are_out_of_sample():
    """MAPE must be recorded from the *pre-update* prediction: a constant-
    runtime stream still shows a nonzero first error (est anchor off)."""
    p = RuntimePredictor(assist=True)
    j = mk(1, 2, runtime=1000.0, est=2000.0)
    p.on_submit(j, 0.0)
    p.on_finish(j, 1000.0)
    assert p.mape() == pytest.approx(1.0)  # |2000-1000|/1000, pre-training


def test_unknown_duration_jobs_served_from_baseline_anchor():
    """A job without a usable declared estimate anchors on the running-mean
    baseline instead (unknown-duration trace rows)."""
    p = RuntimePredictor(assist=True)
    for k in range(5):
        p.baseline.observe(mk(k, 2, runtime=800.0, user=3), 800.0)
    j = mk(99, 2, runtime=500.0, est=float("nan"), user=3)
    p50, _ = p.predict_quantiles([j])       # untrained head: anchor exactly
    assert p50[0] == pytest.approx(800.0)
    j2 = mk(100, 2, runtime=500.0, est=-1.0, user=3)
    assert p.reserve_runtime(j2) == pytest.approx(800.0)


def test_kernel_forward_matches_numpy():
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.kernels.ops import predict_mlp
    mlp = QuantileMLP(seed=3)
    rng = np.random.default_rng(0)
    mlp.params["w3"][:] = rng.normal(0, 0.1,
                                     mlp.params["w3"].shape).astype(np.float32)
    mlp.params["b3"][:] = rng.normal(0, 0.1,
                                     mlp.params["b3"].shape).astype(np.float32)
    X = rng.normal(0, 1, (6, PREDICT_FEATURES)).astype(np.float32)
    out = np.asarray(predict_mlp(X, mlp.params))
    assert out.shape == (6, 2)
    assert np.allclose(out, mlp.forward(X), atol=1e-5)


@pytest.mark.parametrize("n", [1, 7, 9, 600, KERNEL_MAX_ROWS + 1])
def test_kernel_batches_pad_to_buckets(n):
    """The kernel path pads each batch to a power-of-two row bucket (and
    chunks beyond the largest); the real rows match the numpy forward."""
    p = RuntimePredictor(use_kernel=True, seed=2)
    rng = np.random.default_rng(n)
    p.mlp.params["w3"][:] = rng.normal(0, 0.1, p.mlp.params["w3"].shape)
    X = rng.normal(0, 1, (n, PREDICT_FEATURES)).astype(np.float32)
    out = p._forward(X)
    assert out.shape == (n, 2)
    assert np.allclose(out, p.mlp.forward(X), atol=1e-5)


def test_kernel_failure_raises(monkeypatch):
    """A failing predictor kernel raises; the predictor never drops to
    numpy behind the caller's back."""
    import repro.kernels.ops as ops

    def boom(*_a, **_k):
        raise RuntimeError("kernel failed")

    monkeypatch.setattr(ops, "predict_mlp", boom)
    p = RuntimePredictor(use_kernel=True)
    with pytest.raises(RuntimeError, match="kernel failed"):
        p.predict_quantiles([mk(0, 2)])
    assert p.use_kernel


def test_context_features_shape():
    eng = SchedulerEngine(make_cluster("helios"), _est_pri(),
                          allocator="pack")
    p = RuntimePredictor(assist=True)
    p.bind(eng)
    ctx = p._context(eng)
    assert ctx.shape == (len(CONTEXT_NAMES),)
    assert np.isfinite(ctx).all()
    assert PREDICT_FEATURES == 17 + len(CONTEXT_NAMES)


# --------------------------------------------------- reservations / overrun --


def _tiny_spec():
    return ClusterSpec(nodes=[NodeSpec(0, "V100", 8, 64, 512.0, 1.0)],
                       name="tiny")


def test_backfill_overrun_preempts_and_bars_offender():
    """A backfilled job blowing its p90 reservation is checkpoint-preempted
    (grace elapsed, head job waiting) and barred from further predictor-
    gated backfill; the head job then starts on the freed GPUs."""
    p = RuntimePredictor(assist=True, overrun=OverrunPolicy(grace_s=60.0))
    eng = SchedulerEngine(_tiny_spec(), _est_pri(), allocator="pack",
                          hooks=(p,), predictor=p)
    j1 = mk(1, 4, runtime=5000.0, submit=0.0)
    j2 = mk(2, 8, runtime=100.0, submit=10.0)          # head, blocked
    j3 = mk(3, 4, runtime=20000.0, est=100.0, submit=20.0)  # liar, backfills
    j4 = mk(4, 1, runtime=50.0, submit=6000.0)         # wakes the engine
    eng.submit([j1, j2, j3, j4])
    eng.drain()
    assert eng.bf_reservations >= 1
    assert eng.bf_overruns == 1
    assert 3 in eng._bf_overrun_jobs
    done = {j.job_id: j for j in eng.completed}
    assert set(done) == {1, 2, 3, 4}
    assert done[3].restarts >= 1                        # evicted, resumed
    # the overrun must not starve the head job until the liar finishes
    assert done[2].first_start_time < 20000.0


def test_reservation_cleared_on_normal_finish():
    """A backfilled job finishing inside its reservation leaves no deadline
    behind and counts no overrun."""
    p = RuntimePredictor(assist=True)
    eng = SchedulerEngine(_tiny_spec(), _est_pri(), allocator="pack",
                          hooks=(p,), predictor=p)
    eng.submit([mk(1, 4, runtime=5000.0, submit=0.0),
                mk(2, 8, runtime=100.0, submit=10.0),
                mk(3, 4, runtime=80.0, est=100.0, submit=20.0)])
    eng.drain()
    assert eng.bf_reservations == 1
    assert eng.bf_overruns == 0
    assert not eng._bf_deadlines
    assert p.reservations == 1
    slacks, cur = p.recent_slacks(0)
    assert cur == 1 and len(slacks) == 1 and slacks[0] >= 0.0


def test_trained_predictor_blocks_known_liar_backfill():
    """After training on a lying cohort, the p90 gate must refuse the
    backfill the declared estimate would have taken."""
    p = RuntimePredictor(assist=True)
    # teach it: user 7's jobs declare 100 but run 20000
    for k in range(300):
        j = mk(1000 + k, 4, runtime=20000.0, est=100.0, user=7)
        p.on_submit(j, 0.0)
        p.on_finish(j, 20000.0)
    eng = SchedulerEngine(_tiny_spec(), _est_pri(), allocator="pack",
                          hooks=(p,), predictor=p)
    p.bind(eng)
    eng.submit([mk(1, 4, runtime=5000.0, submit=0.0),
                mk(2, 8, runtime=100.0, submit=10.0),
                mk(3, 4, runtime=20000.0, est=100.0, submit=20.0, user=7)])
    eng.drain()
    assert eng.bf_overruns == 0                 # never backfilled -> no blow
    done = {j.job_id: j for j in eng.completed}
    assert done[2].first_start_time <= 5000.0 + 1e-6


# ----------------------------------------------------------- MILP durations --


def test_lookahead_weights_clamped_and_none_passthrough():
    assert _lookahead_weights([], None) is None
    assert _lookahead_weights([mk(1, 2)], None) is None
    w = _lookahead_weights([mk(1, 2), mk(2, 2), mk(3, 2)],
                           [60.0, 3600.0, 1e9])
    assert w == [0.1, 1.0, 8.0]
    # durations shorter than the lookahead pad with the 1h declared default
    w2 = _lookahead_weights([mk(1, 2), mk(2, 2)], [7200.0])
    assert w2 == [2.0, 1.0]


def test_choose_allocation_durations_none_bit_identical():
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    look = [mk(10, 2), mk(11, 8), mk(12, 1)]
    a = choose_allocation(c, j, ways, look, solution_cache=False)
    b = choose_allocation(c, j, ways, look, solution_cache=False,
                          durations=None)
    assert a.placement == b.placement and a.way_index == b.way_index
    assert a.objective == b.objective


def test_choose_allocation_durations_reweight_objective():
    """Long predicted durations upweight a lookahead job's term; the solve
    stays feasible and the cache keys the two variants apart."""
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    look = [mk(10, 2), mk(11, 8)]
    base = choose_allocation(c, j, ways, look)
    wtd = choose_allocation(c, j, ways, look,
                            durations=[8 * 3600.0, 60.0])
    assert wtd.placement in [w for w in ways]
    # same cluster version: both results must have come from distinct
    # cache entries, not one clobbering the other
    again = choose_allocation(c, j, ways, look)
    assert again.objective == base.objective


# ------------------------------------------------------ autoscaler forecast --


def test_autoscaler_forecast_none_without_assist():
    from repro.scale import QueuePressureAutoscaler, pools_from_spec
    spec = make_cluster("helios")
    asc = QueuePressureAutoscaler(pools_from_spec(spec))
    eng = SchedulerEngine(spec, _est_pri(), allocator="pack")
    assert asc._forecast_gpu_hours(eng) is None
    shadow = RuntimePredictor(assist=False)
    eng2 = SchedulerEngine(spec, _est_pri(), allocator="pack",
                           predictor=shadow)
    assert asc._forecast_gpu_hours(eng2) is None


def test_autoscaler_forecast_triggers_scale_up():
    from repro.scale import QueuePressureAutoscaler, pools_from_spec
    spec = make_cluster("helios")
    asc = QueuePressureAutoscaler(pools_from_spec(spec, max_frac=2.0),
                                  forecast_up_gpu_hours=4.0)
    pred = RuntimePredictor(assist=True)
    eng = SchedulerEngine(spec, _est_pri(), allocator="pack",
                          hooks=(pred,), predictor=pred)
    # saturate, then stack a predicted backlog the wait-p99 has not seen
    eng.submit([mk(1, 80, runtime=40000.0, submit=0.0)]
               + [mk(10 + i, 8, runtime=7200.0, submit=1.0)
                  for i in range(6)])
    eng.step(2.0)
    fc = asc._forecast_gpu_hours(eng)
    assert fc is not None and fc > 4.0
    direction, reason = asc.desired_direction(eng, 2.0, None)
    assert direction == 1 and "forecast" in reason


def test_target_util_forecast_holds_scale_down():
    from repro.scale import TargetUtilizationAutoscaler, pools_from_spec
    spec = make_cluster("helios")
    asc = TargetUtilizationAutoscaler(pools_from_spec(spec),
                                      max_pending_for_down=64,
                                      forecast_hold_gpu_hours=2.0)
    pred = RuntimePredictor(assist=True)
    eng = SchedulerEngine(spec, _est_pri(), allocator="pack",
                          hooks=(pred,), predictor=pred)
    # idle cluster (util 0 < util_low) but a fat predicted backlog
    eng.submit([mk(10 + i, 100, runtime=7200.0, submit=0.0)
                for i in range(4)])
    eng.step(1.0)
    direction, reason = asc.desired_direction(eng, 1.0, None)
    assert direction == 0 and "hold" in reason


# ----------------------------------------------------------------- failover --


def test_failover_roundtrip_preserves_predictor():
    from repro.core.trace import generate_trace
    p = RuntimePredictor(assist=True, seed=0)
    eng = SchedulerEngine(make_cluster("helios"), _est_pri(),
                          allocator="pack", hooks=(p,), predictor=p)
    jobs = generate_trace("helios", 60, seed=5)
    eng.submit(jobs)
    eng.step(jobs[30].submit_time)
    blob = eng.save_state()
    eng2 = SchedulerEngine.load_state(blob)
    assert eng2.predictor is not None
    assert eng2.predictor.engine is eng2         # rebound, not pickled ref
    assert eng2.predictor in eng2.hooks          # training resumes
    eng.drain()
    eng2.drain()
    assert _signature(eng) == _signature(eng2)
    assert eng.predictor.train_steps == eng2.predictor.train_steps


# ------------------------------------------------------------- bit-identity --


@pytest.mark.parametrize("scenario", sorted(list_scenarios()))
def test_shadow_predictor_is_bit_identical_per_scenario(scenario):
    """assist=False trains from the hook stream but must never steer: job
    tuples and every decision/backfill counter match predictor=None."""
    base = run_scenario(scenario, num_jobs=90, seed=1)
    shadow = RuntimePredictor(assist=False, seed=0)
    got = run_scenario(scenario, num_jobs=90, seed=1, predictor=shadow)
    assert _signature(got.engine) == _signature(base.engine)
    assert got.engine.bf_reservations == 0
    assert got.engine.bf_overruns == 0
    assert shadow.train_steps == len(got.batch.jobs)  # it did observe


def test_shadow_predictor_is_bit_identical_federation():
    from repro.fed import run_fleet

    def sig(res):
        jobs = tuple(sorted(
            (j.job_id, round(j.submit_time, 6),
             round(j.first_start_time if j.first_start_time is not None
                   else -1, 6),
             round(j.finish_time if j.finish_time is not None else -1, 6),
             j.restarts) for j in res.result.jobs))
        return jobs, tuple((e.decisions, e.milp_calls, e.backfills,
                            e.bf_reservations, e.bf_overruns)
                           for e in res.fed.engines)

    base = sig(run_fleet("fleet-skewed-flash", num_jobs=120, seed=3))
    got = run_fleet("fleet-skewed-flash", num_jobs=120, seed=3,
                    predictor_factory=lambda i, spec:
                    RuntimePredictor(assist=False, seed=i))
    assert sig(got) == base


def test_assisted_run_changes_backfill_and_reports_metrics():
    """Assist mode must actually engage on a congested scenario: committed
    reservations, telemetry mirrors, and obs metrics all light up."""
    from repro.obs import Observability
    pred = RuntimePredictor(assist=True, seed=0)
    obs = Observability(name="predict-test")
    sr = run_scenario("flash-crowd", num_jobs=200, seed=1, allocator="pack",
                      prioritizer=_est_pri(), predictor=pred, obs=obs)
    assert sr.engine.bf_reservations > 0
    assert pred.train_steps == len(sr.batch.jobs)
    last = sr.telemetry.samples[-1]
    assert last.bf_reservations == sr.engine.bf_reservations
    assert last.bf_overruns == sr.engine.bf_overruns
    assert 0.0 <= last.bf_overrun_ratio <= 1.0
    assert last.prediction_mape > 0.0
    text = obs.prometheus()
    assert "repro_prediction_mape" in text
    assert "repro_predicted_backfills_total" in text
    assert "repro_reservation_slack_seconds" in text


def test_overrun_ratio_zero_division_safe():
    from repro.sched.telemetry import TelemetrySample
    s = TelemetrySample(time=0.0, window=1.0, finished_in_window=0,
                        throughput_jph=0.0, jct_p50=0.0, jct_p95=0.0,
                        jct_p99=0.0, wait_p50=0.0, wait_p95=0.0,
                        wait_p99=0.0, utilization=0.0, queue_len=0,
                        running=0, requeues=0, vc_fairness=1.0)
    assert s.bf_overrun_ratio == 0.0


# -------------------------------------------------------- scenario registry --


def test_mispredict_storm_registered_and_lying():
    run = get_scenario("mispredict-storm").build(300, 0)
    ratios = np.array([j.est_runtime / max(j.runtime, 1e-9)
                       for j in run.jobs])
    liars = (ratios < 0.5).mean()
    assert 0.1 < liars < 0.5                     # ~30% of users lowball
    assert "mispredict-storm" in list_scenarios()
