"""Tests for repro.obs: MultiHooks fan-out, span tracer, metrics registry,
decision audit log, report CLI, and the obs-off bit-identity guarantee."""
import io
import json
import math

import pytest

from repro.core import PolicyPrioritizer, make_policy
from repro.obs import (DecisionAuditLog, EngineMetricsHook, MetricsRegistry,
                       Observability, SpanTracer, merge_documents, span,
                       validate_trace)
from repro.obs.report import analyze, main as report_main, print_report
from repro.sched import (EngineHooks, MultiHooks, SchedulerEngine,
                         get_scenario, list_scenarios, run_scenario,
                         run_stream)


def _make_engine(spec, policy="fcfs", **kw):
    return SchedulerEngine(spec, PolicyPrioritizer(make_policy(policy)), **kw)


def _signature(engine):
    jobs = tuple(sorted(
        (j.job_id, round(j.submit_time, 6),
         round(j.first_start_time if j.first_start_time is not None else -1, 6),
         round(j.finish_time if j.finish_time is not None else -1, 6),
         j.restarts)
        for j in engine.completed))
    return jobs, (engine.decisions, engine.milp_calls, engine.backfills,
                  engine.restarts)


def _drain_scenario(scenario, n, seed, hooks=()):
    run = get_scenario(scenario).build(n, seed)
    eng = _make_engine(run.spec, allocator="pack",
                       fault_model=run.fault_model, hooks=hooks)
    eng.submit([j.clone_pending() for j in run.jobs])
    eng.drain()
    return eng


# --------------------------------------------------------------- MultiHooks --


class _Recorder(EngineHooks):
    def __init__(self, tag, log):
        self.tag = tag
        self.log = log

    def on_submit(self, job, now):
        self.log.append((self.tag, "submit", job.job_id))

    def on_start(self, job, now):
        self.log.append((self.tag, "start", job.job_id))


class _Exploder(EngineHooks):
    def on_start(self, job, now):
        raise RuntimeError("observer bug")


def test_multihooks_preserves_child_order():
    log = []
    mh = MultiHooks(_Recorder("a", log), _Recorder("b", log))

    class _J:
        job_id = 7
    mh.on_submit(_J(), 0.0)
    assert log == [("a", "submit", 7), ("b", "submit", 7)]


def test_multihooks_skips_inherited_noops_and_wants():
    log = []
    mh = MultiHooks(_Recorder("a", log))
    assert mh.wants("on_submit") and mh.wants("on_start")
    # _Recorder only overrides on_submit/on_start — the rest stay no-ops
    assert not mh.wants("on_finish")
    assert not mh.wants("on_decision_audit")
    # nested MultiHooks delegate through wants()
    outer = MultiHooks(mh)
    assert outer.wants("on_submit") and not outer.wants("on_finish")


def test_multihooks_accepts_duck_typed_partial_hooks():
    """A plain object with one hook method — no EngineHooks subclassing —
    still receives its events through the fan-out."""
    seen = []

    class _Partial:
        def on_finish(self, job, now):
            seen.append(job.job_id)

    mh = MultiHooks(_Partial())
    assert mh.wants("on_finish") and not mh.wants("on_submit")

    class _J:
        job_id = 3
    mh.on_finish(_J(), 1.0)
    assert seen == [3]


def test_multihooks_isolates_raising_child():
    log = []
    mh = MultiHooks(_Recorder("a", log), _Exploder(), _Recorder("b", log))

    class _J:
        job_id = 1
    mh.on_start(_J(), 0.0)
    # both healthy children ran despite the middle one raising
    assert log == [("a", "start", 1), ("b", "start", 1)]
    assert mh.error_counts == {"on_start:RuntimeError": 1}
    assert len(mh.errors) == 1


def test_multihooks_error_recording_is_capped():
    mh = MultiHooks(_Exploder())

    class _J:
        job_id = 1
    for _ in range(MultiHooks.MAX_RECORDED_ERRORS + 25):
        mh.on_start(_J(), 0.0)
    assert len(mh.errors) == MultiHooks.MAX_RECORDED_ERRORS
    cap = MultiHooks.MAX_RECORDED_ERRORS + 25
    assert mh.error_counts["on_start:RuntimeError"] == cap


def test_raising_hook_does_not_corrupt_engine_state():
    """State-machine invariant pin: a user hook raising on every on_start
    must leave the schedule itself untouched — same completions, same
    counters as a hook-free run, and no job stuck in a half-started state."""
    from repro.core.types import JobState
    bare = _drain_scenario("steady", 80, 0)
    mh = MultiHooks(_Exploder())
    observed = _drain_scenario("steady", 80, 0, hooks=(mh,))
    assert _signature(observed) == _signature(bare)
    assert mh.error_counts["on_start:RuntimeError"] > 0
    assert not observed.pending and not observed.running
    assert all(j.state == JobState.COMPLETED for j in observed.completed)


def test_service_forwards_full_surface_to_partial_hook():
    """run_stream composes user hooks via MultiHooks: a duck-typed partial
    observer sees lifecycle events without subclassing EngineHooks."""
    run = get_scenario("steady").build(60, 0)

    class _Counts:
        def __init__(self):
            self.submits = 0
            self.finishes = 0

        def on_submit(self, job, now):
            self.submits += 1

        def on_finish(self, job, now):
            self.finishes += 1

    c = _Counts()
    res = run_stream(run.spec, [j.clone_pending() for j in run.jobs],
                     PolicyPrioritizer(make_policy("fcfs")),
                     allocator="pack", fault_model=run.fault_model,
                     hooks=(c,))
    assert c.submits == 60
    assert c.finishes == len(res.engine.completed) == 60


# ------------------------------------------------------------------ metrics --


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help", cluster="a")
    c.inc()
    c.inc(2.5)
    assert reg.value("repro_test_total", cluster="a") == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("repro_test_gauge", "help")
    g.set(4)
    g.dec(1.5)
    assert reg.value("repro_test_gauge") == 2.5
    h = reg.histogram("repro_test_seconds", "help", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 3 and h.sum == 55.5
    # cumulative() excludes +Inf; the overflow shows up via count
    assert h.cumulative() == [1, 2]
    assert h.quantile(0.5) == 10.0 and h.quantile(1.0) == math.inf


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("repro_x_total", "h", path="milp")
    assert reg.counter("repro_x_total", "h", path="milp") is a
    b = reg.counter("repro_x_total", "h", path="greedy")
    assert b is not a
    with pytest.raises(ValueError):
        reg.gauge("repro_x_total", "h")


def test_prometheus_render_format():
    reg = MetricsRegistry()
    reg.counter("repro_jobs_total", "jobs seen", cluster='he"l\\o\n').inc(2)
    reg.histogram("repro_lat_seconds", "latency", buckets=(0.1, 1.0)) \
        .observe(0.5)
    text = reg.render()
    assert "# HELP repro_jobs_total jobs seen\n" in text
    assert "# TYPE repro_jobs_total counter\n" in text
    # label values escape backslash, quote, and newline
    assert 'cluster="he\\"l\\\\o\\n"' in text
    assert 'repro_lat_seconds_bucket{le="0.1"} 0\n' in text
    assert 'repro_lat_seconds_bucket{le="1"} 1\n' in text
    assert 'repro_lat_seconds_bucket{le="+Inf"} 1\n' in text
    assert "repro_lat_seconds_sum 0.5\n" in text
    assert "repro_lat_seconds_count 1\n" in text
    # ends with exactly one trailing newline
    assert text.endswith("\n") and not text.endswith("\n\n")


def test_registry_merge_sums_everything():
    def mk(n):
        reg = MetricsRegistry()
        reg.counter("repro_c_total", "h", cluster=n).inc(1)
        reg.gauge("repro_q", "h").set(2)
        reg.histogram("repro_h_seconds", "h", buckets=(1.0,)).observe(0.5)
        return reg

    merged = MetricsRegistry.merged([mk("a"), mk("b")])
    assert merged.value("repro_c_total", cluster="a") == 1
    assert merged.value("repro_c_total", cluster="b") == 1
    # gauges sum across members: fleet queue lengths are additive
    assert merged.value("repro_q") == 4
    fam = merged.as_dict()["repro_h_seconds"]
    series = next(iter(fam["series"].values()))
    assert series["count"] == 2 and series["sum"] == 1.0


def test_histogram_merge_rejects_mismatched_buckets():
    r1 = MetricsRegistry()
    r1.histogram("repro_h_seconds", "h", buckets=(1.0,))
    r2 = MetricsRegistry()
    r2.histogram("repro_h_seconds", "h", buckets=(2.0,))
    with pytest.raises(ValueError):
        r1.merge(r2)


def test_engine_metrics_hook_on_real_run():
    reg = MetricsRegistry()
    hook = EngineMetricsHook(reg, cluster="t")
    eng = _drain_scenario("steady", 60, 0, hooks=(hook,))
    assert reg.value("repro_jobs_submitted_total", cluster="t") == 60
    assert reg.value("repro_jobs_finished_total", cluster="t") == 60
    assert reg.value("repro_decisions_total", cluster="t") == eng.decisions
    text = reg.render()
    assert "repro_job_wait_seconds_bucket" in text


# ------------------------------------------------------------------- tracer --


def test_tracer_span_model_and_validation():
    obs = Observability(name="t", metrics=False, audit=False)
    res = run_scenario("steady", num_jobs=40, seed=0, obs=obs)
    doc = obs.trace_document()
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    queued = [e for e in evs if e.get("name") == "queued" and e["ph"] == "X"]
    running = [e for e in evs if e.get("name") == "running" and e["ph"] == "X"]
    finishes = [e for e in evs if e.get("name") == "finish"]
    assert len(queued) >= 40 and len(running) >= 40 and len(finishes) == 40
    assert all(e["dur"] >= 0 for e in queued + running)
    # control-plane spans live on their own pid, in wall-clock time
    ctl = [e for e in evs if e.get("cat") == "control"]
    assert ctl and all(e["pid"] != queued[0]["pid"] for e in ctl)
    assert res.obs is obs


def test_tracer_preempt_and_fault_instants():
    obs = Observability(name="t", metrics=False, audit=False)
    run_scenario("fault-storm", num_jobs=60, seed=2, obs=obs)
    evs = obs.trace_document()["traceEvents"]
    evicted = [e for e in evs if e.get("name") == "running"
               and e.get("args", {}).get("evicted")]
    assert evicted, "fault kills must close running spans as evicted"
    assert validate_trace(obs.trace_document()) == []


def test_tracer_finalize_closes_open_spans():
    tracer = SpanTracer(name="x")

    class _J:
        job_id = 1
        num_gpus = 2
        restarts = 0
    tracer.on_submit(_J(), 100.0)
    tracer.finalize(200.0)
    doc = tracer.to_document()
    assert validate_trace(doc) == []
    open_spans = [e for e in doc["traceEvents"]
                  if e.get("args", {}).get("open_at_end")]
    assert len(open_spans) == 1 and open_spans[0]["name"] == "queued"
    # finalize is idempotent
    tracer.finalize(300.0)
    assert len(tracer.to_document()["traceEvents"]) \
        == len(doc["traceEvents"])


def test_tracer_caps_events_and_counts_drops():
    tracer = SpanTracer(name="x", max_events=4)

    class _J:
        num_gpus = 1
        restarts = 0
    for i in range(10):
        j = _J()
        j.job_id = i
        tracer.on_submit(j, float(i))
        tracer.on_start(j, float(i) + 1.0)   # emits the queued span
    doc = tracer.to_document()
    assert len(doc["traceEvents"]) <= 4 + 2   # + process metadata events
    assert doc["otherData"]["dropped_events"] > 0
    assert validate_trace(doc) == []


def test_validate_trace_flags_malformed_documents():
    assert validate_trace({"no": "events"})
    assert validate_trace({"traceEvents": [{"ph": "X"}]})
    assert validate_trace(
        {"traceEvents": [{"name": "a", "ph": "Z", "ts": 0,
                          "pid": 1, "tid": 1}]})
    assert validate_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "ts": -5.0,
                          "pid": 1, "tid": 1, "dur": 1}]})
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                           "pid": 1, "tid": 1, "dur": 2.0}]}
    assert validate_trace(ok) == []


def test_merge_documents_concatenates_and_sums():
    t1 = SpanTracer(name="a", member=0)
    t2 = SpanTracer(name="b", member=1)

    class _J:
        job_id = 1
        num_gpus = 1
        restarts = 0
    t1.on_submit(_J(), 0.0)
    t2.on_submit(_J(), 0.0)
    t1.finalize(10.0)
    t2.finalize(10.0)
    merged = merge_documents([t1.to_document(), t2.to_document()])
    assert validate_trace(merged) == []
    pids = {e["pid"] for e in merged["traceEvents"] if e.get("cat") == "job"}
    assert len(pids) == 2
    assert set(merged["otherData"]["sim_t0"]) == {str(p) for p in pids}


# -------------------------------------------------------------------- audit --


def test_audit_log_aggregates_real_run():
    obs = Observability(name="t", trace=False, metrics=False)
    res = run_scenario("flash-crowd", num_jobs=120, seed=0, obs=obs)
    log = obs.audit
    assert log.decisions == res.engine.decisions
    s = log.summary()
    assert s["decisions"] == log.decisions
    assert sum(s["path_counts"].values()) == s["decisions"]
    assert s["alloc_counts"].get("heuristic", 0) \
        + s["alloc_counts"].get("milp", 0) \
        + s["alloc_counts"].get("greedy-fallback", 0) \
        + s["alloc_counts"].get("none", 0) == s["decisions"]
    assert json.dumps(s)   # JSON-serializable by contract


def test_audit_records_fcfs_degraded_path():
    from repro.chaos import DegradationPolicy
    run = get_scenario("chaos-storm").build(100, 0)
    log = DecisionAuditLog()
    eng = SchedulerEngine(
        run.spec, PolicyPrioritizer(make_policy("fcfs")), allocator="milp",
        fault_model=run.fault_model, hooks=(log,),
        degradation=DegradationPolicy(window_deadline_s=0.0,
                                      fcfs_windows=2))
    eng.submit([j.clone_pending() for j in run.jobs])
    eng.drain()
    assert eng.degraded_windows > 0
    assert log.path_counts.get("fcfs-degraded", 0) > 0
    assert log.summary()["path_counts"]["fcfs-degraded"] > 0


def test_audit_ring_truncates_but_counters_do_not():
    log = DecisionAuditLog(keep=5)
    for i in range(12):
        log.on_decision_audit(
            {"now": float(i), "path": "policy", "window": 1,
             "rank_wall_s": 0.001, "top_job": i, "placed": True,
             "alloc": "heuristic", "skips": {"head-no-placement": 1},
             "backfills": 0})
    assert len(log.records) == 5
    assert log.decisions == 12
    assert log.skip_counts["head-no-placement"] == 12


# ------------------------------------------------------------------- report --


def test_report_cli_validates_and_prints(tmp_path, capsys):
    obs = Observability(name="t")
    run_scenario("flash-crowd", num_jobs=100, seed=0, obs=obs)
    path = tmp_path / "trace.json"
    obs.export_trace(str(path))
    rc = report_main([str(path), "--validate", "--top", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "trace OK" in out
    assert "critical path" in out
    assert "decision paths" in out
    assert "top queueing causes" in out


def test_report_cli_rejects_corrupt_and_invalid(tmp_path, capsys):
    missing = tmp_path / "nope.json"
    assert report_main([str(missing)]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert report_main([str(bad), "--validate"]) == 1
    err = capsys.readouterr().err
    assert "schema violation" in err


def test_report_analyze_matches_audit_counts(tmp_path):
    obs = Observability(name="t")
    res = run_scenario("flash-crowd", num_jobs=100, seed=0, obs=obs)
    model = analyze(obs.trace_document())
    assert sum(model["path_counts"].values()) == res.engine.decisions
    assert model["blocked_windows"] == obs.audit.blocked_windows
    buf = io.StringIO()
    print_report(obs.trace_document(), top=3, out=buf)
    assert "critical path" in buf.getvalue()


# ------------------------------------------------------------- bit-identity --


@pytest.mark.parametrize("scenario", sorted(list_scenarios()))
def test_obs_off_is_bit_identical_per_scenario(scenario):
    """The full bundle (trace + metrics + audit) must observe, never steer:
    job tuples and decision counters match an unobserved run exactly."""
    base = run_scenario(scenario, num_jobs=90, seed=1)
    obs = Observability(name=scenario)
    got = run_scenario(scenario, num_jobs=90, seed=1, obs=obs)
    assert _signature(got.engine) == _signature(base.engine)
    assert validate_trace(obs.trace_document()) == []


def test_obs_off_is_bit_identical_federation():
    from repro.fed import run_fleet
    def sig(res):
        jobs = tuple(sorted(
            (j.job_id, round(j.submit_time, 6),
             round(j.first_start_time if j.first_start_time is not None
                   else -1, 6),
             round(j.finish_time if j.finish_time is not None else -1, 6),
             j.restarts) for j in res.result.jobs))
        return jobs, tuple((e.decisions, e.milp_calls, e.backfills)
                           for e in res.fed.engines)

    base = sig(run_fleet("fleet-skewed-flash", num_jobs=120, seed=3))
    obs = Observability(name="fleet")
    got = run_fleet("fleet-skewed-flash", num_jobs=120, seed=3, obs=obs)
    assert sig(got) == base
    doc = obs.trace_document()
    assert validate_trace(doc) == []
    # one job pid per member plus the fleet's own — distinct trace rows
    jp = {e["pid"] for e in doc["traceEvents"] if e.get("cat") == "job"}
    assert len(jp) >= 3
    assert "repro_fed_routed_total" in obs.prometheus()
    assert set(got.obs.audit_summary()["members"]) \
        == {"helios-large", "helios-mid", "helios-small"}


# --------------------------------------------------------------- engine API --


def test_add_hook_rebuilds_gated_dispatch():
    run = get_scenario("steady").build(30, 0)
    eng = _make_engine(run.spec, allocator="pack")
    assert eng._audit_obs == [] and eng._alloc_obs == []
    log = DecisionAuditLog()
    eng.add_hook(log)
    assert log in eng._audit_obs
    eng.submit([j.clone_pending() for j in run.jobs])
    eng.drain()
    assert log.decisions == eng.decisions


def test_save_load_state_rebuilds_obs_dispatch():
    # flash-crowd saturates the cluster: jobs are still pending at the
    # snapshot, so the restored engine must make fresh audited decisions
    run = get_scenario("flash-crowd").build(120, 0)
    obs = Observability(name="t", trace=False, metrics=False)
    eng = _make_engine(run.spec, allocator="pack", hooks=obs.hooks())
    jobs = [j.clone_pending() for j in run.jobs]
    eng.submit(jobs)
    eng.step(jobs[0].submit_time + 3600.0)
    blob = eng.save_state()
    restored = SchedulerEngine.load_state(blob)
    # hooks are deliberately dropped on restore; dispatch lists match
    assert restored._audit_obs == []
    log = DecisionAuditLog()
    restored.add_hook(log)
    assert log in restored._audit_obs
    restored.drain()
    eng.drain()
    assert _signature(restored) == _signature(eng)
    assert log.decisions > 0


def test_observability_finalize_idempotent_and_exports(tmp_path):
    obs = Observability(name="t")
    run_scenario("steady", num_jobs=40, seed=0, obs=obs)
    n = len(obs.trace_document()["traceEvents"])
    obs.finalize(None)
    assert len(obs.trace_document()["traceEvents"]) == n
    prom = tmp_path / "m.prom"
    obs.write_prometheus(str(prom))
    assert "repro_jobs_submitted_total" in prom.read_text()
    tr = tmp_path / "t.json"
    obs.export_trace(str(tr))
    assert validate_trace(json.loads(tr.read_text())) == []


def test_observability_switches_disable_components():
    obs = Observability(trace=False, metrics=False, audit=False)
    assert obs.hooks() == ()
    assert obs.tracer is None and obs.metrics_hook is None \
        and obs.audit is None
    run_scenario("steady", num_jobs=20, seed=0, obs=obs)
    assert obs.trace_document()["traceEvents"] == []


def test_controller_ticks_recorded_in_metrics():
    obs = Observability(name="t", trace=False, audit=False)
    run_scenario("chaos-storm", num_jobs=80, seed=0, obs=obs)
    reg = obs.merged_registry()
    assert reg.value("repro_controller_ticks_total",
                     cluster="t", controller="chaos") > 0
    assert reg.value("repro_rescan_windows_total", cluster="t") > 0


def test_fleet_window_note_requires_no_nan():
    obs = Observability(name="f")
    obs.note_window()
    with obs.recording(), span("autoscaler", sim_t=60.0):
        obs.note_controller("autoscaler", 2)
    assert validate_trace(obs.trace_document()) == []
    assert math.isfinite(
        obs.merged_registry().value("repro_rescan_windows_total"))
