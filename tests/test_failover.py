"""Control-plane failover tests: ``SchedulerEngine.save_state`` /
``load_state`` must restore a mid-stream engine **bit-identically** — a
restored run finishes exactly like a run that never crashed, on every
registered scenario, at arbitrary cut points, through the MILP path, the
degradation ladder, and mid-flight preemptions."""
import math

import pytest
from conftest import hypothesis_or_stubs

from repro.chaos import DegradationPolicy
from repro.core import PolicyPrioritizer, make_policy
from repro.lifecycle import CkptCostModel
from repro.sched import (QuotaPrioritizer, RollingTelemetry, SchedulerEngine,
                         get_scenario, list_scenarios, wrap_tenancy)

given, settings, st = hypothesis_or_stubs()


def fresh_engine(run, *, allocator="pack", degradation=None):
    """A drain-mode engine wired exactly like the service loop wires one
    (tenancy wrap + incremental quota hook)."""
    pri = wrap_tenancy(PolicyPrioritizer(make_policy("fcfs")),
                       run.sla_users, run.vc_quotas)
    hooks = (pri,) if isinstance(pri, QuotaPrioritizer) else ()
    eng = SchedulerEngine(run.spec, pri, allocator=allocator,
                          fault_model=run.fault_model, hooks=hooks,
                          degradation=degradation)
    eng.submit([j.clone_pending() for j in run.jobs])
    return eng


def fingerprint(eng):
    jobs = sorted((j.job_id, j.start_time, j.finish_time, j.num_gpus,
                   j.restarts) for j in eng.completed)
    return (jobs, eng.decisions, eng.backfills, eng.milp_calls,
            eng.restarts, eng.preemptions, eng.now)


def roundtrip_equals_straight(name, cut, *, num_jobs=60, allocator="pack",
                              degradation=None):
    straight = fresh_engine(get_scenario(name).build(num_jobs, 0),
                            allocator=allocator, degradation=degradation)
    straight.drain()

    crashed = fresh_engine(get_scenario(name).build(num_jobs, 0),
                           allocator=allocator, degradation=degradation)
    crashed.step(math.inf, max_events=cut)
    blob = crashed.save_state()
    del crashed                                   # the control plane died
    restored = SchedulerEngine.load_state(blob)
    restored.drain()
    assert fingerprint(restored) == fingerprint(straight), (name, cut)


@pytest.mark.parametrize("name", sorted(list_scenarios()))
def test_roundtrip_matches_uninterrupted_run(name):
    for cut in (1, 7, 23):
        roundtrip_equals_straight(name, cut)


def test_roundtrip_through_milp_allocator():
    roundtrip_equals_straight("steady", 11, allocator="milp")


def test_roundtrip_with_degradation_ladder_engaged():
    deg = DegradationPolicy(milp_budget_s=0.0, trip_after=1,
                            reset_after_decisions=8, window_deadline_s=0.0)
    roundtrip_equals_straight("steady", 17, allocator="milp",
                              degradation=deg)


def test_roundtrip_preserves_degradation_counters():
    deg = DegradationPolicy(milp_budget_s=0.0, trip_after=1,
                            reset_after_decisions=8)
    eng = fresh_engine(get_scenario("steady").build(40, 0),
                       allocator="milp", degradation=deg)
    eng.step(math.inf, max_events=40)
    restored = SchedulerEngine.load_state(eng.save_state())
    assert restored.milp_fallbacks == eng.milp_fallbacks
    assert restored.degradation == deg
    assert restored._deg_fallback_open == eng._deg_fallback_open
    restored.drain()
    assert restored.done and restored.milp_fallbacks > 0


def test_roundtrip_after_midstream_preemption():
    def run_one(save_after_preempt):
        eng = fresh_engine(get_scenario("steady").build(40, 0))
        eng.step(600.0)
        victim = next(iter(eng.running), None)
        if victim is not None:
            eng.preempt_job(victim, CkptCostModel(ckpt_interval=1800.0,
                                                  restore_s=120.0))
            eng.reschedule(at=eng.now)
        if save_after_preempt:
            eng = SchedulerEngine.load_state(eng.save_state())
        eng.drain()
        return fingerprint(eng)

    assert run_one(True) == run_one(False)


def test_save_state_does_not_disturb_live_engine():
    """Taking a snapshot mid-stream (detaching the prioritizer back-ref)
    must leave the live engine able to continue bit-identically."""
    straight = fresh_engine(get_scenario("multi-tenant").build(50, 0))
    straight.drain()
    live = fresh_engine(get_scenario("multi-tenant").build(50, 0))
    live.step(math.inf, max_events=13)
    live.save_state()                              # snapshot, then carry on
    assert getattr(live.prioritizer, "engine", live) is live
    live.drain()
    assert fingerprint(live) == fingerprint(straight)


def test_load_state_reattaches_fresh_hooks():
    eng = fresh_engine(get_scenario("steady").build(30, 0))
    eng.step(math.inf, max_events=9)
    tel = RollingTelemetry(window=6 * 3600.0, sample_interval=600.0)
    restored = SchedulerEngine.load_state(eng.save_state(), hooks=[tel])
    assert tel in restored.hooks
    restored.drain()
    assert restored.done
    assert tel._last_t is not None                 # the observer saw ticks


@given(cut=st.integers(min_value=0, max_value=400))
@settings(max_examples=20, deadline=None)
def test_roundtrip_fuzzed_cut_points(cut):
    """The restore point must be unobservable wherever the crash lands —
    before the first decision, mid-backfill, past the last event."""
    roundtrip_equals_straight("flash-crowd", cut, num_jobs=40)
