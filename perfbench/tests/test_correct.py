"""``correct`` at a size a CPU test run can hold: a sound run passes, the
lower-precision control put in the program's place fails, and a run with
the timed path broken underneath fails, once for each fault the cells can
have (the device check of the harness is skipped with ``--rehearse``)."""
import argparse
import importlib.util
import json
import os
import pickle

import numpy as np
import pytest

import recorders
import reference
from conftest import BENCH

SMALL = ('{"traffic": {"num_jobs": 1500, "crowd": {"jobs": 700}, '
         '"window_rows": [300, 300], "reference_decisions": 30}, '
         '"config": {"scheduler": {"queue_window": 300}}}')
#: SMALL as a multi-tenant deployment: four VCs with 55/25/12/8% of the
#: demand and a quarter of the slice each, under the program's quota gate
QUOTA = json.dumps({
    "traffic": json.loads(SMALL)["traffic"],
    "config": {"jobs": {"vc_share": [[0, 0.55], [1, 0.25], [2, 0.12],
                                     [3, 0.08]]},
               "scheduler": {"queue_window": 300,
                             "vc_quotas": {str(v): 0.25 for v in range(4)}}}})


def _harness():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_small(seed=4_000_000_123, trace=0, override=SMALL):
    return _harness().run(argparse.Namespace(
        workload="helios-flash", seed=seed, seconds=1.5, trace=trace,
        rehearse=True, override=override))


@pytest.fixture(scope="module")
def sound():
    return run_small()


def test_sound_run_is_correct(sound):
    res = sound["result"]
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0
    assert min(sound["info"]["reference_decisions"]) >= 3


def test_bfloat16_control_fails(sound):
    fwd = reference.control_forward("bfloat16")
    got = reference.device_numbers(sound["samples"], sound["actor"],
                                   forward=fwd)
    checks = sound["result"]["checks"]
    failed = [k for k, v in got.items()
              if k in checks and v > checks[k]["limit"]]
    assert failed, (got, checks)


def _step_unchanged(monkeypatch):
    from repro.sched.engine import SchedulerEngine
    orig = SchedulerEngine.step

    def step(self, until=float("inf"), max_events=None):
        if self.optimized:      # the timed engine only; the reference runs
            return 0
        return orig(self, until, max_events)
    monkeypatch.setattr(SchedulerEngine, "step", step)


def _half_batch(monkeypatch):
    from repro.kernels.batch_score import BucketedScorer
    orig = BucketedScorer.score

    def score(self, feats):
        half = max(len(feats) // 2, 1)
        out = np.asarray(orig(self, feats[:half]))
        return np.concatenate([out, np.full(len(feats) - half, out.mean(),
                                            np.float32)])
    monkeypatch.setattr(BucketedScorer, "score", score)


def _actor_order_altered(monkeypatch):
    import repro.core.agent as agent
    orig = agent.greedy_step
    monkeypatch.setattr(agent, "greedy_step",
                        lambda p, ov, m: orig(p, ov, m)[::-1])


def _fast_path_features_altered(monkeypatch):
    """The fast path's vectorized feature rows (the reference loop builds
    its own): only the schedule comparison sees this one."""
    import repro.core.features as features
    orig = features._build_features_vec

    def vec(*a, **k):
        out = orig(*a, **k)
        out[:, features._IDX["req_time"]] += 0.5
        return out
    monkeypatch.setattr(features, "_build_features_vec", vec)


def _backfill_ignores_reservation(monkeypatch):
    """The timed engine backfills as if the head had no reservation."""
    from repro.sched.engine import SchedulerEngine
    orig = SchedulerEngine._earliest_start

    def earliest(self, job):
        return float("inf") if self.optimized else orig(self, job)
    monkeypatch.setattr(SchedulerEngine, "_earliest_start", earliest)


@pytest.mark.parametrize("fault", [_step_unchanged, _half_batch,
                                   _actor_order_altered,
                                   _fast_path_features_altered,
                                   _backfill_ignores_reservation])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_small()["result"]
    assert not res["correct"], res["checks"]


def test_guarantee_check_sees_a_backfill_past_the_reservation(monkeypatch):
    _backfill_ignores_reservation(monkeypatch)
    run = run_small()
    assert run["info"]["guarantees_broken"]["reservation"] > 0, run["info"]
    assert run["result"]["checks"]["guarantee_violations"]["value"] > 0


def test_sound_run_checks_every_start_and_two_segments(sound):
    info = sound["info"]
    assert info["guarantees_checked"]["starts"] > info["reference_starts"][0]
    assert info["guarantees_checked"]["backfills"] > 0
    assert len(info["reference_from"]) == 2
    assert info["reference_from"][1] > info["reference_from"][0]


# ---- a multi-tenant deployment: the program's VC-quota gate ---------------


@pytest.fixture(scope="module", params=[0, 1], ids=["untraced", "traced"])
def quota_run(request):
    """A quota rehearsal, with the engine states handed to the reference
    replay kept."""
    blobs = []
    orig = reference.replay_schedule

    def keep(engine_cls, hooks_cls, blob, *a):
        blobs.append(blob)
        return orig(engine_cls, hooks_cls, blob, *a)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "replay_schedule", keep)
        run = run_small(trace=request.param, override=QUOTA)
    return run, blobs, request.param


def test_quota_run_is_correct_and_the_gate_has_work(quota_run):
    run, _, _ = quota_run
    res, info = run["result"], run["info"]
    assert res["correct"], res["checks"]
    assert res["checks"]["schedule_mismatches"]["value"] == 0
    assert info["guarantees_broken"]["quota"] == 0
    assert info["guarantees_checked"]["quota_decisions"] > 0, info


def test_reference_keeps_the_gate_and_drops_the_traced_wrapper(quota_run):
    from repro.core.env import RLPrioritizer
    from repro.sched import QuotaPrioritizer
    _, blobs, traced = quota_run
    assert len(blobs) == 2
    for blob in blobs:
        timed = pickle.loads(blob)["prioritizer"]
        assert isinstance(timed, QuotaPrioritizer)
        assert isinstance(timed.base, recorders.TracedPrioritizer) == traced
        state = pickle.loads(reference.naive_blob(blob))
        pri = state["prioritizer"]
        assert state["optimized"] is False
        assert isinstance(pri, QuotaPrioritizer)
        assert type(pri.base) is RLPrioritizer
        assert pri._base_rank_window.__self__ is pri.base
        assert pri.quotas == timed.quotas and pri._usage == timed._usage
        assert pri._usage, "the gate's usage travels with the state"


def _gate_usage_unattached(monkeypatch):
    """The timed engine's gate never hears of a start or a finish (what a
    harness wrapper outermost did in traced runs); the reference's gate,
    re-attached on load, does."""
    import repro.sched.service as service
    from repro.sched import MultiHooks, QuotaPrioritizer
    monkeypatch.setattr(service, "MultiHooks", lambda *ch: MultiHooks(
        *[c for c in ch if not isinstance(c, QuotaPrioritizer)]))


def _reference_strips_the_gate(monkeypatch):
    """The reference takes off the outermost prioritizer, the gate."""
    def naive_blob(blob):
        state = pickle.loads(blob)
        state["optimized"] = False
        state["cluster"].cache_enabled = False
        state["prioritizer"] = state["prioritizer"].base
        return pickle.dumps(state)
    monkeypatch.setattr(reference, "naive_blob", naive_blob)


def _gate_never_demotes(monkeypatch):
    """The gate keeps the base order in the timed run and the reference
    alike: only the quota guarantee can see it."""
    from repro.sched import QuotaPrioritizer
    monkeypatch.setattr(QuotaPrioritizer, "_gate",
                        lambda self, jobs, cluster, order: order)


@pytest.mark.parametrize("fault", [_gate_usage_unattached,
                                   _reference_strips_the_gate,
                                   _gate_never_demotes])
def test_quota_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(override=QUOTA)["result"]
    assert not res["correct"], res["checks"]


def test_quota_guarantee_sees_a_gate_that_never_demotes(monkeypatch):
    _gate_never_demotes(monkeypatch)
    run = run_small(override=QUOTA)
    assert run["info"]["guarantees_broken"]["quota"] > 0, run["info"]
    assert run["result"]["checks"]["schedule_mismatches"]["value"] == 0
