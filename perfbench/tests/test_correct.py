"""``correct`` at a size a CPU test run can hold: a sound run passes, the
lower-precision control put in the program's place fails, and a run with
the timed path broken underneath fails, once for each fault the cells can
have (the device check of the harness is skipped with ``--rehearse``)."""
import argparse
import importlib.util
import os

import numpy as np
import pytest

import reference
from conftest import BENCH

SMALL = ('{"traffic": {"num_jobs": 1500, "crowd": {"jobs": 700}, '
         '"window_rows": [300, 300], "reference_decisions": 30}, '
         '"config": {"scheduler": {"queue_window": 300}}}')


def _harness():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_small(seed=4_000_000_123):
    return _harness().run(argparse.Namespace(
        workload="helios-flash", seed=seed, seconds=1.5, trace=0,
        rehearse=True, override=SMALL))


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
