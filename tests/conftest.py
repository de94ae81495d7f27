"""Shared fixtures.  NOTE: device count stays 1 here (smoke tests / benches
must see one device); multi-device tests spawn subprocesses with their own
XLA_FLAGS per the dry-run contract."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def hypothesis_or_stubs():
    """Import (given, settings, st) from hypothesis, or — on minimal installs
    without the [test] extra — return stand-ins that keep the module
    collectable and mark each property test as skipped."""
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
        return given, settings, st
    except ImportError:
        skip = pytest.mark.skip(reason="hypothesis not installed")

        def given(*a, **kw):
            def deco(fn):
                @skip
                def stub():
                    raise AssertionError("skipped: hypothesis missing")
                stub.__name__ = fn.__name__
                stub.__doc__ = fn.__doc__
                return stub
            return deco

        def settings(*a, **kw):
            return lambda fn: fn

        class _Strategies:
            def __getattr__(self, name):
                return lambda *a, **kw: None

        return given, settings, _Strategies()


def run_py(code: str, devices: int = 0, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess (optionally with N fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="session")
def helios_jobs():
    from repro.core import generate_trace
    return generate_trace("helios", 256, seed=0)


@pytest.fixture(scope="session")
def helios_cluster():
    from repro.core import make_cluster
    return make_cluster("helios")



#: the small PAI-shaped stream's queue window: deeper than the actor's 256
#: slots, so the deep scorer ranks the tail
PAI_WINDOW = 320


def pai_quota_stream(*, reference=False, quotas=True):
    """A small stream shaped like the ``pai-quota`` cell: the
    ``multi-tenant`` scenario (the Alibaba PAI slice over T4, P100 and
    V100; four VCs with 55/25/12/8% of the demand and 25% quotas) with 360
    of its 420 jobs queued at t = 0, ranked by the actor and, past 256
    waiting jobs, the deep scorer, under the program's quota gate.

    Returns the schedule's signature (every job's start and finish; the
    decision, MILP and backfill counters) and, per decision, what the
    plain list partition does there: the type of the order the engine was
    handed, the window's rows, how many of them belong to VCs over quota
    (the rows it demotes) and how many VCs are over quota.  With
    ``reference`` the stream runs on the reference loop
    (``repro.sched.reference_run``) instead of ``run_stream``; with
    ``quotas`` False the RL prioritizer ranks the stream without the gate
    (no VC is then ever over quota)."""
    from repro.core.agent import PPOAgent
    from repro.core.env import RLPrioritizer
    from repro.kernels.batch_score import BucketedScorer
    from repro.predict import RuntimePredictor
    from repro.sched import (EngineHooks, get_scenario, reference_run,
                             run_stream, wrap_tenancy)

    class Partitions(EngineHooks):
        def __init__(self):
            self.seen = []

        def on_decision(self, jobs, order, now, engine):
            pri = engine.prioritizer
            total = max(engine.cluster.provisioned_gpu_totals()[0], 1)
            over = {vc for vc, q in getattr(pri, "quotas", {}).items()
                    if pri._usage.get(vc, 0) / total > q}
            self.seen.append((type(order), len(jobs),
                              sum(j.vc in over for j in jobs), len(over)))

    run = get_scenario("multi-tenant").build(420, 0)
    for job in run.jobs[:360]:
        job.submit_time = 0.0
    agent = PPOAgent()
    pri = wrap_tenancy(
        RLPrioritizer(agent, explore=False,
                      deep_scorer=BucketedScorer(agent.params["actor"])),
        vc_quotas=run.vc_quotas if quotas else None)
    seen = Partitions()
    jobs = [j.clone_pending() for j in run.jobs]
    if reference:
        eng = reference_run(run.spec, jobs, pri, queue_window=PAI_WINDOW,
                            predictor=RuntimePredictor(assist=False),
                            hooks=(seen,))
    else:
        eng = run_stream(run.spec, jobs, pri, queue_window=PAI_WINDOW,
                         chunked_submit=True,
                         predictor=RuntimePredictor(assist=False),
                         hooks=(seen,)).engine
    return (tuple(sorted((j.job_id, j.first_start_time, j.finish_time)
                         for j in eng.completed)),
            (eng.decisions, eng.milp_calls, eng.backfills)), seen.seen
