"""Coverage for the tenancy prioritizer wrappers in repro.sched.service:
the SLA bypass lane (previously untested) and the incremental VC-quota gate
(differential-pinned against its O(running) recompute reference)."""
import numpy as np
import pytest

from conftest import PAI_WINDOW, pai_quota_stream
from repro.core import PolicyPrioritizer, make_cluster, make_policy
from repro.core.cluster import ClusterState
from repro.core.prioritizer import WindowFields
from repro.core.types import Job
from repro.sched import (EngineHooks, QuotaPrioritizer, SlaLanePrioritizer,
                         get_scenario, run_stream, wrap_tenancy)
from repro.sched.engine import _PendingFieldIndex


def _job(jid, *, user=0, vc=0, submit=0.0, runtime=100.0, gpus=1):
    return Job(job_id=jid, user=user, submit_time=submit, runtime=runtime,
               est_runtime=runtime, num_gpus=gpus, vc=vc)


@pytest.fixture()
def cluster_state():
    from repro.core.cluster import ClusterState
    return ClusterState(make_cluster("helios"))


# ---------------------------------------------------------------- SLA lane ----


def test_sla_jobs_bypass_to_front(cluster_state):
    """SLA-bound users' jobs rank before every best-effort job, regardless
    of what the base policy would prefer."""
    jobs = [
        _job(0, user=1, submit=0.0, runtime=10.0),     # best-effort, tiny
        _job(1, user=9, submit=50.0, runtime=9000.0),  # SLA, huge
        _job(2, user=2, submit=10.0, runtime=20.0),    # best-effort
        _job(3, user=9, submit=5.0, runtime=8000.0),   # SLA
    ]
    pri = SlaLanePrioritizer(PolicyPrioritizer(make_policy("sjf")),
                             frozenset({9}))
    order = pri.rank(jobs, cluster_state, now=100.0)
    assert order[:2] == [3, 1]          # SLA first...
    assert set(order[2:]) == {0, 2}     # ...then everyone else


def test_sla_jobs_fcfs_among_themselves(cluster_state):
    """Inside the SLA lane, ordering is FCFS by (submit_time, job_id) even
    when the base policy (SJF) would invert it."""
    jobs = [
        _job(0, user=5, submit=30.0, runtime=1.0),    # SLA, latest, shortest
        _job(1, user=5, submit=10.0, runtime=500.0),  # SLA, earliest, longest
        _job(2, user=5, submit=20.0, runtime=50.0),   # SLA, middle
    ]
    pri = SlaLanePrioritizer(PolicyPrioritizer(make_policy("sjf")),
                             frozenset({5}))
    assert pri.rank(jobs, cluster_state, now=40.0) == [1, 2, 0]


def test_sla_lane_preserves_base_order_for_best_effort(cluster_state):
    """Best-effort jobs keep exactly the base prioritizer's relative order
    behind the SLA lane."""
    jobs = [
        _job(0, user=1, runtime=300.0),
        _job(1, user=7, runtime=5.0),      # SLA
        _job(2, user=2, runtime=10.0),
        _job(3, user=3, runtime=100.0),
    ]
    base = PolicyPrioritizer(make_policy("sjf"))
    pri = SlaLanePrioritizer(base, frozenset({7}))
    order = pri.rank(jobs, cluster_state, now=0.0)
    rest = [jobs[i] for i in order if jobs[i].user != 7]
    base_rest = [j for j in jobs if j.user != 7]
    base_order = base.rank(base_rest, cluster_state, now=0.0)
    assert rest == [base_rest[i] for i in base_order]   # SJF: 2, 3, 0
    assert [j.job_id for j in rest] == [2, 3, 0]


def test_sla_lane_no_sla_users_is_transparent(cluster_state):
    jobs = [_job(0, runtime=300.0), _job(1, runtime=5.0)]
    base = PolicyPrioritizer(make_policy("sjf"))
    pri = SlaLanePrioritizer(base, frozenset())
    assert pri.rank(jobs, cluster_state, 0.0) == \
        base.rank(jobs, cluster_state, 0.0)
    assert pri.use_estimates == base.use_estimates


# -------------------------------------------------------------- quota gate ----


def test_quota_demotes_over_quota_vcs(cluster_state):
    """Jobs from a VC whose hook-fed usage exceeds its quota are demoted
    behind every under-quota job."""
    pri = QuotaPrioritizer(PolicyPrioritizer(make_policy("fcfs")),
                           {0: 0.10, 1: 0.90})
    # simulate engine hooks: VC 0 holds 200 of 400 GPUs (over a 10% quota)
    pri.on_start(_job(90, vc=0, gpus=200), now=0.0)
    jobs = [_job(0, vc=0, submit=0.0), _job(1, vc=1, submit=1.0),
            _job(2, vc=0, submit=2.0), _job(3, vc=1, submit=3.0)]
    assert pri.rank(jobs, cluster_state, 10.0) == [1, 3, 0, 2]
    # once the hog finishes, FCFS order is restored
    pri.on_finish(_job(90, vc=0, gpus=200), now=5.0)
    assert pri.rank(jobs, cluster_state, 10.0) == [0, 1, 2, 3]


def test_quota_usage_tracks_start_finish_requeue():
    pri = QuotaPrioritizer(PolicyPrioritizer(make_policy("fcfs")), {0: 0.5})
    a, b = _job(0, vc=2, gpus=8), _job(1, vc=2, gpus=4)
    pri.on_start(a, 0.0)
    pri.on_start(b, 0.0)
    assert pri._usage == {2: 12}
    pri.on_requeue(a, 1.0)      # fault kill re-queues: usage drops
    assert pri._usage == {2: 4}
    pri.on_finish(b, 2.0)
    assert pri._usage == {}     # empty VCs are dropped, not left at 0
    pri.reset_usage()
    assert pri._usage == {}


class _UsageAuditor(EngineHooks):
    """Asserts that the hook-fed incremental usage equals a fresh
    O(running) recompute from the engine's running set at every engine
    tick, and right after every ranking — so every gate decision read the
    recompute's usage."""

    def __init__(self, pri):
        self.pri = pri
        self.checked = 0
        self.decisions = 0

    def _check(self, engine):
        expect = {}
        for job, *_ in engine.running.values():
            expect[job.vc] = expect.get(job.vc, 0) + job.num_gpus
        assert self.pri._usage == expect

    def on_tick(self, now, engine):
        self._check(engine)
        self.checked += 1

    def on_decision(self, jobs, order, now, engine):
        self._check(engine)
        self.decisions += 1


@pytest.mark.parametrize("scenario", ["multi-tenant", "fault-storm"])
def test_quota_incremental_matches_recompute_every_tick(scenario):
    """The incremental usage dict equals the O(running) recompute after
    every processed event batch and at every decision, including
    fault-driven requeues."""
    run = get_scenario(scenario).build(64, seed=2)
    quotas = run.vc_quotas or {0: 0.25, 1: 0.25, 2: 0.25, 3: 0.25}
    pri = QuotaPrioritizer(PolicyPrioritizer(make_policy("fcfs")), quotas)
    auditor = _UsageAuditor(pri)
    run_stream(run.spec, [j.clone_pending() for j in run.jobs], pri,
               allocator="pack", fault_model=run.fault_model,
               hooks=(auditor,))
    assert auditor.checked > 0 and auditor.decisions > 0


def test_wrap_tenancy_composition():
    base = PolicyPrioritizer(make_policy("fcfs"))
    assert wrap_tenancy(base) is base
    sla = wrap_tenancy(base, frozenset({1}))
    assert isinstance(sla, SlaLanePrioritizer)
    both = wrap_tenancy(base, frozenset({1}), {0: 0.5})
    assert isinstance(both, QuotaPrioritizer)
    assert isinstance(both.base, SlaLanePrioritizer)
    assert isinstance(wrap_tenancy(base, vc_quotas={0: 0.5},
                                   enforce_quotas=False),
                      PolicyPrioritizer)


# ------------------------------------- the gate against its plain reference ----


def _list_gate(jobs, usage, quotas, total, order):
    """The quota gate as a plain list partition: the reference the
    columnar gate must equal for every input."""
    over = {vc for vc, q in quotas.items() if usage.get(vc, 0) / total > q}
    under = [i for i in order if jobs[i].vc not in over]
    demoted = [i for i in order if jobs[i].vc in over]
    return under + demoted


class _Given:
    """Base prioritizer that ranks every window with a given order."""

    use_estimates = False

    def __init__(self, order):
        self.order = order

    def rank(self, jobs, cluster, now):
        return self.order

    def rank_window(self, jobs, cluster, now, fields):
        return self.order

    def observe_finish(self, job):
        pass


ROWS = 4096
QUOTAS = {0: 0.25, 1: 0.25, 2: 0.25, 3: 0.25}
#: VC draws: the PAI deployment's skew over the four quota'd VCs, and
#: uniform over five ids (one with no quota)
VC_DRAWS = {"skewed": ([0, 1, 2, 3], [0.55, 0.25, 0.12, 0.08]),
            "uniform": ([0, 1, 2, 3, 4], None)}


def _window(source, vcs):
    """``(jobs, fields)`` of a ranking window of ``ROWS`` rows: fields
    built from the jobs, a ``take()`` row subset of a larger window, or the
    engine's pending-field index sliced at ``ROWS``."""
    jobs = [Job(job_id=i, user=i % 7, submit_time=float(i), runtime=60.0,
                est_runtime=60.0, num_gpus=1 + i % 4, vc=int(v))
            for i, v in enumerate(vcs)]
    if source == "from_jobs":
        return jobs[:ROWS], WindowFields.from_jobs(jobs[:ROWS])
    if source == "take":
        rows = sorted(np.random.default_rng(5).choice(
            len(jobs), ROWS, replace=False).tolist())
        return ([jobs[i] for i in rows],
                WindowFields.from_jobs(jobs).take(rows))
    index = _PendingFieldIndex()
    for i, job in enumerate(jobs):
        index.insert(i, job)
    return jobs[:ROWS], index.window(ROWS)


@pytest.mark.parametrize("form", ["list", "array"])
@pytest.mark.parametrize("n_over", [0, 1, 2, 3])
@pytest.mark.parametrize("draw", sorted(VC_DRAWS))
@pytest.mark.parametrize("source", ["from_jobs", "take", "engine"])
def test_quota_gate_equals_the_list_partition(source, draw, n_over, form):
    """``rank_window`` and ``rank`` both give the plain partition's order,
    on seeded windows with zero to three VCs over quota; with nothing to
    demote ``rank_window`` hands the base order back untouched, and
    ``rank`` gives the protocol's list."""
    rng = np.random.default_rng(1000 * n_over + len(source) + len(draw))
    ids, p = VC_DRAWS[draw]
    jobs, fields = _window(source, rng.choice(ids, size=ROWS + 512, p=p))
    cs = ClusterState(make_cluster("alibaba"))
    total = cs.provisioned_gpu_totals()[0]
    over = set(rng.choice(4, size=n_over, replace=False).tolist())
    # one GPU above the quota's share, or exactly at it (not over)
    usage = {vc: int(total * QUOTAS[vc]) + (vc in over) for vc in QUOTAS}
    assert {vc for vc in QUOTAS if usage[vc] / total > QUOTAS[vc]} == over
    perm = rng.permutation(len(jobs))
    order = perm.tolist() if form == "list" else perm.astype(np.intp)
    pri = QuotaPrioritizer(_Given(order), QUOTAS)
    pri._usage = usage
    want = _list_gate(jobs, usage, QUOTAS, total, perm.tolist())
    got = pri.rank_window(jobs, cs, 0.0, fields)
    listed = pri.rank(jobs, cs, 0.0)
    assert [int(i) for i in got] == want
    assert [int(i) for i in listed] == want
    demoted = sum(jobs[i].vc in over for i in perm)
    if demoted:
        assert isinstance(got, np.ndarray) and got.dtype == np.intp
        assert type(listed) is list
        assert want != perm.tolist()
    else:
        assert got is order
        assert listed is order if form == "list" else type(listed) is list


@pytest.fixture(scope="module")
def pai_fast():
    """The PAI-shaped stream on the fast engine."""
    return pai_quota_stream()


def test_pai_stream_deep_and_gated(pai_fast):
    """The stream exercises what the cell does: deep windows past the
    actor's slots, and the gate demoting rows at most decisions, handing
    the engine arrays."""
    _, seen = pai_fast
    assert max(rows for _, rows, _, _ in seen) == PAI_WINDOW
    demoting = [form for form, _, demoted, _ in seen if demoted]
    assert len(demoting) > len(seen) // 2
    assert set(demoting) == {np.ndarray}


def test_pai_stream_fast_engine_equals_reference_loop(pai_fast):
    """The fast engine taking the gate's arrays schedules exactly as the
    reference loop (``ReferenceEngine``), which ranks through ``rank``."""
    assert pai_quota_stream(reference=True)[0] == pai_fast[0]


def test_pai_stream_array_order_equals_list_order(pai_fast, monkeypatch):
    """The gate's array order and the same order as a list give the same
    schedule on the fast engine."""
    gated = QuotaPrioritizer.rank_window

    def as_list(self, *args):
        return [int(i) for i in gated(self, *args)]
    monkeypatch.setattr(QuotaPrioritizer, "rank_window", as_list)
    sig, seen = pai_quota_stream()
    assert {form for form, *_ in seen} == {list}
    assert sig == pai_fast[0]
