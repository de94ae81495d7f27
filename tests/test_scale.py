"""Scale-out pins: parallel federation stepping, the MILP solution cache,
compact completed-summary mode, and the shape-bucketed deep-window scorer.

Everything here guards the "Raw speed, round 3" contract: every fast path
is opt-in and bit-identical to its serial/uncached/default reference —
identical job tuples AND identical decision counters, not just identical
aggregates.
"""
import numpy as np
import pytest

from conftest import PAI_WINDOW, pai_quota_stream
from repro.core import PolicyPrioritizer, make_policy
from repro.core.agent import PPOAgent
from repro.core.cluster import ClusterState
from repro.core.env import RLPrioritizer
from repro.core.features import MAX_QUEUE_SIZE
from repro.core.milp import choose_allocation
from repro.core.prioritizer import WindowFields
from repro.core.types import ClusterSpec, Job, NodeSpec
from repro.fed import run_fleet
from repro.fed.scenarios import FLEET_SCENARIOS
from repro.kernels.batch_score import BucketedScorer, bucket_for
from repro.sched import SchedulerEngine, get_scenario


# ------------------------------------------------- parallel federation ----


def _fleet_sig(sr):
    """Bit-identity signature: completed job tuples + per-member decision
    counters + routing counts + fleet aggregates."""
    jobs = tuple(sorted((j.job_id, j.submit_time, j.first_start_time,
                         j.finish_time, j.num_gpus, j.vc)
                        for j in sr.result.jobs))
    eng = sr.fed.engines
    return (jobs,
            tuple(e.decisions for e in eng),
            tuple(e.backfills for e in eng),
            tuple(sr.fed.routed),
            sr.fed.deferrals,
            len(sr.fed.migrations))


@pytest.mark.parametrize("name", sorted(FLEET_SCENARIOS))
def test_parallel_federation_bit_identical(name):
    """parallel=True must replay every registered fleet scenario —
    fault-storm and blackout chaos included — bit-identically to the
    serial path: same job tuples, same decisions/backfills per member,
    same routing and deferral counts."""
    serial = _fleet_sig(run_fleet(name, num_jobs=150, seed=3))
    par = _fleet_sig(run_fleet(name, num_jobs=150, seed=3, parallel=True))
    assert serial == par


def test_parallel_federation_pool_lifecycle():
    """The stepping pool is lazy, reused across windows, and close() is
    idempotent (a closed federation re-creates it on the next step)."""
    from repro.fed.federation import FederatedScheduler
    run = FLEET_SCENARIOS["fleet-steady"].build(60, 1)
    fed = FederatedScheduler(run.clusters, "jsq",
                             fault_models=run.fault_models, parallel=True)
    assert fed._pool is None          # lazy: no threads before stepping
    fed.submit(run.jobs)
    fed.step(run.jobs[0].submit_time + 3600.0)
    assert fed._pool is not None
    fed.close()
    assert fed._pool is None
    fed.close()                       # idempotent
    fed.run_until_complete()          # re-creates the pool transparently
    assert fed.done
    fed.close()


# ---------------------------------------------------- MILP solve cache ----


def _fragmented_cluster() -> ClusterState:
    spec = ClusterSpec(nodes=[NodeSpec(node_id=i, gpu_type="V100",
                                       num_gpus=8, num_cpus=96, mem_gb=768.0)
                              for i in range(16)])
    cluster = ClusterState(spec)
    for i in range(8):   # fragment: spread and pack become distinct ways
        filler = Job(job_id=900 + i, user=0, submit_time=0.0,
                     runtime=86400.0, est_runtime=86400.0, num_gpus=4,
                     gpu_type="V100")
        cluster.allocate(filler, {i: 4})
    return cluster


def _probe(jid: int, gpus: int) -> Job:
    return Job(job_id=jid, user=0, submit_time=0.0, runtime=3600.0,
               est_runtime=3600.0, num_gpus=gpus, gpu_type="V100")


def test_milp_solution_cache_differential():
    """Cached and uncached paths return identical results for every probe
    shape, and repeats on an unchanged cluster are served from the cache
    (same object, no re-solve)."""
    cluster = _fragmented_cluster()
    for gpus in (8, 12, 16, 24):
        job = _probe(gpus, gpus)
        ways = cluster.candidate_ways(job)
        assert len(ways) >= 2, gpus
        look = [_probe(100 + gpus + i, 8) for i in range(3)]
        uncached = choose_allocation(cluster, job, ways, look,
                                     solution_cache=False)
        first = choose_allocation(cluster, job, ways, look)
        again = choose_allocation(cluster, job, ways, look)
        assert (uncached.placement, uncached.way_index) \
            == (first.placement, first.way_index)
        assert again is first           # dict hit, not a re-solve


def test_milp_solution_cache_invalidated_on_version_bump():
    """Any cluster mutation bumps the version and must bypass (and reset)
    the solution cache — a stale placement for the old free-GPU state
    would corrupt the allocator."""
    cluster = _fragmented_cluster()
    job = _probe(1, 8)
    ways = cluster.candidate_ways(job)
    first = choose_allocation(cluster, job, ways, [])
    ver0, store0 = cluster._milp_sol_cache
    assert store0                      # populated at the current version

    # mutate: allocate 4 more GPUs -> version bump, fresh ways
    blocker = _probe(2, 4)
    cluster.allocate(blocker, {8: 4})
    ways2 = cluster.candidate_ways(job)
    second = choose_allocation(cluster, job, ways2, [])
    ver1, store1 = cluster._milp_sol_cache
    assert ver1 != ver0                # keyed to the new version...
    assert second is not first         # ...and genuinely re-solved
    assert len(store1) == 1            # old version's entries dropped


def test_milp_skeletons_thread_local():
    """_SKELETONS is thread-local: concurrent federation stepping must
    never share (or corrupt) the mutable skeleton arrays."""
    import threading

    from repro.core.milp import _SKELETONS, _skeleton

    _skeleton(4, 8, 2)
    main_len = len(_SKELETONS)
    assert main_len >= 1
    seen: dict = {}

    def worker():
        seen["before"] = len(_SKELETONS)
        _skeleton(4, 8, 2)
        seen["after"] = len(_SKELETONS)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["before"] == 0         # fresh store in the new thread
    assert seen["after"] == 1
    assert len(_SKELETONS) == main_len  # main thread's store untouched


# ------------------------------------------- compact completed summary ----


def _stream(engine, jobs):
    jobs = [j.clone_pending() for j in jobs]
    feed = 0
    while True:
        nxt = engine.next_event_time()
        if feed < len(jobs):
            nxt = min(nxt, jobs[feed].submit_time)
        if nxt == float("inf"):
            break
        horizon = max(engine.now, nxt) + 3600.0
        hi = feed
        while hi < len(jobs) and jobs[hi].submit_time <= horizon:
            hi += 1
        if hi > feed:
            engine.submit(jobs[feed:hi])
            feed = hi
        engine.step(horizon)
    return engine


def test_compact_completed_mode_pinned_to_default():
    """completed_summary=True must change only the bookkeeping: identical
    decisions, backfills, completed counts, aggregate stats, and result()
    makespan/avg-JCT — while the full Job list is dropped and the ring
    stays bounded."""
    run = get_scenario("flash-crowd").build(600, seed=0)

    def build(compact):
        pri = PolicyPrioritizer(make_policy("fcfs"))
        return SchedulerEngine(run.spec, pri, allocator="pack",
                               fault_model=run.fault_model,
                               queue_window=256, completed_summary=compact,
                               completed_keep=32)

    full = _stream(build(False), run.jobs)
    compact = _stream(build(True), run.jobs)

    assert compact.completed_count == full.completed_count == 600
    assert compact.decisions == full.decisions
    assert compact.backfills == full.backfills
    assert len(compact.completed) == 0          # jobs not retained
    assert len(compact.completed_ring) == 32    # bounded ring
    assert len(full.completed) == 600

    sf, sc = full.completed_stats(), compact.completed_stats()
    assert sc["completed"] == sf["completed"]
    assert sc["mean_jct_s"] == pytest.approx(sf["mean_jct_s"])
    assert sc["mean_wait_s"] == pytest.approx(sf["mean_wait_s"])

    rf, rc = full.result(), compact.result()
    assert rc.makespan == rf.makespan
    assert rc.gpu_seconds_used == rf.gpu_seconds_used
    assert rc.decisions == rf.decisions
    # per-job averages in compact mode come from completed_stats() (the
    # result() docstring's contract — result().jobs is intentionally empty)
    assert sc["mean_jct_s"] == pytest.approx(rf.avg_jct)
    assert sc["mean_wait_s"] == pytest.approx(rf.avg_wait)

    # snapshots agree on the headline counters too
    assert compact.snapshot().num_completed == full.snapshot().num_completed


def test_compact_ring_holds_most_recent_tuples():
    run = get_scenario("steady").build(100, seed=0)
    pri = PolicyPrioritizer(make_policy("fcfs"))
    eng = _stream(SchedulerEngine(run.spec, pri, allocator="pack",
                                  fault_model=run.fault_model,
                                  completed_summary=True, completed_keep=10),
                  run.jobs)
    assert eng.completed_count == 100
    ring = list(eng.completed_ring)
    assert len(ring) == 10
    # tuples are (job_id, submit, first_start, finish, num_gpus, vc) in
    # finish order — the tail of the stream
    finishes = [r[3] for r in ring]
    assert finishes == sorted(finishes)


# --------------------------------------------- bucketed deep-window scorer ----


def test_bucket_ladder():
    assert bucket_for(1) == 256
    assert bucket_for(256) == 256
    assert bucket_for(257) == 512
    assert bucket_for(5000) == 8192
    assert bucket_for(10 ** 6) == 16384     # clamped at the cap


def _mk_cluster():
    spec = ClusterSpec(nodes=[NodeSpec(node_id=i, gpu_type="V100",
                                       num_gpus=8, num_cpus=64, mem_gb=512.0)
                              for i in range(8)])
    return ClusterState(spec)


def _mk_jobs(n):
    rng = np.random.default_rng(0)
    return [Job(job_id=i, user=i % 5, submit_time=float(i),
                runtime=600.0 + 10 * i, est_runtime=600.0 + 10 * i,
                num_gpus=int(rng.integers(1, 8)), gpu_type="V100", vc=i % 3)
            for i in range(n)]


def test_bucketed_scorer_matches_reference_mlp():
    """The Pallas batch scorer must match a plain numpy forward pass of
    the same actor MLP (tanh-tanh-linear) on every row."""
    agent = PPOAgent()
    sc = BucketedScorer(agent.params["actor"])
    rng = np.random.default_rng(1)
    x = rng.normal(size=(300, 8)).astype(np.float32)
    got = sc.score(x)
    h = x.astype(np.float64)
    for i, lyr in enumerate(agent.params["actor"]):
        h = h @ np.asarray(lyr["w"], dtype=np.float64) \
            + np.asarray(lyr["b"], dtype=np.float64)
        if i < 2:
            h = np.tanh(h)
    assert got.shape == (300,)
    np.testing.assert_allclose(got, h[:, 0], rtol=1e-4, atol=1e-5)
    assert sc.compiled_buckets == (512,)
    # a second nearby size reuses the same bucket — no new compilation
    sc.score(rng.normal(size=(400, 8)).astype(np.float32))
    assert sc.compiled_buckets == (512,)


def test_deep_window_head_identical_tail_policy_ordered():
    """deep_scorer changes ONLY the FIFO tail beyond MAX_QUEUE_SIZE: the
    actor-window head ranking stays bit-identical, the tail becomes a
    permutation ordered by the bucketed logits."""
    cluster = _mk_cluster()
    jobs = _mk_jobs(400)

    base = RLPrioritizer(PPOAgent(), explore=False)
    order_base = base.rank(jobs, cluster, now=500.0)

    agent = PPOAgent()
    deep = RLPrioritizer(agent, explore=False,
                         deep_scorer=BucketedScorer(agent.params["actor"]))
    order_deep = deep.rank(jobs, cluster, now=500.0)

    assert order_base[:256] == order_deep[:256]
    assert sorted(order_deep) == list(range(400))
    assert order_base[256:] == list(range(256, 400))   # default stays FIFO
    assert order_deep[256:] != list(range(256, 400))   # deep mode reorders


def test_deep_scorer_inert_below_window():
    """Queues that fit in the actor window never touch the scorer."""
    cluster = _mk_cluster()
    jobs = _mk_jobs(64)
    agent = PPOAgent()
    sc = BucketedScorer(agent.params["actor"])
    deep = RLPrioritizer(agent, explore=False, deep_scorer=sc)
    base = RLPrioritizer(PPOAgent(), explore=False)
    assert deep.rank(jobs, cluster, now=100.0) \
        == base.rank(jobs, cluster, now=100.0)
    assert sc.compiled_buckets == ()


def _list_order(action, logits, n, tail_logits, rows):
    """The ranking order as a Python list, composed element by element:
    the plain reference for ``RLPrioritizer._rank``'s numpy order."""
    order = list(np.argsort(-logits[:n], kind="stable"))
    if action < n:
        order.remove(action)
        order.insert(0, action)
    if tail_logits is not None:
        order += [int(n + i)
                  for i in np.argsort(-tail_logits, kind="stable")]
    else:
        order += list(range(n, rows))
    return order


def _tied_logits(seed, size, levels):
    """Seeded float32 logits on a few integer levels: many exact ties."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, levels, size=size).astype(np.float32)


class _TiedActor:
    """Stands in for the PPO agent: the same tied logits over the head
    rows at every call (masked rows below), and either the greedy head or
    an action off it, as the sampling (explore) path can return."""

    def __init__(self, rows, explore, seed):
        n = min(rows, MAX_QUEUE_SIZE)
        self.logits = np.full(MAX_QUEUE_SIZE, -1e9, dtype=np.float32)
        self.logits[:n] = _tied_logits(seed, n, 8)
        head = np.argsort(-self.logits[:n], kind="stable")
        self.head = int(head[0])
        self.action = int(head[n // 2]) if explore else self.head

    def act(self, ov, cv, mask, explore=True, record=True):
        return self.action, self.logits.copy()


class _TiedScorer:
    """Stands in for the deep scorer: tied logits for the tail rows."""

    def __init__(self, seed):
        self.seed = seed
        self.calls = []

    def score(self, rows):
        self.calls.append(len(rows))
        return _tied_logits(self.seed, len(rows), 16)


@pytest.mark.parametrize("mode", ["greedy", "explore"])
@pytest.mark.parametrize("deep", [False, True], ids=["fifo-tail", "deep-tail"])
@pytest.mark.parametrize("rows", [64, 300, 4096])
def test_rank_order_array_equals_list_composition(rows, deep, mode):
    """``_rank`` builds the permutation as one ``np.intp`` array: value for
    value the list composition, with ties kept in row order in the head
    and in the scored tail, and a sampled action off the greedy head moved
    to the front.  ``rank_window`` hands the engine that array, ``rank``
    the protocol's list of plain ints."""
    jobs, cluster = _mk_jobs(rows), _mk_cluster()
    explore = mode == "explore"
    agent = _TiedActor(rows, explore, seed=rows)
    scorer = _TiedScorer(seed=rows + 1) if deep else None
    pri = RLPrioritizer(agent, explore=explore, deep_scorer=scorer)
    got = pri.rank_window(jobs, cluster, 500.0, WindowFields.from_jobs(jobs))
    listed = pri.rank(jobs, cluster, 500.0)

    n = min(rows, MAX_QUEUE_SIZE)
    scored = deep and rows > n
    tail = _tied_logits(rows + 1, rows - n, 16) if scored else None
    want = [int(i) for i in _list_order(agent.action, agent.logits, n, tail,
                                        rows)]
    assert isinstance(got, np.ndarray) and got.dtype == np.intp
    assert got.tolist() == want
    assert type(listed) is list and all(type(i) is int for i in listed)
    assert listed == want
    assert sorted(want) == list(range(rows))
    assert want[0] == agent.action
    assert (agent.action != agent.head) == explore
    if scorer is not None:
        assert scorer.calls == ([rows - n] * 2 if scored else [])


@pytest.fixture(scope="module")
def deep_rl_stream():
    """The PAI-shaped deep-window stream ranked by the RL prioritizer
    alone (no quota gate) on the fast engine."""
    return pai_quota_stream(quotas=False)


@pytest.mark.parametrize("other", ["list_order", "reference_loop"])
def test_deep_rl_stream_array_order_schedules_alike(deep_rl_stream, other,
                                                    monkeypatch):
    """The fast engine taking ``RLPrioritizer.rank_window``'s arrays over
    windows past the actor's slots schedules exactly as the same engine
    handed the order as a list, and as the reference loop, which ranks
    through ``rank``."""
    sig, seen = deep_rl_stream
    assert max(rows for _, rows, _, _ in seen) == PAI_WINDOW
    assert {form for form, *_ in seen} == {np.ndarray}
    if other == "list_order":
        ranked = RLPrioritizer.rank_window

        def as_list(self, *args):
            return ranked(self, *args).tolist()
        monkeypatch.setattr(RLPrioritizer, "rank_window", as_list)
        got, seen_list = pai_quota_stream(quotas=False)
        assert {form for form, *_ in seen_list} == {list}
    else:
        got = pai_quota_stream(reference=True, quotas=False)[0]
    assert got == sig
