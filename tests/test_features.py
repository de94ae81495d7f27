import numpy as np
import pytest
from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

from repro.core import ClusterState, make_cluster
from repro.core.features import (CV_SIZE, MAX_QUEUE_SIZE, NUM_FEATURES,
                                 OV_SIZE, build_features, build_state,
                                 sample_features)
from repro.core.trace import generate_trace


def test_feature_matrix_shape(helios_jobs, helios_cluster):
    c = ClusterState(helios_cluster)
    feats = build_features(helios_jobs[:32], c, now=1e5)
    assert feats.shape == (32, NUM_FEATURES)
    assert np.isfinite(feats).all()


def test_state_padding(helios_jobs, helios_cluster):
    c = ClusterState(helios_cluster)
    ov, cv, mask = build_state(helios_jobs[:10], c, now=1e5)
    assert ov.shape == (MAX_QUEUE_SIZE, OV_SIZE)
    assert cv.shape == (MAX_QUEUE_SIZE, CV_SIZE)
    assert mask.sum() == 10
    assert (ov[10:] == 0).all()


def test_overflow_truncated(helios_cluster):
    jobs = generate_trace("helios", 300, seed=2)
    c = ClusterState(helios_cluster)
    ov, cv, mask = build_state(jobs, c, now=1e6)
    assert mask.sum() == MAX_QUEUE_SIZE


def test_sampler_conditions(helios_jobs, helios_cluster):
    """High fragmentation selects job_size; low selects urgency (Sec 3.2)."""
    c = ClusterState(helios_cluster)
    feats = build_features(helios_jobs[:8], c, now=1e5)
    # low fragmentation: idle cluster -> CFF small? construct both regimes
    _, names_low = sample_features(feats, c)
    # fragment: take a few GPUs on every node
    for i in range(len(c.gpu_types)):
        c.free_gpus[i] = 2
    _, names_high = sample_features(build_features(helios_jobs[:8], c, 1e5), c)
    assert len(names_low) == OV_SIZE and len(names_high) == OV_SIZE
    assert ("urgency" in names_low) or ("job_size" in names_high)


def test_raw_vs_engineered(helios_jobs, helios_cluster):
    c = ClusterState(helios_cluster)
    ov_raw, _, _ = build_state(helios_jobs[:8], c, 1e5, raw=True)
    ov_eng, _, _ = build_state(helios_jobs[:8], c, 1e5, raw=False)
    assert not np.allclose(ov_raw, ov_eng)


@settings(max_examples=25, deadline=None)
@given(st.floats(min_value=0, max_value=1e7), st.booleans())
def test_features_bounded(now, use_est):
    jobs = generate_trace("helios", 16, seed=5)
    c = ClusterState(make_cluster("helios"))
    feats = build_features(jobs, c, now, use_estimates=use_est)
    assert np.isfinite(feats).all()
    assert (feats >= -1.0 - 1e-6).all() and (feats <= 2.0 + 1e-6).all()


# ------------------------------------------- vectorized FBM differential ----
# The RL path's per-decision feature matrix was an O(window * 17) Python
# loop; the vectorized path over the engine's WindowFields views must be
# bit-identical (same float32 matrix, bit for bit) so RL schedules and
# training trajectories cannot drift.

from repro.core.cluster import _job_shape  # noqa: E402
from repro.core.features import _build_features_scalar  # noqa: E402
from repro.core.prioritizer import WindowFields  # noqa: E402


def _varied_cluster(trace, seed):
    c = ClusterState(make_cluster(trace), cache=True)
    jobs = generate_trace(trace, 12, seed=seed)
    for j in jobs:
        pl = c.find_placement(j, "pack")
        if pl is not None:
            c.allocate(j, pl)
    return c


#: the windows the columnar build is held to the scalar loop on
WINDOWS = ("from_jobs", "deep", "absent", "take", "cordoned")


def _index(jobs):
    """The engine's pending-field index over ``jobs``, row for row."""
    from repro.sched.engine import _PendingFieldIndex
    pi = _PendingFieldIndex()
    for k, j in enumerate(jobs):
        pi.insert(k, j)
    return pi


def _window(kind, trace, seed, n):
    """``(jobs, fields, cluster)`` of one window kind: ``from_jobs``-built
    fields; the engine index's views over a 4,096-row window (``deep``);
    index views after every row of some interned shapes left (``absent``);
    a ``take()`` subset; a cluster with a downed, a cordoned and a retired
    node (``cordoned``)."""
    jobs = generate_trace(trace, 4096 if kind == "deep" else n, seed=seed)
    c = _varied_cluster(trace, seed)
    if kind == "cordoned":
        busy = np.flatnonzero(c.free_gpus < c.total_gpus)
        idle = np.flatnonzero(c.free_gpus == c.total_gpus)
        assert busy.size and idle.size >= 2
        assert not c.remove_node(int(busy[0]))     # cordoned, draining
        assert c.remove_node(int(idle[0]))         # retired
        c.fail_node(int(idle[-1]))
    if kind in ("deep", "absent"):
        pi = _index(jobs)
        if kind == "absent":
            gone = {pi.shape_ids[key] for key in
                    (_job_shape(jobs[0]), _job_shape(jobs[-1]))}
            for k in reversed(range(len(jobs))):
                if pi._sid[k] in gone:
                    pi.remove(k)
                    del jobs[k]
        fields = pi.window(len(jobs))
        if kind == "absent":
            assert len(fields.shape_keys) > fields.present_shapes().size
        return jobs, fields, c
    fields = WindowFields.from_jobs(jobs)
    if kind == "take":
        keep = [k for k in range(len(jobs)) if k % 3]
        return [jobs[k] for k in keep], fields.take(keep), c
    return jobs, fields, c


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["helios", "philly", "alibaba"]),
       st.integers(min_value=0, max_value=10_000),
       st.booleans(), st.sampled_from(WINDOWS))
def test_vectorized_features_bit_identical(trace, seed, use_est, window):
    jobs, fields, c = _window(window, trace, seed % 997, 64)
    now = jobs[len(jobs) // 2].submit_time + float(seed % 7919)
    ref = _build_features_scalar(jobs, c, now, use_estimates=use_est)
    vec = build_features(jobs, c, now, use_estimates=use_est, fields=fields)
    assert vec.dtype == ref.dtype
    assert np.array_equal(ref, vec)


def test_vectorized_features_empty_and_downed_nodes():
    c = ClusterState(make_cluster("helios"), cache=True)
    c.fail_node(0)
    assert np.array_equal(
        build_features([], c, 0.0, fields=WindowFields.from_jobs([])),
        _build_features_scalar([], c, 0.0))
    jobs = generate_trace("helios", 8, seed=3)
    assert np.array_equal(
        build_features(jobs, c, 1e5, fields=WindowFields.from_jobs(jobs)),
        _build_features_scalar(jobs, c, 1e5))


def test_build_state_fields_path_identical(helios_jobs, helios_cluster):
    c = ClusterState(helios_cluster)
    jobs = helios_jobs[:48]
    fields = WindowFields.from_jobs(jobs)
    for raw in (False, True):
        ov_a, cv_a, m_a = build_state(jobs, c, 1e5, raw=raw)
        ov_b, cv_b, m_b = build_state(jobs, c, 1e5, raw=raw, fields=fields)
        assert np.array_equal(ov_a, ov_b)
        assert np.array_equal(cv_a, cv_b)
        assert np.array_equal(m_a, m_b)


def test_rl_prioritizer_rank_window_matches_rank():
    """The engine hands RLPrioritizer.rank_window its field views; the
    returned permutation (and hence the schedule) must equal rank()'s,
    value for value: an ``np.intp`` array against the protocol's list."""
    from repro.core.agent import PPOAgent, PPOConfig
    from repro.core.env import RLPrioritizer

    jobs = generate_trace("helios", 40, seed=9)
    c = ClusterState(make_cluster("helios"), cache=True)
    fields = WindowFields.from_jobs(jobs)
    pri = RLPrioritizer(PPOAgent(PPOConfig(seed=3)), explore=False)
    a = pri.rank(jobs, c, 1e4)
    b = pri.rank_window(jobs, c, 1e4, fields)
    assert type(a) is list and all(type(i) is int for i in a)
    assert isinstance(b, np.ndarray) and b.dtype == np.intp
    assert b.tolist() == a


def test_rl_stream_rank_window_schedule_identical():
    """Stream-level differential: an engine using the rank_window fast path
    (fields from its pending index) schedules bit-identically to one forced
    onto the rank() fallback."""
    from repro.core.agent import PPOAgent, PPOConfig
    from repro.core.env import RLPrioritizer
    from repro.sched import SchedulerEngine, get_scenario

    run = get_scenario("flash-crowd").build(64, seed=6)
    fins = []
    for strip_rank_window in (False, True):
        pri = RLPrioritizer(PPOAgent(PPOConfig(seed=11)), explore=False)
        eng = SchedulerEngine(run.spec, pri, allocator="pack")
        if strip_rank_window:
            eng._rank_window = None     # force the rank() fallback
        eng.submit([j.clone_pending() for j in run.jobs])
        eng.drain()
        fins.append({j.job_id: (j.start_time, j.finish_time)
                     for j in eng.completed})
        assert len(fins[-1]) == 64
    assert fins[0] == fins[1]


@pytest.mark.parametrize("trace,seed,use_est,window", [
    pytest.param("helios", 0, False, "from_jobs", id="helios-0-False"),
    pytest.param("helios", 13, True, "from_jobs", id="helios-13-True"),
    pytest.param("philly", 4, False, "from_jobs", id="philly-4-False"),
    pytest.param("philly", 7, True, "from_jobs", id="philly-7-True"),
    pytest.param("alibaba", 2, False, "from_jobs", id="alibaba-2-False"),
    pytest.param("alibaba", 29, True, "from_jobs", id="alibaba-29-True"),
    pytest.param("helios", 5, False, "deep", id="helios-5-False-deep"),
    pytest.param("philly", 8, True, "deep", id="philly-8-True-deep"),
    pytest.param("helios", 3, False, "absent", id="helios-3-False-absent"),
    pytest.param("alibaba", 6, True, "absent", id="alibaba-6-True-absent"),
    pytest.param("philly", 1, False, "take", id="philly-1-False-take"),
    pytest.param("alibaba", 9, True, "take", id="alibaba-9-True-take"),
    pytest.param("helios", 2, True, "cordoned",
                 id="helios-2-True-cordoned"),
    pytest.param("philly", 11, False, "cordoned",
                 id="philly-11-False-cordoned"),
])
def test_vectorized_features_bit_identical_fixed(trace, seed, use_est, window):
    """Deterministic cover for the differential (the hypothesis variant is
    skipped on minimal installs without the [test] extra)."""
    jobs, fields, c = _window(window, trace, seed, 96)
    now = jobs[len(jobs) // 2].submit_time + 123.0
    ref = _build_features_scalar(jobs, c, now, use_estimates=use_est)
    vec = build_features(jobs, c, now, use_estimates=use_est, fields=fields)
    assert np.array_equal(ref, vec)


def test_engine_index_columns_follow_the_pending_queue():
    """The pending index's ``_jid`` and ``_sid`` columns equal the pending
    jobs' own ids and shapes after inserts, removals and array grows, and
    again after a ``save_state`` / ``load_state`` round trip; the window's
    columnar features equal the scalar loop's throughout."""
    from repro.core import PolicyPrioritizer, make_policy
    from repro.sched import SchedulerEngine, get_scenario

    def check(eng):
        pi, pending = eng._pindex, eng.pending
        assert pi.n == len(pending) > 0
        assert pi._jid[:pi.n].tolist() == [float(j.job_id) for j in pending]
        assert [pi.shape_keys[int(s)] for s in pi._sid[:pi.n]] \
            == [_job_shape(j) for j in pending]
        w = min(eng.queue_window, pi.n)
        assert np.array_equal(
            build_features(pending[:w], eng.cluster, eng.now,
                           fields=pi.window(w)),
            _build_features_scalar(pending[:w], eng.cluster, eng.now))

    run = get_scenario("overcommit-queue").build(900, seed=4)
    eng = SchedulerEngine(run.spec, PolicyPrioritizer(make_policy("sjf")),
                          allocator="pack")
    eng.submit([j.clone_pending() for j in run.jobs])
    mid = sorted(j.submit_time for j in run.jobs)[700]
    eng.step(until=mid)
    assert eng._pindex._cap > 256 and eng.completed   # grown, and removed
    check(eng)
    back = SchedulerEngine.load_state(eng.save_state())
    check(back)
    for e in (eng, back):
        e.step(until=mid + 3600.0)
    check(back)
    assert back._pindex._jid[:back._pindex.n].tolist() \
        == eng._pindex._jid[:eng._pindex.n].tolist()


# ------------------------------------------------------- edge-case coverage --


def test_features_zero_gpu_and_oversized_jobs():
    """Degenerate demands (0 GPUs, demand far past capacity) must stay
    finite and in range on both builder paths."""
    from repro.core import Job
    c = ClusterState(make_cluster("helios"))
    jobs = [
        Job(job_id=1, user=0, submit_time=0.0, runtime=100.0,
            est_runtime=100.0, num_gpus=0),
        Job(job_id=2, user=1, submit_time=0.0, runtime=100.0,
            est_runtime=100.0, num_gpus=10_000),
    ]
    for use_est in (False, True):
        f_scalar = _build_features_scalar(jobs, c, 50.0,
                                          use_estimates=use_est)
        f_vec = build_features(jobs, c, 50.0, use_estimates=use_est,
                               fields=WindowFields.from_jobs(jobs))
        for f in (f_scalar, f_vec):
            assert np.isfinite(f).all()
            assert (np.abs(f) <= 1.0 + 1e-6).all()
        assert np.array_equal(f_scalar, f_vec)


def test_features_empty_cluster_context():
    """A cluster with every node retired/down reports zero capacity; the
    builders must not divide by it."""
    c = ClusterState(make_cluster("helios"))
    c.retired[:] = True
    c.version += 1
    jobs = generate_trace("helios", 8, seed=1)
    feats = build_features(jobs, c, now=10.0)
    assert feats.shape == (8, NUM_FEATURES)
    assert np.isfinite(feats).all()


def test_features_nan_inf_inputs_guarded():
    """Corrupt trace fields (NaN/inf runtimes, estimates, memory) must not
    leak NaN into the policy/predictor batch."""
    from repro.core import Job
    bad = [
        Job(job_id=1, user=0, submit_time=0.0, runtime=float("nan"),
            est_runtime=float("inf"), num_gpus=2),
        Job(job_id=2, user=1, submit_time=float("nan"), runtime=100.0,
            est_runtime=-float("inf"), num_gpus=2,
            req_mem_gb=float("nan")),
    ]
    c = ClusterState(make_cluster("helios"))
    for use_est in (False, True):
        feats = build_features(bad, c, now=5.0, use_estimates=use_est)
        assert np.isfinite(feats).all()
        scalar = _build_features_scalar(bad, c, 5.0, use_estimates=use_est)
        assert np.isfinite(scalar).all()


def test_features_guard_identity_on_finite_inputs():
    """The NaN/inf guard is nan_to_num — bit-identity for every well-formed
    trace is what keeps the pinned schedules unchanged."""
    jobs = generate_trace("philly", 64, seed=9)
    c = ClusterState(make_cluster("philly"))
    feats = build_features(jobs, c, now=1e4)
    assert np.array_equal(feats, np.nan_to_num(feats, nan=0.0,
                                               posinf=1.0, neginf=-1.0))
