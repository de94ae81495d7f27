"""Tests for the span facility ``repro.obs.spans``: the off path builds
nothing and steers nothing, a ``jax.profiler`` session receives every
span of the decision path with the right parent, and an attached bundle
records the same spans on the profiler's clock."""
import collections
import glob
import os

import jax
import numpy as np
import pytest

from conftest import PAI_WINDOW, pai_quota_stream
from repro.core.agent import PPOAgent, greedy_step
from repro.core.cluster import _job_shape
from repro.core.env import RLPrioritizer
from repro.core.features import MAX_QUEUE_SIZE, OV_SIZE
from repro.kernels.batch_score import BucketedScorer
from repro.obs import Observability, spans, validate_trace
from repro.obs.report import analyze
from repro.predict import RuntimePredictor
from repro.sched import EngineHooks, get_scenario, run_stream

#: span -> the program spans it may nest in directly (None: none of them)
PARENTS = {
    "service.submit": {None},
    "predict.submit": {"service.submit"},
    "engine.decide": {None},
    "rank.features": {"engine.decide"},
    "rank.scorer": {"engine.decide"},
    "rank.actor": {"engine.decide"},
    "rank.order": {"engine.decide"},
    "milp.solve": {"engine.decide", "backfill"},
    "backfill": {"engine.decide"},
    "engine.finish": {None},
    "predict.train": {"engine.finish"},
    "engine.hooks": {None},
}
QUEUE_WINDOW = 320      # deeper than the actor's 256 slots: the scorer runs


class _WindowShapes(EngineHooks):
    """Rows and distinct ``_job_shape`` keys of every ranking window."""

    def __init__(self):
        self.windows = []

    def on_decision(self, jobs, order, now, engine):
        self.windows.append((len(jobs), len({_job_shape(j) for j in jobs})))


def _deep_stream(obs=None, hooks=()):
    """An overcommitted queue over three SKUs (so the MILP runs, gangs
    block the head and backfill starts jobs behind it) ranked by the
    actor and, past 256 waiting jobs, the deep scorer; with a shadow
    predictor."""
    run = get_scenario("overcommit-queue").build(600, 0)
    agent = PPOAgent()
    pri = RLPrioritizer(agent, explore=False,
                        deep_scorer=BucketedScorer(agent.params["actor"]))
    res = run_stream(run.spec, [j.clone_pending() for j in run.jobs], pri,
                     queue_window=QUEUE_WINDOW, chunked_submit=True,
                     predictor=RuntimePredictor(assist=False), obs=obs,
                     hooks=hooks)
    eng = res.engine
    return (tuple(sorted((j.job_id, j.first_start_time, j.finish_time)
                         for j in eng.completed)),
            (eng.decisions, eng.milp_calls, eng.backfills))


@pytest.fixture(scope="module")
def baseline():
    return _deep_stream()


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The deep stream under a CPU profiler session with a bundle
    recording: its schedule, its bundle, and the profile's events."""
    from jax.profiler import ProfileData
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs = Observability(name="t")
    windows = _WindowShapes()
    before = spans.traced_totals()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        sig = _deep_stream(obs, hooks=(windows,))
    finally:
        jax.profiler.stop_trace()
    totals = {n: (c - before.get(n, (0, 0))[0], v - before.get(n, (0, 0))[1])
              for n, (c, v) in spans.traced_totals().items()}
    events, start = _profile_events(log_dir, PARENTS)
    return {"sig": sig, "obs": obs, "events": events, "start_ns": start,
            "totals": totals, "windows": windows.windows}


def _profile_events(log_dir, names):
    """``[(name, start_ns, end_ns, metadata)]`` of the events named in
    ``names`` in the profile under ``log_dir``, and the profile's start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    events, start = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return events, start


def _parents(events):
    """Innermost enclosing program span of every event (None: none)."""
    out = []
    for k, (name, s, e, meta) in enumerate(events):
        best = None
        for j, (pn, ps, pe, pm) in enumerate(events):
            if j != k and ps <= s and e <= pe and (pe - ps) >= (e - s) \
                    and (best is None or pe - ps < best[2] - best[1]):
                best = (pn, ps, pe, pm)
        out.append(best)
    return out


def test_greedy_actor_is_one_dispatch_and_one_readback(tmp_path):
    """Inside each greedy ``rank.actor`` span the host rows and mask go
    straight to one ``greedy_step`` dispatch, and the order is read back
    once: no Python-side transfer (``DevicePutWithSharding``) and no
    indexing of the device array (``dynamic_slice``, ``squeeze``).  The
    CPU backend nests ``PjitFunction`` events, so a bare call in the same
    profile says how many one dispatch emits."""
    agent = PPOAgent()
    ov = np.zeros((MAX_QUEUE_SIZE, OV_SIZE), np.float32)
    ov[:100] = np.random.default_rng(0).random((100, OV_SIZE))
    mask = np.zeros((MAX_QUEUE_SIZE,), np.float32)
    mask[:100] = 1.0
    agent.act(ov, None, mask, explore=False, record=False)     # compiled
    log_dir = str(tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for _ in range(3):
            agent.act(ov, None, mask, explore=False, record=False)
        np.asarray(greedy_step(agent.params, ov, mask))
    finally:
        jax.profiler.stop_trace()
    dispatch = "PjitFunction(greedy_step)"
    events, _ = _profile_events(log_dir, {
        "rank.actor", dispatch, "PjitFunction(dynamic_slice)",
        "PjitFunction(squeeze)", "DevicePutWithSharding"})
    actors = [ev for ev in events if ev[0] == "rank.actor"]
    assert len(actors) == 3

    def inside(ev, outer):
        return outer[1] <= ev[1] and ev[2] <= outer[2]
    bare = [ev for ev in events if ev[0] == dispatch
            and not any(inside(ev, a) for a in actors)]
    assert bare
    for a in actors:
        got = collections.Counter(ev[0] for ev in events
                                  if ev is not a and inside(ev, a))
        assert got == {dispatch: len(bare)}


def test_off_opens_no_annotation_and_keeps_schedule(baseline, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)
    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert spans.span("engine.decide", decision=1) is spans.span("x")
    assert _deep_stream() == baseline
    assert made == []


def test_profiler_sees_every_span_with_its_parent(profiled, baseline):
    assert profiled["sig"] == baseline
    events = profiled["events"]
    assert {n for n, *_ in events} == set(PARENTS)
    for (name, s, e, meta), parent in zip(events, _parents(events)):
        got = parent[0] if parent is not None else None
        assert got in PARENTS[name], (name, got)


def test_decision_id_is_shared_by_the_decisions_spans(profiled, baseline):
    events = profiled["events"]
    decides = [ev for ev in events if ev[0] == "engine.decide"]
    assert [ev[3]["decision"] for ev in decides] \
        == list(range(1, baseline[1][0] + 1))
    assert all(ev[3]["window"] > 0 for ev in decides)
    backfills = [(ev, p) for ev, p in zip(events, _parents(events))
                 if ev[0] == "backfill"]
    assert backfills
    for (name, s, e, meta), parent in backfills:
        assert meta["decision"] == parent[3]["decision"]
        assert meta["tried"] >= meta["started"] >= 0
    scored = [(ev[3], p[3]) for ev, p in zip(events, _parents(events))
              if ev[0] == "rank.scorer"]
    assert scored
    for meta, decide in scored:
        assert meta["rows"] == decide["window"] - 256 > 0
        assert meta["bucket"] == 256


def test_feature_span_counts_rows_and_shapes(profiled):
    """Each ``rank.features`` span carries its window's rows and the
    number of distinct job shapes in it, the feature build's per-shape
    work."""
    feats = sorted((ev for ev in profiled["events"]
                    if ev[0] == "rank.features"), key=lambda ev: ev[1])
    got = [(meta["rows"], meta["shapes"]) for _, _, _, meta in feats]
    assert got == profiled["windows"]
    assert max(rows for rows, _ in got) > 256      # deep windows among them
    assert all(0 < shapes <= rows for rows, shapes in got)


def test_traced_totals_sum_the_profiles_events(profiled):
    """What the process sums under the session is what the profile holds:
    per span name the same calls, and the same seconds up to the few
    microseconds of entering and leaving each TraceMe."""
    totals = profiled["totals"]
    assert {n for n, (c, _) in totals.items() if c} == set(PARENTS)
    for name in PARENTS:
        evs = [e - s for n, s, e, _ in profiled["events"] if n == name]
        calls, secs = totals[name]
        assert calls == len(evs), name
        prof = sum(evs) * 1e-9
        assert abs(secs - prof) <= 0.02 * prof + 20e-6 * calls, name


def test_bundle_and_profile_share_the_clock(profiled):
    """The same spans in the bundle's Chrome export and in the profile:
    same count, start times within 1 ms on the profiler's host clock."""
    doc = profiled["obs"].trace_document()
    assert validate_trace(doc) == []
    origin = doc["otherData"]["clock_origin_ns"]
    chrome = [origin + ev["ts"] * 1e3 for ev in doc["traceEvents"]
              if ev.get("name") == "engine.decide"]
    prof = [profiled["start_ns"] + s for n, s, *_ in profiled["events"]
            if n == "engine.decide"]
    assert len(chrome) == len(prof) > 0
    assert max(abs(a - b) for a, b in zip(chrome, prof)) < 1e6


def test_bundle_counts_spans_and_report_still_reads_audit(profiled,
                                                         baseline):
    obs = profiled["obs"]
    reg = obs.merged_registry()
    decisions = baseline[1][0]
    assert reg.value("repro_span_calls_total",
                     span="engine.decide") == decisions
    assert reg.value("repro_span_seconds_total", span="rank.actor") > 0
    doc = obs.trace_document()
    args = [ev["args"] for ev in doc["traceEvents"]
            if ev.get("name") == "rank.features"]
    assert args and all(a["parent"] == "engine.decide" for a in args)
    model = analyze(doc)
    assert sum(model["path_counts"].values()) == decisions
    assert model["alloc_counts"]


def test_recording_sink_gets_nesting_and_late_metadata():
    got = []

    class Sink:
        def record_span(self, name, start_ns, end_ns, parent, meta):
            got.append((name, end_ns >= start_ns, parent, dict(meta)))

    with spans.recording(Sink()):
        with spans.span("outer", decision=7) as outer:
            with spans.span("inner"):
                pass
            outer.set(tried=3)
    assert got == [("inner", True, "outer", {}),
                   ("outer", True, None, {"decision": 7, "tried": 3})]
    assert spans.span("after") is spans.span("after")   # detached again


def test_tracer_has_no_private_wall_origin():
    obs = Observability(name="t")
    assert not hasattr(obs.tracer, "_wall0")
    assert not hasattr(obs, "wall_elapsed_s")
    t0 = spans.clock_ns()
    with obs.recording(), spans.span("probe"):
        pass
    doc = obs.trace_document()
    (ev,) = [e for e in doc["traceEvents"] if e.get("name") == "probe"]
    assert doc["otherData"]["clock_origin_ns"] == spans.ORIGIN_NS
    assert abs(spans.ORIGIN_NS + ev["ts"] * 1e3 - t0) < 1e6


@pytest.fixture(scope="module")
def quota_profiled(tmp_path_factory):
    """The PAI-shaped deep stream under the quota gate, profiled: its
    schedule, the plain partition's work per decision, and the profile's
    ``engine.decide`` and ``rank.quota`` events."""
    log_dir = str(tmp_path_factory.mktemp("quota_profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        sig, seen = pai_quota_stream()
    finally:
        jax.profiler.stop_trace()
    events, _ = _profile_events(log_dir, {"engine.decide", "rank.quota"})
    return {"sig": sig, "windows": [w[1:] for w in seen], "events": events}


def test_quota_span_counts_rows_and_demotions(quota_profiled):
    """One ``rank.quota`` span per ranking, inside its ``engine.decide``:
    ``rows`` is the window, ``demoted`` the rows the plain partition puts
    behind the rest, ``over`` the VCs over quota."""
    events = quota_profiled["events"]
    gates = sorted((ev for ev in events if ev[0] == "rank.quota"),
                   key=lambda ev: ev[1])
    got = [(m["rows"], m["demoted"], m["over"]) for *_, m in gates]
    assert got == quota_profiled["windows"]
    assert len(got) == quota_profiled["sig"][1][0]     # every decision
    assert max(rows for rows, _, _ in got) == PAI_WINDOW
    assert sum(1 for _, demoted, _ in got if demoted) > len(got) // 2
    for (name, *_), parent in zip(events, _parents(events)):
        if name == "rank.quota":
            assert parent[0] == "engine.decide"


def test_quota_spans_off_keep_the_schedule(quota_profiled):
    assert pai_quota_stream()[0] == quota_profiled["sig"]
