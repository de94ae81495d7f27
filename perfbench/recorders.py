"""What the harness attaches to the program under test.

- ``Sampler``: wraps the three device entry points of the decision path
  (the actor's ``act``, the deep scorer's ``score`` and the runtime
  predictor's batched forward) and keeps a seeded reservoir sample of
  their inputs and outputs while the measured window is open.  The
  reference comparison reads these samples after the window.
- ``StartLog``: engine hook that logs every job start (the schedule's
  answers) with its instant and placement, and the job each scheduling
  decision ranked first; ``TickLog`` marks the counters and the start log
  after every event batch of the window.
- ``GcClock``: the garbage collector's passes inside the window.
- ``LayerClock``: engine hook for traced runs; receives the engine's
  gated audit stream (ranking wall time per decision, allocation wall
  time per placement).
- ``TracedPrioritizer``: wraps the prioritizer in traced runs so that each
  ranking shows as a ``rank`` span on the profiler's clock, and counts the
  deep-scorer rows (queue rows beyond the actor's window).
"""
from __future__ import annotations

import random
import time

import numpy as np


class Reservoir:
    """Uniform sample of at most ``k`` items from a stream (Algorithm R),
    drawn from a seeded generator."""

    def __init__(self, k: int, rng: random.Random):
        self.k = k
        self.rng = rng
        self.seen = 0
        self.items: list = []

    def slot(self) -> int | None:
        """Slot for the next stream item, or None when it is not kept."""
        n = self.seen
        self.seen += 1
        if n < self.k:
            self.items.append(None)
            return n
        j = self.rng.randrange(n + 1)
        return j if j < self.k else None


class Sampler:
    """Reservoir samples of the device path's inputs and outputs.

    The wrappers are installed as instance attributes, so ``detach``
    leaves each object exactly as the program built it (needed before the
    engine's state is pickled)."""

    def __init__(self, agent, scorer, predictor, seed: int, k: dict):
        rng = random.Random(seed)
        self.actor = Reservoir(k["actor"], rng)
        self.scorer = Reservoir(k["scorer"], rng)
        self.predictor = Reservoir(k["predictor"], rng)
        self.active = False
        self._targets = [(agent, "act", self._wrap_act(agent.act)),
                         (scorer, "score", self._wrap_score(scorer.score)),
                         (predictor, "_forward",
                          self._wrap_forward(predictor, predictor._forward))]

    def attach(self) -> None:
        for obj, name, fn in self._targets:
            setattr(obj, name, fn)

    def detach(self) -> None:
        for obj, name, _ in self._targets:
            obj.__dict__.pop(name, None)

    def _wrap_act(self, act):
        def recording_act(ov, cv, mask, explore=True, record=True):
            action, logits = act(ov, cv, mask, explore=explore, record=record)
            if self.active:
                s = self.actor.slot()
                if s is not None:
                    self.actor.items[s] = (np.array(ov), np.array(mask),
                                           int(action), np.array(logits))
            return action, logits
        return recording_act

    def _wrap_score(self, score):
        def recording_score(feats):
            out = score(feats)
            if self.active:
                s = self.scorer.slot()
                if s is not None:
                    self.scorer.items[s] = (np.array(feats), np.array(out))
            return out
        return recording_score

    def _wrap_forward(self, predictor, forward):
        def recording_forward(x):
            out = forward(x)
            if self.active:
                s = self.predictor.slot()
                if s is not None:
                    params = {k: np.array(v)
                              for k, v in predictor.mlp.params.items()}
                    self.predictor.items[s] = (np.array(x), np.array(out),
                                               params)
            return out
        return recording_forward


def counters(eng) -> tuple:
    """(decisions, MILP solves, MILP fallbacks, backfills) of an engine."""
    return (eng.decisions, eng.milp_calls, eng.milp_fallbacks, eng.backfills)


class StartLog:
    """Engine hook: ``(instant, job_id, placement)`` of every job start,
    and ``(instant, head_job_id, starts_before)`` of every scheduling
    decision."""

    def __init__(self):
        self.starts: list[tuple] = []
        self.decisions: list[tuple] = []

    def on_start(self, job, now):
        self.starts.append((now, job.job_id,
                            tuple(sorted((job.placement or {}).items()))))

    def on_decision(self, jobs, order, now, engine):
        self.decisions.append((now, jobs[order[0]].job_id, len(self.starts)))


class TickLog:
    """Engine hook: the counters and the length of ``start_log`` after
    each event batch the engine processes while ``active``."""

    def __init__(self, start_log: StartLog):
        self.start_log = start_log
        self.active = False
        self.ticks: list[tuple] = []

    def on_tick(self, now, engine):
        if self.active:
            self.ticks.append((counters(engine), len(self.start_log.starts)))


class GcClock:
    """The garbage collector's passes while ``active``: count and seconds
    per generation, and the longest pass."""

    def __init__(self):
        import gc
        self.active = False
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.longest = 0.0
        self._t0 = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if not self.active:
            self._t0 = None
        elif phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += d
            self.longest = max(self.longest, d)


class LayerClock:
    """Engine hook for the gated audit stream (traced runs only: attaching
    it makes the engine read the clock around ranking and allocation)."""

    def __init__(self):
        self.active = False
        self.rank_s = 0.0
        self.decisions = 0
        self.alloc_s = 0.0
        self.placements = 0

    def on_decision_audit(self, rec):
        if self.active:
            self.rank_s += rec["rank_wall_s"]
            self.decisions += 1

    def on_alloc(self, job, placement, now, wall_s, path):
        if self.active:
            self.alloc_s += wall_s
            self.placements += 1


class TracedPrioritizer:
    """Prioritizer wrapper for traced runs: each ranking is a ``rank``
    span on the profiler's clock.  Counts the rows the deep scorer is
    handed (queue rows beyond the actor's ``head`` window)."""

    def __init__(self, base, head: int):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.base = base
        self.head = head
        self.use_estimates = base.use_estimates
        self.active = False
        self.tail_calls = 0
        self.tail_rows = 0

    def _count(self, jobs) -> None:
        if self.active and len(jobs) > self.head:
            self.tail_calls += 1
            self.tail_rows += len(jobs) - self.head

    def rank_window(self, jobs, cluster, now, fields):
        self._count(jobs)
        with self._annotation("rank"):
            return self.base.rank_window(jobs, cluster, now, fields)

    def observe_finish(self, job):
        self.base.observe_finish(job)
