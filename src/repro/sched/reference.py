"""The seed's decision loop, kept as the reference the engine is held to.

``ReferenceEngine`` overrides only the decision loop: the pending queue is
a plain list re-sorted per decision and searched linearly, the cluster
caches nothing, each backfill reservation builds a fresh ``ClusterState``
and sorts the running set, and the window is ranked through the
prioritizer's ``rank``.  Events, faults, lifecycle operations, hooks,
counters and ``save_state`` are ``SchedulerEngine``'s, so a differential
test compares the two decision loops and nothing else.
"""
from __future__ import annotations

import math
from typing import Iterable

from repro.core.cluster import ClusterState
from repro.core.types import ClusterSpec, Job, JobState
from repro.sched.engine import (EngineHooks, MultiHooks, Prioritizer,
                                SchedulerEngine)
from repro.sched.service import QuotaPrioritizer


class ReferenceEngine(SchedulerEngine):
    """``SchedulerEngine`` with the seed's naive decision loop: re-sort and
    linear scans, no feasibility cache, no pending-field index.  Schedules
    bit-identically to the engine (pinned by the differential tests)."""

    optimized = False

    def __init__(self, spec: ClusterSpec, prioritizer: Prioritizer, **kw):
        super().__init__(spec, prioritizer, **kw)
        self.cluster = ClusterState(spec, cache=False)
        self._pindex = None

    # ------------------------------------------------------ pending queue ----
    def _push_pending(self, job: Job) -> None:
        self.pending.append(job)

    def _remove_pending(self, job: Job) -> None:
        self.pending.remove(job)

    # ------------------------------------------------------ decision loop ----
    def _earliest_start(self, job: Job) -> float:
        """Seed implementation: fresh ClusterState (four array allocations)
        per reservation, running set sorted by finish time per call."""
        cluster = self.cluster
        sim = ClusterState(self.spec)
        sim.free_gpus = cluster.free_gpus.copy()
        sim.free_cpus = cluster.free_cpus.copy()
        sim.free_mem = cluster.free_mem.copy()
        sim.node_down = cluster.node_down.copy()
        sim.cordoned = cluster.cordoned.copy()
        sim.retired = cluster.retired.copy()
        if sim.find_placement(job, "pack") is not None:
            return self.now
        for jid, (rj, pl, st, fin, sp) in sorted(self.running.items(),
                                                 key=lambda kv: kv[1][3]):
            sim.release(rj, pl)
            if sim.find_placement(job, "pack") is not None:
                return fin
        return float("inf")

    def _any_schedulable(self, queue: list[Job]) -> bool:
        cluster = self.cluster
        up = cluster.placeable_mask()
        free_any = int(cluster.free_gpus[up].sum())
        if free_any == 0:
            return False
        free_by_type: dict[str, int] = {}
        for i, t in enumerate(cluster.gpu_types):
            if up[i]:
                free_by_type[t] = free_by_type.get(t, 0) + int(cluster.free_gpus[i])
        for j in queue:
            avail = free_any if j.gpu_type == "any" \
                else free_by_type.get(j.gpu_type, 0)
            if avail >= j.num_gpus and cluster.can_schedule_now(j):
                return True
        return False

    def _schedule_pass(self) -> None:
        """Seed decision loop: full re-sort + linear `.remove()` per
        decision."""
        cluster, prioritizer = self.cluster, self.prioritizer
        while self.pending:
            self.pending.sort(key=lambda j: (j.submit_time, j.job_id))
            queue = self.pending[: self.queue_window]
            if not self._any_schedulable(queue):
                return
            if self._fcfs_degraded():
                order = list(range(len(queue)))
            else:
                order = prioritizer.rank(queue, cluster, self.now)
            self.decisions += 1
            if self.hooks:
                self._fire_decision(queue, order)
            top = queue[order[0]]
            rest = [queue[i] for i in order[1:1 + self.lookahead_k]]
            placement = self._alloc_for(top, rest,
                                        self._lookahead_durations(rest))
            if placement is not None:
                self.pending.remove(top)
                self._start_job(top, placement)
                continue
            if not self.backfill:
                return
            # EASY backfill under reservation for `top`
            t_res = self._earliest_start(top)
            progressed = False
            pred = self._predict_assist()
            for i in order[1:]:
                cand = queue[i]
                if cand.state != JobState.PENDING or cand is top:
                    continue
                if pred is not None:
                    if cand.job_id in self._bf_overrun_jobs:
                        continue
                    rt = max(float(pred.reserve_runtime(cand, self)), 1.0)
                else:
                    rt = self._est_rt(cand)
                if self.now + rt > t_res:
                    continue
                pl = self._alloc_for(cand, [])
                if pl is not None:
                    self.pending.remove(cand)
                    self._start_job(cand, pl)
                    self.backfills += 1
                    progressed = True
                    if pred is not None and t_res < math.inf:
                        self.bf_reservations += 1
                        self._bf_deadlines[cand.job_id] = t_res
                        note = getattr(pred, "note_reservation", None)
                        if note is not None:
                            note(t_res - (self.now + rt))
            if not progressed:
                return
            # after backfills the reserved job may now fit; loop again
            if not cluster.can_schedule_now(top):
                return


def reference_run(spec: ClusterSpec, jobs: list[Job],
                  prioritizer: Prioritizer, *, predictor=None,
                  hooks: Iterable[EngineHooks] = (),
                  **engine_kw) -> ReferenceEngine:
    """Run ``jobs`` to completion on a fresh ``ReferenceEngine`` wired as
    ``service.run_stream`` wires the engine: ``hooks``, then the predictor,
    then a quota gate (its usage reset) composed through one
    ``MultiHooks``.  ``engine_kw`` goes to the engine (allocator, window,
    fault model, ...).  Returns the finished engine.

    Windowed stepping equals one drain, so this is the schedule
    ``run_stream`` must give with the whole stream submitted up front, and
    with chunked submission too unless an assisting predictor is attached
    (it caches each job's features at submission)."""
    children = list(hooks)
    if predictor is not None:
        children.append(predictor)
    if isinstance(prioritizer, QuotaPrioritizer):
        prioritizer.reset_usage()
        children.append(prioritizer)
    engine = ReferenceEngine(
        spec, prioritizer, hooks=(MultiHooks(*children),) if children else (),
        predictor=predictor, **engine_kw)
    engine.submit(jobs)
    engine.run_until_complete()
    return engine
