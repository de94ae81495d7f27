"""Queue-prioritizer interface shared by the batch simulator and the
streaming engine (leaf module: keeps repro.core <-> repro.sched acyclic)."""
from __future__ import annotations

from typing import Protocol, Sequence

import numpy as np

from repro.core.cluster import ClusterState, _job_shape
from repro.core.policies import Policy
from repro.core.types import Job


class WindowFields:
    """Contiguous float64 arrays of the hot job fields for one ranking
    window, aligned index-for-index with the job list handed to ``rank``.

    The streaming engine maintains these arrays incrementally alongside its
    indexed pending queue and passes O(1) views per decision, so batch
    scoring never re-gathers Python attributes.  Arrays are read-only by
    convention; integer-valued fields (``num_gpus``, ``user``, ``vc``,
    ``job_id``) are stored as float64 — exact for any realistic value
    (< 2**53), and float keys hash/compare equal to the original ints so
    dict-based policy state (fair-share usage, runtime history) stays
    collision-free.

    ``shape_id`` is each row's id in ``shape_keys``, the interned table of
    ``_job_shape`` keys ``(num_gpus, gpu_type, req_cpus, req_mem_gb)``.
    Everything that depends on a job only through its shape (placement
    ways, SKU, CPU and memory requests) is then computed once per distinct
    shape present and gathered to the rows with numpy; no per-row Python
    runs over the window.
    """

    __slots__ = ("submit_time", "runtime", "est_runtime", "num_gpus",
                 "user", "vc", "shape_id", "job_id", "shape_keys",
                 "_present")

    def __init__(self, submit_time: np.ndarray, runtime: np.ndarray,
                 est_runtime: np.ndarray, num_gpus: np.ndarray,
                 user: np.ndarray, vc: np.ndarray, shape_id: np.ndarray,
                 job_id: np.ndarray, shape_keys: Sequence[tuple]):
        self.submit_time = submit_time
        self.runtime = runtime
        self.est_runtime = est_runtime
        self.num_gpus = num_gpus
        self.user = user
        self.vc = vc
        self.shape_id = shape_id
        self.job_id = job_id
        self.shape_keys = shape_keys
        self._present = None

    @classmethod
    def from_jobs(cls, jobs: list[Job]) -> "WindowFields":
        ids: dict[tuple, int] = {}
        sid = [ids.setdefault(_job_shape(j), len(ids)) for j in jobs]
        return cls(
            np.array([j.submit_time for j in jobs], dtype=np.float64),
            np.array([j.runtime for j in jobs], dtype=np.float64),
            np.array([j.est_runtime for j in jobs], dtype=np.float64),
            np.array([j.num_gpus for j in jobs], dtype=np.float64),
            np.array([j.user for j in jobs], dtype=np.float64),
            np.array([j.vc for j in jobs], dtype=np.float64),
            np.array(sid, dtype=np.float64),
            np.array([j.job_id for j in jobs], dtype=np.float64),
            list(ids),
        )

    def take(self, indices: list[int]) -> "WindowFields":
        """Row-subset copy for wrapper prioritizers that rank a partition
        of the window (e.g. the non-SLA lane) through their base."""
        ix = np.asarray(indices, dtype=np.intp)
        return WindowFields(self.submit_time[ix], self.runtime[ix],
                            self.est_runtime[ix], self.num_gpus[ix],
                            self.user[ix], self.vc[ix], self.shape_id[ix],
                            self.job_id[ix], self.shape_keys)

    def present_shapes(self) -> np.ndarray:
        """Sorted ids of the shapes present in the window (computed once
        per view)."""
        if self._present is None:
            self._present = np.flatnonzero(
                np.bincount(self.shape_id.astype(np.intp)))
        return self._present


class Prioritizer(Protocol):
    """Ranks the pending queue; index 0 = schedule first.

    Implementations may additionally expose
    ``rank_window(jobs, cluster, now, fields)`` accepting a
    :class:`WindowFields`; the engine uses it when present and falls back
    to ``rank`` otherwise (wrapper prioritizers that reorder sublists keep
    working unchanged).  ``rank`` returns a list; ``rank_window`` may
    return the permutation as an ``np.intp`` array instead (the RL
    prioritizer and the quota gate do), which the engine and its hooks
    index and never mutate as a list."""

    use_estimates: bool

    def rank(self, jobs: list[Job], cluster: ClusterState, now: float) -> list[int]: ...
    def observe_finish(self, job: Job) -> None: ...


def _order(scores: np.ndarray) -> list[int]:
    """Stable lowest-score-first permutation of a float64 score array."""
    # a stable argsort of a non-decreasing array is the identity
    # permutation — the engine's window arrives sorted by
    # (submit_time, job_id), so e.g. FCFS always takes this exit
    if scores.size > 1 and bool((scores[1:] >= scores[:-1]).all()):
        return list(range(scores.size))
    # .tolist() materializes plain ints ~2x faster than list()
    return np.argsort(scores, kind="stable").tolist()


class PolicyPrioritizer:
    """Adapter: a Table-5 policy as a Prioritizer (lowest score first).

    Scores the window with one ``policy.score_batch`` call over contiguous
    job-field arrays when the policy provides it (all built-in policies do,
    bit-identical to the scalar loop); ``batch=False`` forces the per-job
    ``policy.score`` loop — the retained naive reference path used by the
    differential equivalence tests.
    """

    def __init__(self, policy: Policy, batch: bool = True):
        self.policy = policy
        self.use_estimates = getattr(policy, "use_estimates", False)
        self.batch = batch and hasattr(policy, "score_batch")

    def rank(self, jobs: list[Job], cluster: ClusterState, now: float) -> list[int]:
        if self.batch:
            return _order(self.policy.score_batch(jobs, now))
        scores = [self.policy.score(j, now) for j in jobs]
        return list(np.argsort(scores, kind="stable"))

    def rank_window(self, jobs: list[Job], cluster: ClusterState, now: float,
                    fields: WindowFields | None) -> list[int]:
        """``rank`` with engine-maintained contiguous field arrays."""
        if self.batch:
            return _order(self.policy.score_batch(jobs, now, fields))
        return self.rank(jobs, cluster, now)

    def observe_finish(self, job: Job) -> None:
        self.policy.observe_finish(job)
