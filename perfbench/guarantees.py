"""The guarantees a configuration states, checked over the schedule a run
produced, independently of the program.

Reads only the benchmark's own records: the stream's columns (each job's
GPU count, GPU type, runtime and submit instant, as the generator made
them), the configuration's node groups, and what the harness's hooks
logged (which job started when and on which nodes; which job each
scheduling decision ranked first).  It replays the cluster's occupancy
from t = 0 with its own arithmetic (a job placed on nodes of speeds s
runs ``runtime / min(s)`` seconds; the configurations have no faults and
no preemption) and counts every start that breaks one of:

- ``gang``: the placement gives the job exactly its GPU count, on
  existing nodes of the requested type (any node for ``"any"``);
- ``capacity``: no node ever holds more GPUs than it has;
- ``order``: a job starts once, not before its submit instant, and only
  within a scheduling decision at that instant; a decision whose first
  ranked job (the head) starts starts nothing else;
- ``reservation`` (EASY backfill): a start within a decision whose head
  did not start ends, by its runtime at speed 1 (at least 1 s), no later
  than the head's reservation: the earliest instant at which the jobs
  running then have freed enough GPUs of the head's type;
- ``quota`` (where the scheduler settings state ``vc_quotas``, a share of
  the slice's GPUs per virtual cluster): a VC is over its quota at a
  decision when its running GPUs over the slice's total exceed the share;
  a decision whose head belongs to a VC over its quota breaks it when its
  window, the first ``queue_window`` jobs submitted by then and not
  started, in submit order, holds a job of a VC that is not.  This one
  counts decisions, not starts.

The configurations' CPU and memory requests scale with the GPU count and
fit any node whose GPUs fit, so GPU capacity is the binding one.
"""
from __future__ import annotations

import heapq
import math

KINDS = ("gang", "capacity", "order", "reservation", "quota")


def node_table(cluster: dict) -> tuple[list, list, list]:
    """(GPU type, GPU count, speed) of every node, in node-id order."""
    types, gpus, speeds = [], [], []
    for grp in cluster["node_groups"]:
        for _ in range(int(grp["count"])):
            types.append(grp["gpu_type"])
            gpus.append(int(grp["gpus"]))
            speeds.append(float(grp["speed"]))
    return types, gpus, speeds


class Tenancy:
    """The quota guarantee's view of the replay: running GPUs per VC, and
    the jobs submitted and not started, in job-id (submit) order.  A
    Fenwick tree over job ids counts the waiting jobs before any id, and a
    list per VC of its waiting ids (ascending; started ones skipped
    lazily) gives the VC's first, so whether a VC has a job in the window
    costs O(log n) a decision."""

    def __init__(self, vc: list, gpus: list, submit: list, slice_gpus: int,
                 quotas: dict, window: int):
        self.vc = vc
        self.gpus = gpus
        self.submit = submit
        self.total = max(slice_gpus, 1)
        self.quotas = quotas
        self.window = window
        self.used = dict.fromkeys(set(self.vc) | set(quotas), 0)
        self.waiting = {v: [] for v in self.used}
        self.front = dict.fromkeys(self.used, 0)
        #: 0 not yet submitted, 1 waiting, 2 started
        self.state = bytearray(len(self.vc))
        self.tree = [0] * (len(self.vc) + 1)
        self.fed = 0
        self.decisions = 0

    def _add(self, j: int, d: int) -> None:
        i = j + 1
        while i < len(self.tree):
            self.tree[i] += d
            i += i & -i

    def _before(self, j: int) -> int:
        """Waiting jobs with an id below ``j``."""
        n, i = 0, j
        while i > 0:
            n += self.tree[i]
            i -= i & -i
        return n

    def start(self, j: int) -> None:
        if self.state[j] == 1:
            self._add(j, -1)
        self.state[j] = 2
        self.used[self.vc[j]] += self.gpus[j]

    def finish(self, j: int) -> None:
        self.used[self.vc[j]] -= self.gpus[j]

    def _first(self, v: int) -> int | None:
        ids, k = self.waiting[v], self.front[v]
        while k < len(ids) and self.state[ids[k]] != 1:
            k += 1
        self.front[v] = k
        return ids[k] if k < len(ids) else None

    def breaks(self, now: float, head: int) -> bool:
        """Whether the decision at ``now`` that ranked ``head`` first
        breaks the guarantee; counts the decisions at which a VC over its
        quota had a job in the window."""
        while self.fed < len(self.submit) and self.submit[self.fed] <= now:
            j = self.fed
            self.fed += 1
            if self.state[j] == 0:
                self.state[j] = 1
                self._add(j, 1)
                self.waiting[self.vc[j]].append(j)
        # the program's gate: used / total > quota
        over = {v for v, q in self.quotas.items()
                if self.used[v] / self.total > q}
        if not over:
            return False
        in_window = {v for v in self.used
                     if (f := self._first(v)) is not None
                     and self._before(f) < self.window}
        if in_window & over:
            self.decisions += 1
        return self.vc[head] in over and bool(in_window - over)


def violations(cluster: dict, cols: dict, starts: list, decisions: list,
               scheduler: dict | None = None) -> tuple[dict, dict]:
    """Count the starts (and, for ``quota``, the decisions) that break each
    guarantee; also returns how many starts, and backfill starts among
    them, were checked, and at how many decisions a VC over its quota had
    a job in the window (``quota_decisions``).

    ``starts``: ``(instant, job_id, ((node, gpus), ...))`` in the order the
    jobs started; ``decisions``: ``(instant, head_job_id, n)`` in order,
    where ``n`` is how many starts had happened before the decision;
    ``scheduler``: the configuration's scheduler settings (``vc_quotas``,
    ``queue_window``)."""
    types, total, speeds = node_table(cluster)
    free = list(total)
    gpus = [int(g) for g in cols["gpus"]]
    want = [str(t) for t in cols["gpu_type"]]
    runtime = [float(r) for r in cols["runtime"]]
    submit = [float(s) for s in cols["submit"]]
    quotas = (scheduler or {}).get("vc_quotas")
    tenancy = Tenancy(
        [int(v) for v in cols["vc"]], gpus, submit, sum(total),
        {int(v): float(q) for v, q in quotas.items()},
        int(scheduler["queue_window"])) if quotas else None
    bad = dict.fromkeys(KINDS, 0)
    running: list[tuple] = []          # heap of (finish, job_id, placement)
    started: set[int] = set()
    backfills = 0

    def eligible(j):
        return [i for i, ty in enumerate(types)
                if want[j] == "any" or ty == want[j]]

    def reservation(head, now):
        nodes = eligible(head)
        have = sum(free[i] for i in nodes)
        if have >= gpus[head]:
            return now
        elig = set(nodes)
        for fin, _, pl in sorted(running):
            have += sum(g for i, g in pl if i in elig)
            if have >= gpus[head]:
                return fin
        return math.inf

    bad["order"] += decisions[0][2] if decisions else len(starts)
    bounds = [d[2] for d in decisions[1:]] + [len(starts)]
    for (now, head, lo), hi in zip(decisions, bounds):
        while running and running[0][0] <= now:
            _, j, pl = heapq.heappop(running)
            for i, g in pl:
                free[i] += g
            if tenancy is not None:
                tenancy.finish(j)
        if tenancy is not None and tenancy.breaks(now, head):
            bad["quota"] += 1
        head_starts = lo < hi and starts[lo][1] == head
        t_res = None if head_starts or lo == hi else reservation(head, now)
        for k in range(lo, hi):
            t, j, pl = starts[k]
            if (t != now or j in started or t < submit[j]
                    or (head_starts and k > lo)):
                bad["order"] += 1
            started.add(j)
            if (sum(g for _, g in pl) != gpus[j]
                    or any(not 0 <= i < len(types) or g <= 0
                           or (want[j] != "any" and types[i] != want[j])
                           for i, g in pl)):
                bad["gang"] += 1
                continue
            if any(free[i] < g for i, g in pl):
                bad["capacity"] += 1
            if t_res is not None:
                backfills += 1
                if t + max(runtime[j], 1.0) > t_res:
                    bad["reservation"] += 1
            for i, g in pl:
                free[i] -= g
            if tenancy is not None:
                tenancy.start(j)
            speed = max(min(speeds[i] for i, _ in pl), 1e-3)
            heapq.heappush(running, (t + runtime[j] / speed, j, pl))
    return bad, {"starts": len(starts), "backfills": backfills,
                 "quota_decisions": tenancy.decisions if tenancy else 0}
