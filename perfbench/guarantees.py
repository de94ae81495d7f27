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
  running then have freed enough GPUs of the head's type.

The configurations' CPU and memory requests scale with the GPU count and
fit any node whose GPUs fit, so GPU capacity is the binding one.
"""
from __future__ import annotations

import heapq
import math

KINDS = ("gang", "capacity", "order", "reservation")


def node_table(cluster: dict) -> tuple[list, list, list]:
    """(GPU type, GPU count, speed) of every node, in node-id order."""
    types, gpus, speeds = [], [], []
    for grp in cluster["node_groups"]:
        for _ in range(int(grp["count"])):
            types.append(grp["gpu_type"])
            gpus.append(int(grp["gpus"]))
            speeds.append(float(grp["speed"]))
    return types, gpus, speeds


def violations(cluster: dict, cols: dict, starts: list,
               decisions: list) -> tuple[dict, dict]:
    """Count the starts that break each guarantee; also returns how many
    starts, and backfill starts among them, were checked.

    ``starts``: ``(instant, job_id, ((node, gpus), ...))`` in the order the
    jobs started; ``decisions``: ``(instant, head_job_id, n)`` in order,
    where ``n`` is how many starts had happened before the decision."""
    types, total, speeds = node_table(cluster)
    free = list(total)
    gpus = [int(g) for g in cols["gpus"]]
    want = [str(t) for t in cols["gpu_type"]]
    runtime = [float(r) for r in cols["runtime"]]
    submit = [float(s) for s in cols["submit"]]
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
            _, _, pl = heapq.heappop(running)
            for i, g in pl:
                free[i] += g
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
            speed = max(min(speeds[i] for i, _ in pl), 1e-3)
            heapq.heappush(running, (t + runtime[j] / speed, j, pl))
    return bad, {"starts": len(starts), "backfills": backfills}
