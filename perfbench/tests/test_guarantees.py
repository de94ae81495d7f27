"""The guarantee checker on hand-made schedules: a sound one passes, and
each broken guarantee is counted under its own kind."""
import numpy as np
import pytest

import guarantees

CLUSTER = {"node_groups": [
    {"count": 2, "gpu_type": "P100", "gpus": 4, "speed": 1.0},
    {"count": 1, "gpu_type": "V100", "gpus": 8, "speed": 1.5}]}

# job: gpus, type, runtime, submit
JOBS = [(4, "P100", 100.0, 0.0),    # 0
        (4, "P100", 300.0, 0.0),    # 1
        (8, "P100", 50.0, 0.0),     # 2: needs both P100 nodes
        (2, "V100", 30.0, 0.0),     # 3
        (4, "any", 500.0, 10.0)]    # 4


def cols():
    g, ty, rt, sub = zip(*JOBS)
    return {"gpus": np.array(g), "gpu_type": np.array(ty),
            "runtime": np.array(rt), "submit": np.array(sub)}


def sound():
    """Jobs 0 and 1 fill the P100s; job 2 is the head at t=0 and its
    reservation is t=300 (job 1 ends); job 3 backfills (ends by t=30);
    job 4 takes the V100 node at 10; job 2 starts at 300."""
    starts = [(0.0, 0, ((0, 4),)), (0.0, 1, ((1, 4),)),
              (0.0, 3, ((2, 2),)), (10.0, 4, ((2, 4),)),
              (300.0, 2, ((0, 4), (1, 4)))]
    decisions = [(0.0, 0, 0), (0.0, 1, 1), (0.0, 2, 2), (10.0, 4, 3),
                 (300.0, 2, 4)]
    return starts, decisions


def test_sound_schedule_passes():
    starts, decisions = sound()
    bad, seen = guarantees.violations(CLUSTER, cols(), starts, decisions)
    assert bad == dict.fromkeys(guarantees.KINDS, 0)
    assert seen == {"starts": 5, "backfills": 1, "quota_decisions": 0}


def broken_capacity(s, d):
    s[4] = (100.0, 2, ((0, 4), (1, 4)))        # job 1 still holds node 1
    d[4] = (100.0, 2, 4)


def broken_type(s, d):
    s[2] = (0.0, 3, ((0, 2),))                 # V100 job on a P100 node


def broken_count(s, d):
    s[2] = (0.0, 3, ((2, 1),))                 # one GPU of two


def broken_order(s, d):
    s[1] = (0.0, 0, ((1, 4),))                 # job 0 twice


def broken_early(s, d):
    s[3] = (0.0, 4, ((2, 4),))                 # before its submit instant
    d[3] = (0.0, 4, 3)


@pytest.mark.parametrize("fault,kind", [
    (broken_capacity, "capacity"), (broken_type, "gang"),
    (broken_count, "gang"), (broken_order, "order"),
    (broken_early, "order")])
def test_each_broken_guarantee_is_counted(fault, kind):
    starts, decisions = sound()
    fault(starts, decisions)
    bad, _ = guarantees.violations(CLUSTER, cols(), starts, decisions)
    assert bad[kind] >= 1, bad


def test_backfill_past_the_reservation_is_counted():
    starts, decisions = sound()
    c = cols()
    c["runtime"][3] = 350.0          # job 3 now ends after t=300
    bad, _ = guarantees.violations(CLUSTER, c, starts, decisions)
    assert bad["reservation"] == 1, bad
    assert sum(bad.values()) == 1


def test_start_outside_a_decision_is_counted():
    starts, decisions = sound()
    bad, _ = guarantees.violations(CLUSTER, cols(), starts, decisions[1:])
    assert bad["order"] >= 1, bad


# quota: job: vc, gpus, type, runtime, submit; the slice has 16 GPUs
QUOTA_JOBS = [(0, 8, "V100", 1000.0, 0.0),   # 0: VC 0 at 8/16 once started
              (0, 1, "P100", 100.0, 10.0),   # 1: VC 0 again
              (1, 4, "P100", 100.0, 10.0)]   # 2: VC 1, under its quota
QUOTAS = {"vc_quotas": {"0": 0.25, "1": 0.25}, "queue_window": 2}


def quota_cols():
    vc, g, ty, rt, sub = zip(*QUOTA_JOBS)
    return {"vc": np.array(vc), "gpus": np.array(g), "gpu_type": np.array(ty),
            "runtime": np.array(rt), "submit": np.array(sub)}


def quota_schedule(first, second):
    """Job 0 starts at t=0; at t=10 two decisions start ``first`` then
    ``second`` (each the head of its decision)."""
    place = {1: ((0, 1),), 2: ((1, 4),)}
    starts = [(0.0, 0, ((2, 8),)), (10.0, first, place[first]),
              (10.0, second, place[second])]
    decisions = [(0.0, 0, 0), (10.0, first, 1), (10.0, second, 2)]
    return starts, decisions


def test_quota_gate_order_passes():
    bad, seen = guarantees.violations(CLUSTER, quota_cols(),
                                      *quota_schedule(2, 1), QUOTAS)
    assert bad == dict.fromkeys(guarantees.KINDS, 0)
    assert seen["quota_decisions"] == 2


def test_over_quota_head_before_a_waiting_under_quota_job_is_counted():
    bad, seen = guarantees.violations(CLUSTER, quota_cols(),
                                      *quota_schedule(1, 2), QUOTAS)
    assert bad["quota"] == 1, bad
    assert sum(bad.values()) == 1
    assert seen["quota_decisions"] == 1


def test_under_quota_job_beyond_the_window_is_not_counted():
    bad, seen = guarantees.violations(CLUSTER, quota_cols(),
                                      *quota_schedule(1, 2),
                                      dict(QUOTAS, queue_window=1))
    assert bad == dict.fromkeys(guarantees.KINDS, 0)
    assert seen["quota_decisions"] == 1


@pytest.mark.parametrize("scheduler", [None, {"queue_window": 2}])
def test_no_vc_quotas_counts_no_quota(scheduler):
    bad, seen = guarantees.violations(CLUSTER, quota_cols(),
                                      *quota_schedule(1, 2), scheduler)
    assert bad == dict.fromkeys(guarantees.KINDS, 0)
    assert seen["quota_decisions"] == 0
