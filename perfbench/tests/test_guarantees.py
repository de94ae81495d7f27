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
    assert seen == {"starts": 5, "backfills": 1}


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
