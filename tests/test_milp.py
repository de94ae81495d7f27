import numpy as np
import pytest
from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

from repro.core import ClusterState, Job, choose_allocation, make_cluster
from repro.core.milp import (_SKELETONS, _greedy_choice, _solve_milp,
                             _solve_milp_reference)


def mk(i, gpus, cpus=0, mem=0.0):
    return Job(job_id=i, user=0, submit_time=0, runtime=100, est_runtime=100,
               num_gpus=gpus, req_cpus=cpus, req_mem_gb=mem)


def test_skeleton_solver_matches_reference_differential():
    """The memoized constraint-skeleton solver (bounds filled in place) must
    return the identical MILPResult as the per-call dense builder across
    random cluster states, job shapes, and look-ahead depths — including
    repeated hits on the same cached skeleton."""
    rng = np.random.default_rng(42)
    checked = 0
    for trace in ("helios", "philly", "alibaba"):
        for _ in range(12):
            c = ClusterState(make_cluster(trace))
            for i in range(int(rng.integers(0, 6))):
                filler = mk(1000 + i, int(rng.integers(1, 8)),
                            cpus=int(rng.integers(0, 16)),
                            mem=float(rng.integers(0, 64)))
                pl = c.find_placement(filler, "pack")
                if pl:
                    c.allocate(filler, pl)
            j = mk(0, int(rng.integers(1, 17)),
                   cpus=int(rng.integers(0, 32)),
                   mem=float(rng.integers(0, 128)))
            ways = c.candidate_ways(j)
            if len(ways) < 2:
                continue
            look = [mk(10 + i, int(rng.integers(1, 9)))
                    for i in range(int(rng.integers(0, 5)))]
            a = _solve_milp(c, j, ways[:2], look)
            b = _solve_milp_reference(c, j, ways[:2], look)
            assert (a is None) == (b is None)
            if a is not None:
                assert a.placement == b.placement
                assert a.way_index == b.way_index
                assert a.objective == pytest.approx(b.objective, abs=1e-9)
                assert a.lookahead_scheduled == b.lookahead_scheduled
            checked += 1
    assert checked >= 10


def test_skeleton_cache_is_bounded_and_reused():
    """One skeleton per (n_nodes, gpn, K): repeated solves on the same
    cluster shape reuse the cached structure instead of growing the dict."""
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    look = [mk(10, 2), mk(11, 2)]
    before = len(_SKELETONS)
    for _ in range(5):
        assert _solve_milp(c, j, ways[:2], look) is not None
    after = len(_SKELETONS)
    assert after - before <= 1


def test_single_way_short_circuit():
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 80)  # needs every GPU -> exactly one way
    ways = c.candidate_ways(j)
    res = choose_allocation(c, j, ways)
    assert res.placement in ways and not res.used_solver


def test_solver_picks_feasible_way():
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    res = choose_allocation(c, j, ways, lookahead=[])
    assert sum(res.placement.values()) == 4
    assert res.used_solver or len(ways) == 1
    # chosen placement must be allocatable
    c.allocate(j, res.placement)
    c.release(j, res.placement)


def test_lookahead_influences_choice():
    """With an 8-GPU job waiting, the solver should leave a node whole."""
    c = ClusterState(make_cluster("helios"))
    # fill most nodes so spreading would fragment the last full nodes
    for i in range(8):
        filler = mk(100 + i, 6)
        c.allocate(filler, {i: 6})
    j = mk(0, 4)
    big = mk(1, 8)
    ways = c.candidate_ways(j)
    res = choose_allocation(c, j, ways, lookahead=[big])
    c.allocate(j, res.placement)
    assert c.can_schedule_now(big), \
        "look-ahead MILP must preserve an 8-GPU hole"


def test_respects_cpu_mem_constraints():
    c = ClusterState(make_cluster("helios"))
    # drain CPU on node 0 so it cannot host GPU jobs despite free GPUs
    c.free_cpus[0] = 1
    j = mk(0, 8, cpus=32, mem=64.0)
    ways = c.candidate_ways(j)
    res = choose_allocation(c, j, ways)
    frac = {n: g / 8 for n, g in res.placement.items()}
    for n, g in res.placement.items():
        assert c.free_cpus[n] >= round(32 * frac[n])


def test_greedy_fallback():
    c = ClusterState(make_cluster("helios"))
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    res = _greedy_choice(c, j, ways, [mk(1, 8)])
    assert res.placement in ways


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=16),
       st.integers(min_value=0, max_value=4))
def test_solver_feasibility_property(gpus, n_look):
    """Whatever the MILP picks must satisfy every per-node resource bound."""
    c = ClusterState(make_cluster("helios"))
    j = mk(0, gpus)
    ways = c.candidate_ways(j)
    if not ways:
        return
    look = [mk(10 + i, 2) for i in range(n_look)]
    res = choose_allocation(c, j, ways, lookahead=look)
    assert sum(res.placement.values()) == gpus
    for n, g in res.placement.items():
        assert g <= c.free_gpus[n]


def test_failed_solve_is_a_counted_fallback(monkeypatch):
    """A solve that ends without an optimum (time limit, infeasible) takes
    the greedy way and the engine counts it as a fallback, not a call."""
    import types

    import repro.core.milp as milp_mod
    from repro.core import PolicyPrioritizer, generate_trace, make_policy
    from repro.sched import SchedulerEngine

    monkeypatch.setattr(milp_mod, "milp", lambda **_: types.SimpleNamespace(
        success=False, x=None, status=1, message="Time limit reached"))
    c = ClusterState(make_cluster("helios"))
    for i in range(8):                  # partly filled: spread != pack
        c.allocate(mk(100 + i, 6), {i: 6})
    j = mk(0, 4)
    ways = c.candidate_ways(j)
    assert len(ways) > 1
    assert not choose_allocation(c, j, ways, [mk(1, 8)]).used_solver

    eng = SchedulerEngine(make_cluster("helios"),
                          PolicyPrioritizer(make_policy("fcfs")),
                          allocator="milp")
    eng.submit([x.clone_pending() for x in generate_trace("helios", 40)])
    eng.drain()
    assert eng.done
    assert eng.milp_calls == 0 and eng.milp_fallbacks > 0
    assert eng.snapshot().milp_fallback_ratio == 1.0


def test_solver_exception_propagates(monkeypatch):
    """A solver error is raised, never turned into a silent greedy pick."""
    import repro.core.milp as milp_mod

    def boom(**_):
        raise RuntimeError("solver crashed")

    monkeypatch.setattr(milp_mod, "milp", boom)
    c = ClusterState(make_cluster("helios"))
    for i in range(8):
        c.allocate(mk(100 + i, 6), {i: 6})
    j = mk(0, 4)
    with pytest.raises(RuntimeError, match="solver crashed"):
        choose_allocation(c, j, c.candidate_ways(j), [mk(1, 8)],
                          solution_cache=False)
