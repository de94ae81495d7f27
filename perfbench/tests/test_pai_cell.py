"""The ``pai-quota`` cell rehearsed at a CPU size (``--rehearse``), once
untraced and once traced: the Alibaba PAI slice under its VC quotas is
``correct``, the reference replay agrees with the timed schedule, the
quota guarantee holds, the gate had work, and the traced run reads the
gate's ``rank.quota`` span."""
import argparse
import importlib.util
import json
import os

import pytest

from conftest import BENCH

#: the cell at a CPU size: a smaller stream and backlog, a window of 300
#: rows (past the actor's 256 slots, so the deep scorer runs)
PAI_SMALL = json.dumps({
    "traffic": {"num_jobs": 3000, "backlog": {"jobs": 500},
                "window_rows": [300, 300], "reference_decisions": 30},
    "config": {"scheduler": {"queue_window": 300}}})


def _harness():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", params=[0, 1], ids=["untraced", "traced"])
def pai_run(request):
    run = _harness().run(argparse.Namespace(
        workload="pai-quota", seed=4_000_000_321, seconds=1.5,
        trace=request.param, rehearse=True, override=PAI_SMALL))
    return run, request.param


def test_pai_run_is_correct_and_the_gate_has_work(pai_run):
    run, _ = pai_run
    res, info = run["result"], run["info"]
    assert res["correct"], res["checks"]
    assert res["checks"]["schedule_mismatches"]["value"] == 0
    assert res["checks"]["stream_ran_dry"]["value"] == 0
    assert info["guarantees_broken"]["quota"] == 0
    assert info["guarantees_checked"]["quota_decisions"] > 0, info
    assert info["window_depth_min"] > 300


def test_traced_run_reads_the_quota_span(pai_run):
    run, traced = pai_run
    metrics = run["result"]["metrics"]
    if traced:
        assert metrics["quota_ms_per_decision"]["value"] > 0
        assert metrics["quota_ms_per_decision"]["unit"] == "ms"
    else:
        assert "quota_ms_per_decision" not in metrics
        assert set(metrics) == {"decision_ms", "setup_s"}
