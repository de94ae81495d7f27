"""The per-layer readers of the program's spans on hand-made span totals,
and on a program without the span facility."""
import sys

import pytest

import program_spans
from run import load_reader

#: reader -> the spans it sums
READERS = {
    "features_ms_per_decision": ("rank.features",),
    "actor_ms_per_decision": ("rank.actor",),
    "tail_score_ms_per_decision": ("rank.scorer",),
    "backfill_ms_per_decision": ("backfill",),
    "predictor_ms_per_decision": ("predict.submit", "predict.train"),
}
#: name -> (calls, seconds), as ``repro.obs.spans.traced_totals`` gives them
TOTALS = {"rank.features": (500, 2.0), "rank.actor": (500, 1.5),
          "rank.scorer": (300, 0.5), "backfill": (40, 0.25),
          "predict.submit": (90, 0.125), "predict.train": (80, 0.375),
          "engine.decide": (500, 6.0)}


def _ctx(decisions=500, trace=1):
    return {"trace": trace, "at_start": (100, 0, 0, 0),
            "at_end": (100 + decisions, 0, 0, 0)}


@pytest.fixture
def totals(monkeypatch):
    """Serve ``program_spans.traced_totals`` from a dict the test fills."""
    got = dict(TOTALS)
    monkeypatch.setattr(program_spans, "traced_totals", lambda: got)
    return got


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_span_seconds_per_decision(name, totals):
    want = 1e3 * sum(TOTALS[s][1] for s in READERS[name]) / 500
    assert load_reader(name)(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_without_its_span_or_decisions(name, totals,
                                                           monkeypatch):
    read = load_reader(name)
    assert read(_ctx(decisions=0)) is None
    assert read(_ctx(trace=0)) is None
    for s in READERS[name]:
        del totals[s]
    assert read(_ctx()) is None
    # a program without the span facility
    monkeypatch.setattr(program_spans, "traced_totals", lambda: None)
    assert read(_ctx()) is None


def test_predictor_reads_either_hook_alone(totals):
    del totals["predict.submit"]
    read = load_reader("predictor_ms_per_decision")
    assert read(_ctx()) == pytest.approx(1e3 * 0.375 / 500)


def test_no_span_facility_reads_as_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert program_spans.traced_totals() is None


def test_the_programs_totals_are_read():
    from repro.obs import spans
    assert program_spans.traced_totals() == spans.traced_totals()
