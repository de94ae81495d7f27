"""Ranking layer: the feature build per decision in the traced window,
from the program's ``rank.features`` spans (the queue window's feature
rows, feature sampling, padding to the actor's slots and the critic's
features)."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "rank.features")
