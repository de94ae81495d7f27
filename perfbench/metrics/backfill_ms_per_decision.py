"""Backfill layer: EASY backfill per decision in the traced window, from
the program's ``backfill`` spans, inclusive: the reserved job's earliest
start and the scan over the candidates behind it."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "backfill")
