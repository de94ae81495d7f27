"""Tenancy layer: the VC-quota gate per decision in the traced window,
from the program's ``rank.quota`` spans (the VCs over quota from the
hook-fed usage, and the stable partition of the base order that demotes
their rows); the ranking beneath the gate is not in it.  A program without
the span gives no reading."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "rank.quota")
