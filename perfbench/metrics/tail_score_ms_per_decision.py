"""Kernels layer: the deep scorer per decision in the traced window, from
the program's ``rank.scorer`` spans, inclusive: padding the tail rows to
their bucket, the ``policy_mlp`` kernel's dispatch and the readback."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "rank.scorer")
