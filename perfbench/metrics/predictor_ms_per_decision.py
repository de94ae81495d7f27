"""Predictor layer: the runtime predictor's hooks per decision in the
traced window, from the program's ``predict.submit`` (a submitted job's
feature row) and ``predict.train`` (a finished job's forward pass and SGD
step) spans, inclusive."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "predict.submit",
                                              "predict.train")
