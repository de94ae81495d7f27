"""Ranking layer: the actor's device round trip per decision in the
traced window, from the program's ``rank.actor`` spans, inclusive: the
transfer of parameters, rows and mask, the ``greedy_step`` dispatch and
the readback (JAX's ``DevicePut`` / ``PjitFunction`` / ``np.asarray``
host events nest inside)."""
import program_spans


def read(ctx):
    return program_spans.span_ms_per_decision(ctx, "rank.actor")
