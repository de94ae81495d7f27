"""Per-decision readings of the program's own spans.

While a ``jax.profiler`` session is active the program's spans
(``repro.obs.spans``) are TraceMe events in the profile and are also
summed per name in the process (``spans.traced_totals()``); the traced
window is the whole of that session, so those sums are the spans' seconds
in the window.  A program without the span facility gives no reading.
"""
from __future__ import annotations


def traced_totals() -> dict | None:
    """``{name: (calls, seconds)}`` of the program's traced spans; None
    where the program has no span facility."""
    try:
        from repro.obs.spans import traced_totals as totals
    except ImportError:
        return None
    return totals()


def span_ms_per_decision(ctx: dict, *names: str) -> float | None:
    """Milliseconds inside the spans ``names`` (inclusive, summed) per
    decision the engine made in the traced window; None where the run was
    not traced, no decision was made or none of the spans was recorded."""
    made = ctx["at_end"][0] - ctx["at_start"][0]
    totals = traced_totals() if ctx.get("trace") else None
    if not totals or made <= 0:
        return None
    found = [totals[n][1] for n in names if n in totals]
    return 1e3 * sum(found) / made if found else None
