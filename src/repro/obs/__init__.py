"""repro.obs — end-to-end observability for the scheduling control plane.

One ``Observability`` bundle owns the three sinks and is what drivers
pass around (``run_stream(..., obs=obs)`` / ``run_fleet(..., obs=obs)``):

- :class:`~repro.obs.tracer.SpanTracer` — job-lifecycle + control-plane
  spans, exported as Chrome trace-event JSON (Perfetto-loadable).
- :class:`~repro.obs.metrics.MetricsRegistry` (fed by
  :class:`~repro.obs.metrics.EngineMetricsHook`) — counters / gauges /
  histograms with a Prometheus text exporter and fleet-level merge.
- :class:`~repro.obs.audit.DecisionAuditLog` — per-decision rank-path /
  allocator / skip-reason accounting.

:mod:`repro.obs.spans` is the program's span facility: ``with span(name,
**meta):`` at the layer boundaries of the decision path (engine, ranking,
actor, deep scorer, MILP, backfill, predictor, service loop).  A span goes
to the ``jax.profiler`` trace when a profiler session is active and to
the attached bundle's control-plane track and span counters while a
driver runs with ``obs=``; both on the profiler's host clock.

``obs.hooks()`` yields the hook objects to attach to an engine (the
service loop composes them with telemetry and RL recorders through
``MultiHooks``); ``obs.member(i, name)`` derives a per-federation-member
child whose trace events and metrics roll up into the fleet-level
``export_trace`` / ``prometheus`` views.

Everything here is observational: with ``obs=None`` the engine and
drivers take bit-identical code paths (pinned by ``tests/test_obs.py``).

The engine and the layers under it import :mod:`repro.obs.spans`, so this
package imports nothing else eagerly: the sinks, which hook into the
engine, load on first use.
"""
from __future__ import annotations

import importlib

from repro.obs.spans import clock_ns, recording, span

__all__ = [
    "Observability", "SpanTracer", "MetricsRegistry", "EngineMetricsHook",
    "DecisionAuditLog", "Counter", "Gauge", "Histogram",
    "merge_documents", "validate_trace", "span", "recording", "clock_ns",
]

_HOME = {
    "Observability": "bundle", "SpanTracer": "tracer",
    "merge_documents": "tracer", "validate_trace": "tracer",
    "MetricsRegistry": "metrics", "EngineMetricsHook": "metrics",
    "Counter": "metrics", "Gauge": "metrics", "Histogram": "metrics",
    "DecisionAuditLog": "audit",
}


def __getattr__(name: str):
    home = _HOME.get(name)
    if home is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    return getattr(importlib.import_module(f"repro.obs.{home}"), name)
