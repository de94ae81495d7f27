"""The ``Observability`` bundle: tracer, metrics registry and audit log for
one engine or one federation (see ``repro.obs``)."""
from __future__ import annotations

import contextlib

from repro.obs import spans
from repro.obs.audit import DecisionAuditLog
from repro.obs.metrics import EngineMetricsHook, MetricsRegistry
from repro.obs.tracer import SpanTracer, merge_documents


class Observability:
    """Bundle of tracer + metrics + audit log for one engine (or, via
    :meth:`member`, one federation).  Any sink can be switched off at
    construction; ``hooks()`` only returns the live ones."""

    def __init__(self, *, name: str = "cluster", member: int = 0,
                 trace: bool = True, metrics: bool = True,
                 audit: bool = True, max_trace_events: int = 2_000_000,
                 keep_audit_records: int = 10_000):
        self.name = name
        self.tracer = SpanTracer(name=name, member=member,
                                 max_events=max_trace_events) \
            if trace else None
        self.registry = MetricsRegistry() if metrics else None
        self.metrics_hook = EngineMetricsHook(self.registry, cluster=name) \
            if metrics else None
        self.audit = DecisionAuditLog(keep=keep_audit_records) \
            if audit else None
        self._members: dict[int, "Observability"] = {}
        self._finalized = False
        #: per span name: its (calls, seconds) counter pair
        self._span_counters: dict[str, tuple] = {}

    # -------------------------------------------------------------- hooks ----
    def hooks(self) -> tuple:
        """Hook objects to attach to one engine, in dispatch order."""
        return tuple(h for h in (self.tracer, self.metrics_hook, self.audit)
                     if h is not None)

    # ---------------------------------------------------------- federation ----
    def member(self, i: int, name: str | None = None) -> "Observability":
        """Per-federation-member child bundle (memoized).  Members get
        disjoint trace pids and a ``cluster`` metric label of their own;
        fleet-level views merge them."""
        child = self._members.get(i)
        if child is None:
            child = Observability(
                name=name or f"{self.name}/{i}", member=i + 1,
                trace=self.tracer is not None,
                metrics=self.registry is not None,
                audit=self.audit is not None,
                max_trace_events=(self.tracer.max_events
                                  if self.tracer is not None else 0),
                keep_audit_records=(self.audit.keep
                                    if self.audit is not None else 0))
            self._members[i] = child
        return child

    def members(self) -> list["Observability"]:
        return [self._members[i] for i in sorted(self._members)]

    # ----------------------------------------------------------- spans ----
    def recording(self):
        """Context in which the program's spans (``repro.obs.spans``) go
        to this bundle's control-plane track and span counters; drivers
        wrap their run in it.  A bundle with neither a tracer nor a
        registry takes none."""
        if self.tracer is None and self.registry is None:
            return contextlib.nullcontext(self)
        return spans.recording(self)

    def record_span(self, name: str, start_ns: int, end_ns: int,
                    parent: str | None, meta: dict) -> None:
        """Span sink: one closed span, timed on the profiler's clock."""
        if self.tracer is not None:
            self.tracer.program_span(name, start_ns, end_ns, parent, meta)
        if self.registry is not None:
            pair = self._span_counters.get(name)
            if pair is None:
                pair = self._span_counters[name] = (
                    self.registry.counter("repro_span_calls_total",
                                          "closed program spans", span=name),
                    self.registry.counter("repro_span_seconds_total",
                                          "seconds inside program spans",
                                          span=name))
            pair[0].inc()
            pair[1].inc((end_ns - start_ns) * 1e-9)

    # --------------------------------------------------- control-plane API ----
    def note_controller(self, kind: str, n_events: int) -> None:
        """Count one controller tick (autoscaler / preemption / chaos /
        fleet-chaos) and its actions; the tick's span is the caller's."""
        if self.metrics_hook is not None:
            self.metrics_hook.note_controller(kind, n_events)

    def note_window(self) -> None:
        """Count one processed rescan window."""
        if self.registry is not None:
            self.registry.counter("repro_rescan_windows_total",
                                  "processed rescan windows",
                                  cluster=self.name).inc()

    def count(self, name: str, help: str = "", n: float = 1.0,
              **labels) -> None:
        """Bump a fleet-level counter (routing / deferral / migration);
        no-op with metrics off."""
        if self.registry is not None:
            self.registry.counter(name, help, **labels).inc(n)

    # ----------------------------------------------------------- finalize ----
    def finalize(self, engine=None) -> None:
        """Close open spans and take a final metrics sample.  Idempotent;
        drivers call it once at end-of-stream."""
        if self._finalized:
            return
        self._finalized = True
        if self.tracer is not None:
            now = engine.now if engine is not None else None
            self.tracer.finalize(now)
        if self.metrics_hook is not None and engine is not None:
            self.metrics_hook.on_tick(engine.now, engine)

    def finalize_fleet(self, fed) -> None:
        """Finalize every member bundle against its engine."""
        for i, child in self._members.items():
            child.finalize(fed.engines[i] if i < len(fed.engines) else None)
        self.finalize()

    # -------------------------------------------------------------- views ----
    def trace_document(self) -> dict:
        """Fleet-merged Chrome trace document (self + members)."""
        docs = []
        if self.tracer is not None:
            docs.append(self.tracer.to_document())
        docs.extend(m.tracer.to_document() for m in self.members()
                    if m.tracer is not None)
        if len(docs) == 1:
            return docs[0]
        return merge_documents(docs)

    def export_trace(self, path: str) -> str:
        import json
        with open(path, "w") as fh:
            json.dump(self.trace_document(), fh)
        return path

    def merged_registry(self) -> MetricsRegistry:
        """Fleet-merged metrics registry (self + members)."""
        regs = [self.registry] + [m.registry for m in self.members()]
        return MetricsRegistry.merged(r for r in regs if r is not None)

    def prometheus(self) -> str:
        """Fleet-merged Prometheus text exposition."""
        return self.merged_registry().render()

    def write_prometheus(self, path: str) -> str:
        with open(path, "w") as fh:
            fh.write(self.prometheus())
        return path

    def audit_summary(self) -> dict:
        """Audit aggregate; per-member summaries attached under
        ``members`` when federation children exist."""
        out = self.audit.summary() if self.audit is not None else {}
        if self._members:
            out = dict(out)
            out["members"] = {m.name: m.audit.summary()
                              for m in self.members()
                              if m.audit is not None}
        return out
