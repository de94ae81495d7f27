"""Program spans on the profiler's host clock.

``with span(name, **meta) as s:`` marks one stretch of the program's work
(ranking's feature build, the actor's device round trip, the backfill
scan, ...).  A span records its start, its end, the span it nests in, and
metadata; ``s.set(**meta)`` adds metadata known only at the end (counts a
scan produced).  Where it goes depends on what is switched on:

- a ``jax.profiler`` session: the span is a ``TraceAnnotation`` (TraceMe),
  on the same host line and clock as JAX's own events and the device ops
  in the profile's ``.xplane.pb``; its calls and seconds are also summed
  per name (:func:`traced_totals`), so a caller that keeps no profile
  file still reads what the profile holds;
- an attached sink (``recording(sink)``; ``Observability.recording()``
  attaches a bundle): the sink's ``record_span(name, start_ns, end_ns,
  parent, meta)`` gets the span, stamped with :func:`clock_ns`;
- neither: ``span`` returns a shared no-op after one check.  No object is
  made, no clock is read and the metadata is not formatted.

The profiler stamps host events with the wall clock (``CLOCK_REALTIME``,
ns since the epoch) and a profile gives them relative to its
``profile_start_time`` on that clock, so :func:`clock_ns` times line up
with the profile's.  There is no switch of its own: the profiler session
and the attached sink are the switches.
"""
from __future__ import annotations

import contextlib
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "recording", "traced_totals", "clock_ns", "ORIGIN_NS"]

#: the profiler's host clock, in ns
clock_ns = time.time_ns
#: origin of the Chrome-trace timestamps every tracer of this process uses
ORIGIN_NS = clock_ns()

_profiling = TraceAnnotation.is_enabled
_sinks: list = []
_local = threading.local()
#: span name -> [calls, ns] of the spans opened under a profiler session
_traced: dict[str, list] = {}


def _open_names() -> list:
    names = getattr(_local, "names", None)
    if names is None:
        names = _local.names = []
    return names


class _Off:
    """The span when nothing records: enters, sets and exits as no-ops."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **meta) -> None:
        return None


_OFF = _Off()


class _Span:
    __slots__ = ("name", "meta", "_ann", "_sinks", "_parent", "_t0")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta
        self._ann = None
        self._sinks = ()

    def __enter__(self):
        # the TraceMe opens last and closes first, so the profile's event
        # and the clock pair enclose the same work, not the sinks' upkeep
        if _sinks:
            self._sinks = tuple(_sinks)
            names = _open_names()
            self._parent = names[-1] if names else None
            names.append(self.name)
        if _profiling():
            self._ann = TraceAnnotation(self.name, **self.meta)
            self._ann.__enter__()
        self._t0 = clock_ns()
        return self

    def set(self, **meta) -> None:
        self.meta.update(meta)
        if self._ann is not None:
            self._ann.set_metadata(**meta)

    def __exit__(self, *exc):
        t1 = clock_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            tot = _traced.setdefault(self.name, [0, 0])
            tot[0] += 1
            tot[1] += t1 - self._t0
        if self._sinks:
            _open_names().pop()
            for sink in self._sinks:
                sink.record_span(self.name, self._t0, t1, self._parent,
                                 self.meta)
        return None


def span(name: str, **meta):
    """Context manager for one span named ``name`` (see the module doc)."""
    if not _sinks and not _profiling():
        return _OFF
    return _Span(name, meta)


def traced_totals() -> dict[str, tuple[int, float]]:
    """``{name: (calls, seconds)}`` of the spans this process opened under
    a ``jax.profiler`` session (inclusive of the spans nested in them), the
    sums of the profile's own events of those names."""
    return {n: (c, ns * 1e-9) for n, (c, ns) in _traced.items()}


@contextlib.contextmanager
def recording(sink):
    """Deliver every span closed on any thread while the block runs to
    ``sink.record_span(name, start_ns, end_ns, parent, meta)``."""
    _sinks.append(sink)
    try:
        yield sink
    finally:
        _sinks.remove(sink)
