"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes and returns, for the
traced window: the device busy time (union of the intervals in which an
operation ran, averaged over the devices that ran any), the window's
length, the device time of named kernels, the device operations that took
most time, and the idle gaps attributed to what the host was doing.

The window is bounded by the harness's ``window`` spans on the host; the
host thread that holds them is the one whose spans label the idle gaps:
each stretch of a gap goes to the innermost span open on that thread.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "window"
#: the device-plane line whose events are the operations that ran
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


def _merge(iv: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def hlo_name(event_name: str) -> str:
    """An op event's HLO instruction name: the trace names device ops by
    their HLO text, ``%policy_mlp.1 = f32[4096]{0} custom-call(...)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def op_name(name: str) -> str:
    """Operation name without XLA's numeric suffix (``copy.3`` -> ``copy``)."""
    return _SUFFIX.sub("", name)


def host_segments(spans: list[tuple[float, float, str]], w0: float,
                  w1: float) -> list[tuple[float, float, str]]:
    """Cut [w0, w1] into pieces labelled by the innermost host span open
    in each (``window`` where none is).  Spans of one thread nest; a span
    that ends while a later one is open is closed where it ends."""
    marks = sorted([(s, 1, e, n) for s, e, n in spans]
                   + [(e, 0, s, n) for s, e, n in spans],
                   key=lambda m: (m[0], m[1]))
    out: list[tuple[float, float, str]] = []
    open_: list[tuple[float, str]] = []
    cur = w0
    for t, is_start, other, name in marks:
        lo, hi = max(cur, w0), min(t, w1)
        if hi > lo:
            out.append((lo, hi, open_[-1][1] if open_ else WINDOW_SPAN))
        cur = max(cur, t)
        if is_start:
            open_.append((other, name))
        else:
            for i in range(len(open_) - 1, -1, -1):
                if open_[i] == (t, name):
                    del open_[i]
                    break
    if w1 > cur:
        out.append((max(cur, w0), w1, open_[-1][1] if open_ else WINDOW_SPAN))
    return out


def reduce_events(host: list[tuple[str, float, float]],
                  devices: list[list[tuple[str, float, float]]],
                  kernels: dict[str, str], top: int = 10) -> dict | None:
    """Reduce host spans and per-device op events, each ``(name, start_ns,
    end_ns)`` on one clock.  ``kernels`` maps a kernel's label to a regex
    of its op names.  Returns None when there is no window span."""
    wins = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not wins:
        return None
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    window_ns = w1 - w0
    busy, per_op = [], {}
    kernel_ns, kernel_n = {k: 0.0 for k in kernels}, {k: 0 for k in kernels}
    merged_all: list[tuple[float, float]] = []
    pats = {k: re.compile(p) for k, p in kernels.items()}
    for ops in devices:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                  if e > w0 and s < w1]
        if not inside:
            continue
        merged = _merge([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged))
        merged_all.extend((s, e) for s, e in merged)
        for n, s, e in inside:
            base = op_name(n)
            per_op[base] = per_op.get(base, 0.0) + (e - s)
            for k, pat in pats.items():
                if pat.match(n):
                    kernel_ns[k] += e - s
                    kernel_n[k] += 1
    merged = _merge(merged_all)
    gaps, cur = [], w0
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    by_label: dict[str, float] = {}
    segs = host_segments([(s, e, n) for n, s, e in host
                          if n != WINDOW_SPAN], w0, w1)
    k = 0
    for s, e in gaps:
        while k < len(segs) and segs[k][1] <= s:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < e:
            cut = min(e, segs[j][1]) - max(s, segs[j][0])
            if cut > 0:
                by_label[segs[j][2]] = by_label.get(segs[j][2], 0.0) + cut
            j += 1
    ns = 1e-9
    return {
        "window_s": window_ns * ns,
        "busy_s": (sum(busy) / len(busy) * ns) if busy else 0.0,
        "devices": len(busy),
        "kernel_s": {k: v * ns for k, v in kernel_ns.items()},
        "kernel_calls": kernel_n,
        "device_ops": [[n, v * ns] for n, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v * ns] for n, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce_xplane(path: str, kernels: dict[str, str]) -> dict | None:
    """Read one ``.xplane.pb`` and reduce it (see ``reduce_events``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    host = evs
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices.append([(hlo_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for line in plane.lines if line.name == OPS_LINE
                            for e in line.events])
    return reduce_events(host, devices, kernels)

