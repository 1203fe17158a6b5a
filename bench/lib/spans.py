"""The program's own spans in a traced run.

The program writes ``acan.`` spans (``repro.core.trace``) on the host
plane of the profile, on the device trace's clock, with their ids as the
events' stats: ``step``, ``micro``, ``rnd``, ``epoch``, and ``bytes`` on
each span that moves data between host and device. ``program_spans``
reads those that start between the benchmark's window markers, from the
trace the harness's ``Tracer`` wrote. A program that writes no such span
gives none, and each reader built on it returns None.

``idle_gaps`` names the device's idle gaps as ``devtrace.summarize`` finds
them, by the shortest ``acan.`` or ``bench.`` host span over each gap's
midpoint (on any thread), for a look below the benchmark's own spans.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import NamedTuple

import devtrace

PREFIX = "acan."


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    ids: dict


def trace_dir() -> str:
    """Where the harness's ``Tracer`` writes a traced run's profile."""
    import harness
    return os.path.join(harness.CACHE, "trace")


def program_spans(log_dir: str | None = None) -> tuple[Span, ...]:
    """The ``acan.`` host spans that start inside the window of the newest
    profile under ``log_dir`` (the traced run's by default), by start."""
    files = sorted(glob.glob(os.path.join(log_dir or trace_dir(), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return ()
    return _read(files[-1], os.stat(files[-1]).st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _read(path: str, _mtime_ns: int) -> tuple[Span, ...]:
    import jax
    marks: dict[str, list[float]] = {devtrace.OPEN: [], devtrace.CLOSE: []}
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name in marks:
                    marks[name].append(e.start_ns)
                elif name.startswith(PREFIX):
                    spans.append(Span(name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    if not marks[devtrace.OPEN] or not marks[devtrace.CLOSE]:
        return ()
    lo, hi = min(marks[devtrace.OPEN]), max(marks[devtrace.CLOSE])
    return tuple(sorted((s for s in spans if lo <= s.start_ns <= hi),
                        key=lambda s: s.start_ns))


def named(spans, name: str) -> list[Span]:
    return [s for s in spans if s.name == name]


def idle_gaps(trace: dict, n: int = 10) -> list[list] | None:
    """The ``n`` longest intervals of the window in which the first
    device ran no op, longest first, as ``[label, seconds]``: the label
    is the shortest ``acan.`` or ``bench.`` host span that covers the
    gap's midpoint (``host.none`` where none does). None where the trace
    has no window or no device ops."""
    win = devtrace.window(trace)
    devices = sorted(p for p, lines in trace.items()
                     if p.startswith(devtrace.DEVICE_PREFIX)
                     and lines.get(devtrace.OPS_LINE))
    if win is None or not devices:
        return None
    lo, hi = win
    ops = [(s, s + d) for _, s, d in trace[devices[0]][devtrace.OPS_LINE]]
    gaps, edge = [], lo
    for a, b in devtrace._union(devtrace._clip(ops, lo, hi)) + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    host = [(s, s + d, name) for plane, lines in trace.items()
            if not plane.startswith(devtrace.DEVICE_PREFIX)
            for events in lines.values() for name, s, d in events
            if name.startswith((PREFIX, "bench."))
            and name not in (devtrace.OPEN, devtrace.CLOSE)]

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        cover = [(e - s, name) for s, e, name in host if s <= mid <= e]
        return min(cover)[1] if cover else "host.none"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:n]]
