"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is held as ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``
(the events of lines that share a name, as host threads do, together),
read from the ``.xplane.pb`` file that ``jax.profiler`` writes (or built
by hand in the tests). Device planes are those named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation run on the device,
and their ``XLA Modules`` line one per execution of a compiled program.
Host spans that the benchmark writes with ``TraceAnnotation`` lie on the
host plane, on the same clock; their names start with ``bench.``.

The window is the interval from the host span ``bench.window_open`` to
the host span ``bench.window_close``. Within it:

- ``busy_s``: the length of the union of the device's op intervals,
  averaged over the device planes;
- ``module_s``: each program's executions' device durations, by module
  name (``jit_<function>``);
- ``top_ops``: the ten operations with the most device self time (less
  the ops nested inside them), each named ``<module>/<op>`` (the HLO
  text cut at `` = ``);
- ``idle_gaps``: the ten longest intervals in which no op ran, each named
  by the innermost ``bench.`` host span that covers its midpoint
  (``host.none`` where none does).
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OPEN, CLOSE = "bench.window_open", "bench.window_close"


def read_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain trace."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out: dict = {}
    for plane in pd.planes:
        lines: dict = {}
        for line in plane.lines:      # host threads share line names
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        out[plane.name] = lines
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def host_spans(trace: dict) -> list[tuple[str, float, float]]:
    """Every ``bench.`` host span as ``(name, start_ns, end_ns)``."""
    out = []
    for plane, lines in trace.items():
        if plane.startswith(DEVICE_PREFIX):
            continue
        for events in lines.values():
            out.extend((n, s, s + d) for n, s, d in events
                       if n.startswith("bench."))
    return out


def window(trace: dict) -> tuple[float, float] | None:
    spans = host_spans(trace)
    opens = [s for n, s, _ in spans if n == OPEN]
    closes = [s for n, s, _ in spans if n == CLOSE]
    if not opens or not closes or max(closes) <= min(opens):
        return None
    return min(opens), max(closes)


def self_times(ops):
    """``(name, (start, self time))`` of each op: its duration less the
    parts that ops nested inside it cover (a ``while`` op spans its
    body's ops on the same line)."""
    stack: list = []                       # [start, end, name, covered]
    for a, b, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= a:
            e = stack.pop()
            yield e[2], (e[0], max(0.0, e[1] - e[0] - e[3]))
        if stack:
            stack[-1][3] += min(b, stack[-1][1]) - a
        stack.append([a, b, n, 0.0])
    while stack:
        e = stack.pop()
        yield e[2], (e[0], max(0.0, e[1] - e[0] - e[3]))


def summarize(trace: dict) -> dict | None:
    """The window's device numbers, or None where the trace holds no
    device plane with ops, or no window."""
    win = window(trace)
    devices = {p: lines for p, lines in trace.items()
               if p.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)}
    if win is None or not devices:
        return None
    lo, hi = win
    busy, module_s = [], defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    first_union = None
    for plane in sorted(devices):
        lines = devices[plane]
        ops = [(s, s + d, n) for n, s, d in lines[OPS_LINE]]
        u = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy.append(sum(b - a for a, b in u))
        if first_union is None:
            first_union = u
        mods = sorted((s, s + d, n.split("(")[0])
                      for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]

        def key(a: float, n: str) -> str:
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            return f"{mod}/{n.split(' = ')[0]}"

        for k, t in self_times(ops):
            if lo <= t[0] < hi:
                op_time[key(t[0], k)] += t[1]
        for s, e, n in mods:
            if lo <= s < hi:
                module_s[n].append((e - s) * 1e-9)
    gaps, edge = [], lo
    for a, b in first_union + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    spans = host_spans(trace)

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in spans
                 if s <= mid <= e and n not in (OPEN, CLOSE)]
        return min(cover)[1] if cover else "host.none"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    n_dev = len(devices)
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "module_s": dict(module_s),
        "top_ops": [[n, t / n_dev * 1e-9] for n, t in
                    sorted(op_time.items(), key=lambda kv: (-kv[1], kv[0]))
                    if t > 0][:10],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:10]],
    }
