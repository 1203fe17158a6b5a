"""ssd_s: device self time of the ops under the program's ``acan.ssd``
scope (the Mamba-2 SSD: its chunked scan and D skip, in the forward, the
recomputation and the backward) in the traced part, per execution of the
gradient program (module jit_loss_fn) there, in s.

The newest profile of the traced run is read as ``spans.program_spans``
reads it. A device op event names its HLO instruction and carries no
scope; the scope is in the instruction's ``op_name`` metadata (a
fusion's is its root's), in the optimized HLO of each module that the
profile holds on its ``/host:metadata`` plane (stat ``Hlo Proto`` of
the module's event metadata). So the ops counted are the ``XLA Ops`` of
``/device:TPU:0`` that run inside a ``jit_loss_fn`` execution, start
between the benchmark's window markers and name a scoped instruction;
each counts its self time, its duration less the ops nested inside it,
as ``devtrace.summarize`` counts it. None where no op carries the scope,
as in a model without an SSD or a program that names no such scope."""

from __future__ import annotations

import bisect
import functools
import glob
import os

import devtrace
from spans import trace_dir

SCOPE = "acan.ssd"
MODULE = "jit_loss_fn"
DEVICE = devtrace.DEVICE_PREFIX + "0"
METADATA = "/host:metadata"


# ------------------------------------------------------- protobuf wire
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """``(field number, value)`` of each field of a serialized protobuf
    message: an int for a varint, bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} not supported")
        yield key >> 3, v


def _first(buf: bytes, number: int, default=b""):
    return next((v for f, v in fields(buf) if f == number), default)


def scoped_instructions(hlo_proto: bytes) -> set[str]:
    """Names of the instructions of a serialized ``xla.HloProto`` whose
    ``op_name`` holds the scope (HloProto.hlo_module 1 ->
    computations 3 -> instructions 2 -> name 1, metadata 7 -> op_name 2)."""
    out = set()
    for f, comp in fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        for g, ins in fields(comp):
            if g == 2:
                meta = _first(ins, 7)
                if SCOPE.encode() in _first(meta, 2):
                    out.add(_first(ins, 1).decode())
    return out


def module_protos(xspace: bytes, module: str) -> list[bytes]:
    """The ``Hlo Proto`` stats of the ``/host:metadata`` plane's event
    metadata named ``<module>(<id>)`` (XSpace.planes 1 -> XPlane name 2,
    event_metadata 4, stat_metadata 5; map entries key 1, value 2;
    XEventMetadata name 2, stats 5; XStat metadata_id 1, bytes_value 6)."""
    for f, plane in fields(xspace):
        if f != 1 or _first(plane, 2) != METADATA.encode():
            continue
        events, stat_names = [], {}
        for g, entry in fields(plane):
            if g == 4:
                events.append(_first(entry, 2))
            elif g == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1, 0)] = _first(meta, 2)
        hlo = {k for k, v in stat_names.items() if v == b"Hlo Proto"}
        return [v for ev in events
                if _first(ev, 2).decode().split("(")[0] == module
                for g, st in fields(ev) if g == 5
                and _first(st, 1, 0) in hlo
                for h, v in fields(st) if h == 6]
    return []


# ------------------------------------------------------- the metric
def scoped_seconds(ops, lo: float, hi: float) -> float | None:
    """Self time in s of the ops ``(start_ns, end_ns, scoped)`` that are
    scoped and start in ``[lo, hi)``; None where none is scoped."""
    got = [t for scoped, (start, t) in devtrace.self_times(ops)
           if scoped and lo <= start < hi]
    return sum(got) * 1e-9 if got else None


@functools.lru_cache(maxsize=1)
def _read(path: str, _mtime_ns: int):
    """The first device's ops in ``jit_loss_fn`` executions as
    ``(start_ns, end_ns, scoped)`` and the window ``(lo, hi)`` (None
    without both markers)."""
    import jax
    with open(path, "rb") as f:
        scoped = set().union(*map(scoped_instructions,
                                  module_protos(f.read(), MODULE)))
    marks: dict[str, list[float]] = {devtrace.OPEN: [], devtrace.CLOSE: []}
    ops, runs = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == DEVICE and line.name == devtrace.MODULES_LINE:
                runs.extend((e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.split("(")[0] == MODULE)
            elif plane.name == DEVICE and line.name == devtrace.OPS_LINE:
                ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                            e.name.split(" = ")[0].lstrip("%") in scoped)
                           for e in line.events)
            elif not plane.name.startswith(devtrace.DEVICE_PREFIX):
                for e in line.events:
                    if e.name in marks:
                        marks[e.name].append(e.start_ns)
    runs.sort()
    starts = [a for a, _ in runs]

    def in_run(t: float) -> bool:
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < runs[i][1]

    ops = [o for o in ops if in_run(o[0])]
    if not marks[devtrace.OPEN] or not marks[devtrace.CLOSE]:
        return ops, None
    return ops, (min(marks[devtrace.OPEN]), max(marks[devtrace.CLOSE]))


def read(run):
    calls = len((run.trace or {}).get("module_s", {}).get(MODULE, []))
    files = sorted(glob.glob(os.path.join(trace_dir(), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not calls or not files:
        return None
    ops, win = _read(files[-1], os.stat(files[-1]).st_mtime_ns)
    s = scoped_seconds(ops, *win) if win else None
    return None if s is None else s / calls
