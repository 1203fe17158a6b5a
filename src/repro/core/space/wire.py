"""Length-prefixed binary framing for the remote tuple space (PR 10).

One *frame* carries one message (a request, a response, or an
unsolicited invalidation) and is laid out so ndarray payloads travel as
raw buffer-protocol bytes, never through a pickle byte-copy:

    [u32 body_len]
    [u32 n_buffers][u64 pickle_len][u64 buf_len x n_buffers]   header
    [pickle bytes (protocol 5, out-of-band buffers elided)]
    [raw buffer bytes ...]

Encoding uses pickle protocol 5 with a ``buffer_callback``: every
contiguous ndarray (or other buffer-protocol object) inside the message
is *elided* from the pickle stream and appended as its own raw segment.
:func:`send_msg` hands the segment list to ``socket.sendmsg`` as a
gather write — one syscall per frame for typical sizes, zero copies of
array bodies on the way out. :func:`recv_msg` reads the body into one
buffer and reconstructs arrays over zero-copy ``memoryview`` slices of
it (``pickle.loads(..., buffers=...)``), so a weight tensor crosses the
wire with exactly one copy end to end (the kernel socket transfer).

Put host (numpy) arrays, not device arrays (``jax.Array``): decoding
one calls ``device_put``, which would make a host-only process (the
tuple-space server, a handler worker) claim the accelerator its parent
holds.

The framing is transport-agnostic: anything with ``sendmsg``/
``recv_into`` works (tests drive it over ``socket.socketpair`` with
deliberately fragmented writes to exercise partial-read recovery).
"""

from __future__ import annotations

import os
import pickle
import struct
from typing import Any

__all__ = ["FrameError", "IOV_MAX", "MAX_FRAME", "decode_msg",
           "encode_segments", "recv_exact", "recv_msg", "send_msg"]

_LEN = struct.Struct("<I")
_HDR = struct.Struct("<IQ")
_BUF = struct.Struct("<Q")


def _iov_max() -> int:
    """The kernel's per-``sendmsg`` iovec cap (Linux: typically 1024).
    A frame with more out-of-band buffers than this must be sent in
    several ``sendmsg`` calls — exceeding the cap fails the whole send
    with ``EMSGSIZE``, which callers would misread as a dead
    connection."""
    try:
        n = os.sysconf("SC_IOV_MAX")
    except (AttributeError, OSError, ValueError):
        n = -1
    return n if n > 0 else 1024


#: Max segments handed to one ``sendmsg`` call (see :func:`_iov_max`).
IOV_MAX = _iov_max()

#: Upper bound on one frame's body — a corrupted/foreign length prefix
#: must fail loudly instead of allocating gigabytes.
MAX_FRAME = 1 << 31


class FrameError(ConnectionError):
    """Malformed frame (bad length prefix / truncated header)."""


def encode_segments(msg: Any) -> list[Any]:
    """Encode ``msg`` into the frame's segment list (bytes/memoryviews),
    ready for a gather write. Array bodies are referenced, not copied."""
    raw: list[Any] = []

    def _grab(pb: pickle.PickleBuffer) -> None:
        raw.append(pb.raw())              # flat view, zero-copy

    try:
        pk = pickle.dumps(msg, protocol=5, buffer_callback=_grab)
    except BufferError:
        # A non-contiguous buffer slipped through: fall back to in-band
        # pickling for the whole message (correct, just not zero-copy).
        raw = []
        pk = pickle.dumps(msg, protocol=5)
    header = (_HDR.pack(len(raw), len(pk))
              + b"".join(_BUF.pack(len(r)) for r in raw))
    body_len = len(header) + len(pk) + sum(len(r) for r in raw)
    if body_len > MAX_FRAME:
        raise FrameError(f"frame body {body_len} exceeds MAX_FRAME")
    return [_LEN.pack(body_len), header, pk, *raw]


def decode_msg(body) -> Any:
    """Decode one frame body (everything after the u32 length prefix)."""
    view = memoryview(body)
    if len(view) < _HDR.size:
        raise FrameError("truncated frame header")
    n_bufs, pk_len = _HDR.unpack_from(view, 0)
    off = _HDR.size
    lens = []
    for _ in range(n_bufs):
        if off + _BUF.size > len(view):
            raise FrameError("truncated buffer-length table")
        lens.append(_BUF.unpack_from(view, off)[0])
        off += _BUF.size
    if off + pk_len + sum(lens) != len(view):
        raise FrameError("frame body length mismatch")
    pk = view[off:off + pk_len]
    off += pk_len
    bufs = []
    for ln in lens:
        bufs.append(view[off:off + ln])
        off += ln
    return pickle.loads(pk, buffers=bufs)


def send_msg(sock, msg: Any, lock=None) -> None:
    """Frame and send ``msg``; gather write, partial-send safe. ``lock``
    (when given) serializes concurrent senders on one socket."""
    segs = [memoryview(s).cast("B") for s in encode_segments(msg)
            if len(s)]
    if lock is not None:
        with lock:
            _send_segments(sock, segs)
    else:
        _send_segments(sock, segs)


def _send_segments(sock, segs: list) -> None:
    while segs:
        try:
            # Never hand the kernel more than IOV_MAX iovecs — a large
            # put_many/snapshot frame can carry thousands of array
            # segments, and an over-long vector fails outright with
            # EMSGSIZE. The outer loop drains whatever remains.
            sent = sock.sendmsg(segs[:IOV_MAX])
        except AttributeError:            # transport without sendmsg
            for s in segs:
                sock.sendall(s)
            return
        while sent > 0:
            if sent >= len(segs[0]):
                sent -= len(segs[0])
                segs.pop(0)
            else:
                segs[0] = segs[0][sent:]
                sent = 0


def recv_exact(sock, n: int) -> bytearray:
    """Read exactly ``n`` bytes (looping over short reads) into one
    buffer; raises ``ConnectionError`` on EOF mid-frame."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("connection closed mid-frame")
        got += r
    return buf


def recv_msg(sock) -> Any:
    """Read one complete frame and decode it. Raises ``ConnectionError``
    on clean EOF at a frame boundary too — callers treat any read
    failure as connection loss."""
    prefix = recv_exact(sock, _LEN.size)
    (body_len,) = _LEN.unpack(prefix)
    if body_len > MAX_FRAME:
        raise FrameError(f"frame length {body_len} exceeds MAX_FRAME")
    return decode_msg(recv_exact(sock, body_len))
