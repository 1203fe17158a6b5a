"""Block-size choice shared by the Pallas TPU kernel wrappers.

Mosaic tiles the last two dims of every block in (8, 128) units
(sublanes, lanes): a block dim is legal when it is a multiple of its
tile or the whole extent of the array's dim."""

from __future__ import annotations

SUBLANE = 8
LANE = 128


def pick_block(dim: int, target: int, align: int) -> int:
    """Largest block <= ``target`` that divides ``dim`` and is a multiple
    of ``align``; the whole ``dim`` when it fits ``target`` or has no such
    aligned divisor (a full-extent block is always legal)."""
    if dim <= target:
        return dim
    for b in range(target - target % align, 0, -align):
        if dim % b == 0:
            return b
    return dim
