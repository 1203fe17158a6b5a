"""The numbers that decide ``correct`` for a training cell.

The program's first ``k`` steps (driven through the window's own call,
``ACANCloud.run``) are held to the float32 reference's first ``k`` steps
from the same weights and batches:

- ``loss``: the largest |program loss - reference loss| / reference loss
  over the ``k`` steps;
- ``grad``: the first gradient as the optimizer got it, worked out from
  the state after one step, ``(p0 - p1) / lr``; per leaf the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
- ``change``: the same of the parameters' change ``p_k - p0``.

Leaves whose exact first reference gradient is under a thousandth of the
median leaf's are left out of ``grad`` and ``change``: they move by
round-off alone.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

SMALL_GRAD = 1e-3


@dataclass
class Trajectory:
    """A run's first ``k`` steps: each step's loss, the state after one
    step and after ``k`` (host trees), and (for the reference) the exact
    first mean gradient."""

    losses: list
    p1: object
    pk: object
    grad0: object = None


def leaf_norms(fn, *trees) -> np.ndarray:
    """The 2-norm of ``fn`` of each leaf tuple, leaf by leaf in float32
    on the host (one leaf of each tree in memory at a time)."""
    out = []
    for xs in zip(*(jax.tree.leaves(t) for t in trees)):
        v = fn(*(np.asarray(jax.device_get(x), np.float32) for x in xs))
        out.append(float(np.sqrt(np.sum(np.square(v, dtype=np.float64)))))
    return np.array(out)


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray) -> float:
    """max over kept leaves of | |prog| - |ref| | / max(|ref|, median|ref|)."""
    med = float(np.median(ref[keep]))
    return float(np.max((np.abs(prog - ref) / np.maximum(ref, med))[keep]))


def numbers(lr: float, p0, prog: Trajectory, ref: Trajectory) -> dict:
    """The compared numbers of ``prog`` against ``ref``."""
    g0 = leaf_norms(lambda g: g, ref.grad0)
    keep = g0 >= SMALL_GRAD * float(np.median(g0))
    if len(prog.losses) != len(ref.losses):
        loss = float("inf")
    else:
        loss = max(abs(a - b) / abs(b)
                   for a, b in zip(prog.losses, ref.losses))

    def first_grad(p1):
        return leaf_norms(lambda a, b: (a - b) / lr, p0, p1)

    def change(pk):
        return leaf_norms(lambda a, b: b - a, p0, pk)

    return {
        "loss": float(loss),
        "grad": worst_leaf_gap(first_grad(prog.p1), first_grad(ref.p1), keep),
        "change": worst_leaf_gap(change(prog.pk), change(ref.pk), keep),
        "leaves_kept": int(keep.sum()),
        "leaves": int(keep.size),
    }
