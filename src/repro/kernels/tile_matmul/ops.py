"""Jitted public wrapper for tile_matmul: picks (8, 128)-legal block
sizes. Blocks that do not fit VMEM are refused by the Mosaic compiler."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.tile_matmul.kernel import tile_matmul
from repro.kernels.tiling import LANE, SUBLANE, pick_block


@partial(jax.jit, static_argnames=("activation", "bm", "bn", "bk",
                                   "interpret"))
def matmul(x, w, b=None, *, activation: str = "none", bm: int = 256,
           bn: int = 256, bk: int = 512, interpret: bool = False):
    """ACAN task-grid GEMM with fused bias+activation epilogue. The kernel
    is compiled for the TPU; ``interpret=True`` runs it in the Pallas
    interpreter (CPU tests only)."""
    M, K = x.shape
    N = w.shape[1]
    # bm is a sublane dim (x, out); bn and bk are lane dims (w/out, x).
    bm, bn, bk = (pick_block(M, bm, SUBLANE), pick_block(N, bn, LANE),
                  pick_block(K, bk, LANE))
    return tile_matmul(x, w, b, activation=activation, bm=bm, bn=bn, bk=bk,
                       interpret=interpret)
