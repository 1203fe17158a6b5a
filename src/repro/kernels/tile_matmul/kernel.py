"""Pallas TPU kernel: the ACAN task-grid tiled GEMM (paper §5.2 adapted).

The paper partitions a forward task over ``(m inputs, n outputs)`` into
uniform quadrants; on TPU the natural fixed-size task is an MXU-aligned
``(bm, bn, bk)`` tile. The grid *is* the ACAN task grid: every (i, j)
output tile is an independent, idempotent task (re-execution rewrites the
same bytes — the paper's §5.4 redundancy argument holds tile-wise), and
the k-loop is the within-task reduction.

Beyond-paper fusion: the paper's separate ``activation`` task is fused
into the forward task's epilogue (bias + activation applied in VMEM before
the tile is written back) — one HBM round-trip instead of two.

Block sizes must be multiples of the MXU/VREG tiling (128 lanes; 8
sublanes fp32) or the whole dim; ops.py picks them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {
    "none": lambda x: x,
    "tanh": jnp.tanh,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation: str,
            has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        out = _ACTS[activation](out)
        o_ref[...] = out.astype(o_ref.dtype)


def tile_matmul(x, w, b=None, *, activation: str = "none",
                bm: int = 128, bn: int = 128, bk: int = 128,
                out_dtype=None, interpret: bool = False):
    """x: (M, K) @ w: (K, N) [+ b: (N,)] with fused epilogue.

    Grid is (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics — the
    accumulator scratch is carried across k steps); M/N parallel.
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"blocks ({bm}, {bn}, {bk}) do not divide "
                         f"({M}, {N}, {K})")
    out_dtype = out_dtype or x.dtype
    has_bias = b is not None
    if b is None:
        b = jnp.zeros((N,), x.dtype)
    b2 = b.reshape(1, N)

    kern = functools.partial(_kernel, activation=activation,
                             has_bias=has_bias)
    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, _j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda _i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda _i, j, _k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, _k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, b2)
