"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

The sequence is partitioned into uniform chunks — the paper's fixed-size
task discipline applied along time (DESIGN.md §4). Grid is
``(B·H, T/Q)`` with the chunk dimension innermost ("arbitrary"): the
running SSM state ``(N, P)`` lives in VMEM scratch and is carried across
chunk steps; each chunk step does the intra-chunk quadratic part (three
small MXU matmuls) plus the state hand-off.

Inputs are pre-expanded to per-head B/C (the ops wrapper repeats groups)
so the kernel body is a clean per-(batch, head) program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, s_final_ref,
            state_ref, *, Q: int):
    bh = pl.program_id(0)
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)      # (1, Q) row
    a = a_ref[bh]                           # scalar (per head, SMEM)
    d = d_ref[bh]                           # scalar (SMEM)
    b = b_ref[0].astype(jnp.float32)        # (Q, N)
    c = c_ref[0].astype(jnp.float32)        # (Q, N)

    # Everything stays 2-D: the per-step vectors are kept both as a row
    # (1, Q) and as a column (Q, 1), and the prefix sums are masked lane/
    # sublane reductions (no 1-D layouts, cumsum or transposes, which the
    # TPU lowering tiles badly or refuses).
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = ii >= jj
    dA = dt * a                                                   # (1, Q) <= 0
    dt_col = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=1, keepdims=True)
    dA_col = jnp.sum(jnp.where(ii == jj, dA, 0.0), axis=1, keepdims=True)
    cum_col = jnp.sum(jnp.where(lower, dA, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ii <= jj, dA_col, 0.0), axis=0,
                      keepdims=True)                              # (1, Q)
    total = jnp.sum(dA, axis=1, keepdims=True)                    # (1, 1)
    # Intra-chunk: y_diag[i] = Σ_{j≤i} (c_i·b_j) exp(cum_i - cum_j) dt_j x_j
    L = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)         # (Q, Q)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * L * dt
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)
    # Inter-chunk: y_off[i] = exp(cum_i) · c_i @ state   (state: (N, P))
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        c, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # State update: S' = exp(cum_Q) S + Σ_j exp(cum_Q - cum_j) dt_j b_j ⊗ x_j
    w = jnp.exp(total - cum_col) * dt_col                         # (Q, 1)
    s_new = jax.lax.dot_general(b * w, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(total) * state_ref[...] + s_new

    y_ref[0] = (y + d * x).astype(y_ref.dtype)

    @pl.when(ic == pl.num_programs(1) - 1)
    def _final():
        s_final_ref[0] = state_ref[...].astype(s_final_ref.dtype)


def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128,
             interpret: bool = False):
    """x: (BH, T, P); dt: (BH, T); a, d: (BH,); b, c: (BH, T, N).

    Returns (y: (BH, T, P), final_state: (BH, N, P)). ``dt`` is laid out
    as (BH, 1, T) so its block is a (1, Q) row; the per-head scalars
    ``a``/``d`` live whole in SMEM."""
    BH, T, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"sequence {T} is not a multiple of chunk {Q}")

    kern = functools.partial(_kernel, Q=Q)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kern,
        grid=(BH, T // Q),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, Q), lambda bh, ic: (bh, 0, ic)),
            smem,
            pl.BlockSpec((1, Q, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ic: (bh, ic, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, N, P), lambda bh, _ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt.reshape(BH, 1, T), a.astype(jnp.float32), b, c,
      d.astype(jnp.float32))
