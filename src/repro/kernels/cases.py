"""One call of each Pallas kernel at the widths of the model it serves,
with its inputs (made from a seed) and its pure-jnp reference.

The TPU compile tests and ``chip_smoke.py`` both take their cases from
:func:`main_path_cases`, so what is compiled ahead of time is what runs
on the chip; a CPU test builds the same cases at tiny widths with
``interpret=True``."""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.tile_matmul.ops import matmul
from repro.kernels.tile_matmul.ref import tile_matmul_ref


@dataclass(frozen=True)
class KernelCase:
    name: str
    fn: Callable[..., Any]                 # the kernel call, jit-able
    ref: Callable[..., Any]                # pure-jnp oracle, same outputs
    specs: tuple[jax.ShapeDtypeStruct, ...]
    make_args: Callable[[int], tuple]      # seed -> host (numpy) inputs
    #: Largest admitted ``max|out - ref| / max|ref|`` per output.
    tol: float


def rel_err(out, ref) -> float:
    """Worst error of ``out`` against ``ref`` over every output leaf,
    relative to the largest magnitude in that leaf of the reference."""
    worst = 0.0
    for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        o = np.asarray(o, np.float32)
        r = np.asarray(r, np.float32)
        worst = max(worst, float(np.max(np.abs(o - r)))
                    / max(float(np.max(np.abs(r))), 1e-30))
    return worst


def _normal(rng, shape, dtype, scale=1.0):
    return (rng.standard_normal(shape, np.float32) * scale).astype(dtype)


def matmul_case(m: int, k: int, n: int, dtype=jnp.bfloat16, *,
                interpret: bool = False) -> KernelCase:
    """(m, k) @ (k, n) through the wrapper's own block choice."""
    def make_args(seed):
        rng = np.random.default_rng(seed)
        return (_normal(rng, (m, k), dtype), _normal(rng, (k, n), dtype, 0.1))
    return KernelCase(
        f"tile_matmul {m}x{k}x{n}",
        functools.partial(matmul, interpret=interpret),
        tile_matmul_ref,
        (jax.ShapeDtypeStruct((m, k), dtype),
         jax.ShapeDtypeStruct((k, n), dtype)),
        make_args, tol=2e-2)


def flash_case(bh: int, g: int, t: int, d: int, block: int,
               dtype=jnp.bfloat16, *, interpret: bool = False) -> KernelCase:
    """Causal GQA attention: ``bh`` = batch x kv heads, ``g`` query heads
    per kv head."""
    def make_args(seed):
        rng = np.random.default_rng(seed)
        return (_normal(rng, (bh, g, t, d), dtype),
                _normal(rng, (bh, t, d), dtype),
                _normal(rng, (bh, t, d), dtype))
    return KernelCase(
        f"flash_attention bh={bh} g={g} t={t} d={d}",
        functools.partial(flash_attention, bq=block, bk=block,
                          interpret=interpret),
        flash_attention_ref,
        (jax.ShapeDtypeStruct((bh, g, t, d), dtype),
         jax.ShapeDtypeStruct((bh, t, d), dtype),
         jax.ShapeDtypeStruct((bh, t, d), dtype)),
        make_args, tol=2e-2)


def ssd_case(bh: int, t: int, p: int, n: int, chunk: int, *,
             interpret: bool = False) -> KernelCase:
    """Mamba-2 SSD scan: ``bh`` = batch x heads, state (n, p) per head."""
    f32 = np.float32

    def make_args(seed):
        rng = np.random.default_rng(seed)
        dt = np.log1p(np.exp(rng.standard_normal((bh, t), f32)))  # softplus
        return (_normal(rng, (bh, t, p), f32, 0.5), dt.astype(f32),
                -np.exp(_normal(rng, (bh,), f32, 0.3)),
                _normal(rng, (bh, t, n), f32, 0.5),
                _normal(rng, (bh, t, n), f32, 0.5),
                np.ones((bh,), f32))
    shapes = ((bh, t, p), (bh, t), (bh,), (bh, t, n), (bh, t, n), (bh,))
    return KernelCase(
        f"ssd_scan bh={bh} t={t} p={p} n={n} chunk={chunk}",
        functools.partial(ssd_scan, chunk=chunk, interpret=interpret),
        ssd_scan_ref,
        tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes),
        make_args, tol=2e-2)


def main_path_cases(*, interpret: bool = False) -> tuple[KernelCase, ...]:
    """Each kernel at the widths of the model it serves:

    - tile_matmul at smollm-360m's MLP up-projection for 4096 tokens
      (d_model 960 -> d_ff 2560), the K that needs a full-extent block;
    - flash_attention at smollm-360m's attention (15 q heads over 5 kv
      heads, head_dim 64, seq 2048, micro-batch 4), 256-blocks;
    - ssd_scan at mamba2-2.7b's SSD (80 heads x batch 2, head_dim 64,
      d_state 128, seq 2048, chunk 128)."""
    return (matmul_case(4096, 960, 2560, interpret=interpret),
            flash_case(4 * 5, 3, 2048, 64, 256, interpret=interpret),
            ssd_case(2 * 80, 2048, 64, 128, 128, interpret=interpret))
