"""Jitted public wrapper: (B, T, H, P)-layout SSD with grouped B/C."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool = False):
    """x: (Bt, T, H, P); dt: (Bt, T, H); A, D: (H,); B, C: (Bt, T, G, N).

    Returns (y (Bt, T, H, P), final_state (Bt, H, N, P)). The kernel is
    compiled for the TPU; ``interpret=True`` runs it in the Pallas
    interpreter (CPU tests only)."""
    Bt, T, H, P = x.shape
    G, N = B.shape[-2], B.shape[-1]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)          # (Bt, T, H, N)
    Ch = jnp.repeat(C, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(Bt * H, T, P)
    dtf = dt.transpose(0, 2, 1).reshape(Bt * H, T)
    bf = Bh.transpose(0, 2, 1, 3).reshape(Bt * H, T, N)
    cf = Ch.transpose(0, 2, 1, 3).reshape(Bt * H, T, N)
    af = jnp.tile(A, Bt)
    df = jnp.tile(D, Bt)
    y, s = ssd_scan(xf, dtf, af, bf, cf, df, chunk=chunk,
                    interpret=interpret)
    return (y.reshape(Bt, H, T, P).transpose(0, 2, 1, 3),
            s.reshape(Bt, H, N, P))
