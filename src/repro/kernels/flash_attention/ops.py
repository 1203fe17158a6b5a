"""Jitted public wrapper: (B, T, H, D)-layout GQA flash attention."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.tiling import SUBLANE, pick_block


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "q_offset", "bq", "bk", "interpret"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              softcap: float = 0.0, q_offset: int = 0,
              bq: int = 256, bk: int = 256, interpret: bool = False):
    """q: (B, Tq, Hq, D); k, v: (B, Tkv, Hkv, D) → (B, Tq, Hq, D). The
    kernel is compiled for the TPU; ``interpret=True`` runs it in the
    Pallas interpreter (CPU tests only)."""
    B, Tq, Hq, D = q.shape
    Tkv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, Tq, D)
    qf = qf.reshape(B * Hkv, G, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Tkv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Tkv, D)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          softcap=softcap, q_offset=q_offset,
                          bq=pick_block(Tq, bq, SUBLANE),
                          bk=pick_block(Tkv, bk, SUBLANE),
                          interpret=interpret)
    out = out.reshape(B, Hkv, G, Tq, D).reshape(B, Hq, Tq, D)
    return out.transpose(0, 2, 1, 3)
