"""Pallas TPU kernel: causal GQA flash attention with sliding-window and
logit-softcap support.

Online-softmax over KV blocks (FlashAttention-2 schedule): grid is
``(B·Hkv, Tq/bq, Tkv/bk)`` with the KV dimension innermost ("arbitrary")
so the (m, l, acc) running statistics live in VMEM scratch across KV
steps. GQA is handled by folding the ``G = Hq/Hkv`` query group into the
block (one KV head's K/V tile is reused by all G query heads — the whole
point of GQA on TPU: K/V HBM traffic divided by G).

Fully-masked KV blocks (beyond the causal frontier or behind the sliding
window) are skipped with ``pl.when`` — block-level sparsity, the kernel
analogue of the ACAN precondition check."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, causal: bool, window: int, softcap: float,
            q_offset: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_offset + iq * bq
    kv_start = ik * bk

    # Block-level skip: fully above the causal diagonal or fully outside
    # the sliding window.
    live = True
    if causal:
        live = jnp.asarray(kv_start <= q_start + bq - 1)
    if window > 0:
        live = jnp.logical_and(live,
                               jnp.asarray(kv_start + bk > q_start - window + 1))

    @pl.when(live)
    def _step():
        q = q_ref[0]                      # (G, bq, D)
        k = k_ref[0]                      # (bk, D)
        v = v_ref[0]                      # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bq, bk)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= kv_pos <= q_pos
        if window > 0:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (G, bq, D)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    bq: int = 256, bk: int = 256,
                    interpret: bool = False):
    """q: (BH, G, Tq, D); k, v: (BH, Tkv, D). Returns (BH, G, Tq, D).

    BH = batch · kv_heads (folded by ops.py); G = query heads per KV head.
    """
    BH, G, Tq, D = q.shape
    Tkv = k.shape[1]
    bq, bk = min(bq, Tq), min(bk, Tkv)
    if Tq % bq or Tkv % bk:
        raise ValueError(f"blocks ({bq}, {bk}) do not divide ({Tq}, {Tkv})")
    scale = 1.0 / (D ** 0.5)

    kern = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                             window=window, softcap=softcap,
                             q_offset=q_offset, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(BH, Tq // bq, Tkv // bk),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda bh, iq, _ik: (bh, 0, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, _iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, _iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D),
                               lambda bh, iq, _ik: (bh, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, Tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
