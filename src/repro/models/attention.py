"""Attention math: train/prefill attention, by one of two paths, and dense
decode attention over a (possibly ring-buffered) KV cache.

Train/prefill attention never materialises the full ``(Tq, Tkv)`` score
matrix in HBM. :func:`gqa_attention` runs it by one of two paths, chosen
by :func:`attention_impl` from what the call shows:

- **flash** — on a single TPU chip (no ``use_rules`` mesh), causal from
  position 0 with ``Dk == Dv``, no soft cap, no window shorter than the
  sequence, and T on the kernel's block grid: the bundled Pallas splash
  kernel (``jax.experimental.pallas.ops.tpu.splash_attention``), forward
  and backward. It keeps each score tile in VMEM, skips the blocks the
  causal mask empties, and reads each KV head once for its G query heads.
- **chunked** — everywhere else (the CPU, the host dry-run, any run under
  a mesh, MLA, ragged or offset shapes): :func:`chunked_attention`, an
  online-softmax over KV chunks inside a ``lax.scan`` with an outer
  ``lax.map`` over Q chunks, in pure jnp. Pallas does not lower on the
  host platform, and a ``pallas_call`` under GSPMD would need a
  ``shard_map``. It is also the oracle the flash path is tested against.

Decode attention is written densely on purpose: with the cache sequence
axis sharded over mesh axes, GSPMD turns the softmax + PV contraction into
the flash-decoding split-K pattern (partial softmax, two small all-reduces)
automatically — see DESIGN.md §5.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.distributed.sharding import rules_active, shard_act
from repro.kernels.tiling import LANE, pick_block
from repro.models.common import soft_cap

NEG_INF = -1e30


@dataclass(frozen=True)
class AttnCfg:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0            # 0 = full attention; >0 = sliding window
    rope_theta: float = 1e4
    qk_norm: bool = False
    softcap: float = 0.0
    bias: bool = False         # qkv projection bias (qwen-style)
    # MLA (DeepSeek-V2); when kv_lora_rank > 0 the MLA path is used.
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def q_group(self) -> int:
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------------
# Flash-style chunked attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      softcap: float = 0.0, q_offset: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 512,
                      remat_qblock: bool = True):
    """Online-softmax attention in FLAT-head layout.

    q: (B, Tq, H, Dk); k: (B, Tkv, H, Dk); v: (B, Tkv, H, Dv)
    returns (B, Tq, H, Dv)

    GQA callers repeat KV heads to H *before* this function (see
    :func:`gqa_attention`): a grouped (B, T, Hkv, G, D) layout splits the
    head dimension into two factors neither of which divides a 16-way
    model axis — measured on danube-1.8b, GSPMD then shards Hkv×G as 8×2
    and emits full-replication all-gathers of score-sized tensors inside
    the backward scan (EXPERIMENTS.md §Perf iterations 1-2). Flat heads
    shard cleanly; the single-chip flash path (:func:`flash_attention`)
    keeps the grouped layout, one KV head read for its G query heads.

    ``q_offset`` is the absolute position of q[0] relative to k[0]
    (chunked prefill / decode-prefill continuation support).
    """
    B, Tq, H, Dk = q.shape
    Tkv = k.shape[1]
    Dv = v.shape[-1]
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tkv)
    # Pad ragged tails to the chunk grid; padded KV is masked below and
    # padded Q rows are sliced off at the end.
    Tq_real, Tkv_real = Tq, Tkv
    pad_q = (-Tq) % q_chunk
    pad_kv = (-Tkv) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        Tq += pad_q
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        Tkv += pad_kv
    nq, nk = Tq // q_chunk, Tkv // kv_chunk
    scale = 1.0 / (Dk ** 0.5)

    k = shard_act(k, ("attn_batch", "seq", "heads", None))
    v = shard_act(v, ("attn_batch", "seq", "heads", None))
    kc = k.reshape(B, nk, kv_chunk, H, Dk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, H, Dv).transpose(1, 0, 2, 3, 4)

    def q_block(args):
        qi, q_blk = args            # q_blk: (B, Cq, H, Dk)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kv):
            m, l, acc = carry
            kj, k_blk, v_blk = kv   # (B, Ck, H, D*)
            kv_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                s = soft_cap(s, softcap)
            mask = (kv_pos[None, :] < Tkv_real)
            if causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            mask = jnp.broadcast_to(mask, (q_chunk, kv_chunk))
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)     # (B, Cq, H, Dv)

    qb = q.reshape(B, nq, q_chunk, H, Dk).transpose(1, 0, 2, 3, 4)
    # Checkpointing the q-block keeps the kv-scan residuals out of the
    # fwd/bwd boundary (the backward recomputes the chunk forward locally)
    # — §Perf iteration 1.
    body = jax.checkpoint(q_block) if remat_qblock else q_block
    out = jax.lax.map(body, (jnp.arange(nq), qb))             # (nq, B, Cq, ...)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, Tq, H, Dv)
    return out[:, :Tq_real]


# ---------------------------------------------------------------------------
# Flash attention (Pallas splash kernel; single-chip TPU train / prefill)
# ---------------------------------------------------------------------------

#: Largest q and kv block of the flash kernel, and largest slice a kv
#: block is computed in. From a sweep of one smollm-360m grad call on a
#: v5e (B 4, T 2048; PERF.md).
FLASH_BLOCK = 1024
FLASH_KV_COMPUTE = 512


def flash_blocks(T: int) -> tuple[int, int]:
    """The flash kernel's (block, kv compute slice) for a sequence of T:
    each the largest whole number of lanes within its limit that divides
    what it tiles, else all of it."""
    block = pick_block(T, FLASH_BLOCK, LANE)
    return block, pick_block(block, FLASH_KV_COMPUTE, LANE)


def attention_impl(T: int, dk: int, dv: int, cfg: AttnCfg, q_offset: int,
                   *, backend: str, meshed: bool) -> str:
    """``"flash"`` or ``"chunked"``: the path :func:`gqa_attention` takes
    for a causal self-attention over T positions with head widths dk/dv,
    on ``backend`` (``jax.default_backend()``), under a ``use_rules``
    mesh when ``meshed``."""
    block, _ = flash_blocks(T)
    if (backend == "tpu" and not meshed and q_offset == 0 and dk == dv
            and cfg.softcap == 0 and (cfg.window == 0 or cfg.window >= T)
            and block % LANE == 0):
        return "flash"
    return "chunked"


@functools.lru_cache(maxsize=None)
def _splash_kernel(T: int, G: int, blocks: tuple[int, int],
                   interpret: bool):
    """The splash MQA kernel for G query heads over one KV head, causal
    over T, with the fused backward; built once per shape."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash
    mask = splash.MultiHeadMask([splash.CausalMask((T, T))] * G)
    block, compute = blocks
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=compute,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)
    # The mask tables are arrays the kernel closes over: make them
    # concrete, so that a kernel first built inside one trace serves
    # every later one.
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=sizes, interpret=interpret)


def flash_attention(q, k, v, *, blocks: tuple[int, int],
                    interpret: bool = False):
    """Causal GQA attention by the Pallas splash kernel, forward and
    backward (its ``custom_vjp``).

    q: (B, T, Hq, D); k, v: (B, T, Hkv, D) → (B, T, Hq, D). Query head h
    reads KV head h // G, as :func:`gqa_attention`'s repeat does. The
    kernel is vmapped over batch and KV heads, so K and V are never
    repeated; q is scaled by 1/sqrt(D) before the call. ``blocks`` is
    the (q and kv block, kv compute slice), as :func:`flash_blocks`
    gives. Products accumulate in f32; the kernel's forward takes P·V in
    f32, every other product in the inputs' dtype."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    kernel = _splash_kernel(T, G, blocks, interpret)
    q = q * jnp.asarray(D ** -0.5, q.dtype)
    q = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(q, k, v)          # (B, Hkv, G, T, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D)


def gqa_attention(q, k, v, cfg: AttnCfg, *, q_offset: int = 0,
                  q_chunk: int = 512, kv_chunk: int = 512):
    """q: (B, T, Hq, Dk) → (B, T, Hq, Dv); k/v: (B, T, Hkv, D*).

    Causal self-attention by the path :func:`attention_impl` picks; the
    choice is recorded once per trace as an ``acan.model.attention``
    instant (ids ``impl``, ``seq``, ``heads``).

    - ``flash`` (single-chip TPU, see the module docstring):
      :func:`flash_attention`, grouped heads; ``q_chunk``/``kv_chunk``
      are unused (the kernel's blocks come from T alone).
    - ``chunked``: KV heads are repeated to Hq (flat layout) and
      :func:`chunked_attention` runs — see its docstring for why; the G×
      activation-memory cost is the price of a clean head sharding under
      a mesh."""
    B, T, Hq, Dk = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    impl = attention_impl(T, Dk, v.shape[-1], cfg, q_offset,
                          backend=jax.default_backend(),
                          meshed=rules_active())
    trace.instant("acan.model.attention", impl=impl, seq=T, heads=Hq)
    if impl == "flash":
        return flash_attention(q, k, v, blocks=flash_blocks(T))
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    out = chunked_attention(q, k, v, causal=True, window=cfg.window,
                            softcap=cfg.softcap, q_offset=q_offset,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)
    return out.reshape(B, T, Hq, -1)


# ---------------------------------------------------------------------------
# Decode attention (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, valid_len, cfg: AttnCfg):
    """q: (B, Hq, Dk); caches: (B, S, Hkv, D*); valid_len: scalar int —
    number of valid cache slots (ring caches pass the full capacity).

    Dense on purpose: GSPMD splits the softmax over the sharded S axis
    (flash-decoding split-K) with two small all-reduces.
    """
    B, S, Hkv, Dk = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Dk)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / (Dk ** 0.5)
    if cfg.softcap > 0:
        s = soft_cap(s, cfg.softcap)
    valid = jnp.arange(S) < valid_len
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, -1).astype(q.dtype)


def mla_decode_attention(q_nope, q_rope, c_cache, krope_cache, w_uk, w_uv,
                         valid_len, cfg: AttnCfg):
    """Absorbed MLA decode (DeepSeek-V2 §"low-rank KV joint compression").

    q_nope: (B, H, Dn); q_rope: (B, H, Dr)
    c_cache: (B, S, R);  krope_cache: (B, S, Dr)
    w_uk: (R, H, Dn);    w_uv: (R, H, Dv)
    Attention runs entirely in the compressed latent space — the cache is
    R + Dr per token instead of 2·H·D (the paper-assigned arch's memory
    feature; see DESIGN.md §4).
    """
    B, S, R = c_cache.shape
    scale = 1.0 / ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)          # (B, H, R)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, krope_cache,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(S) < valid_len
    s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out_lat = jnp.einsum("bhs,bsr->bhr", p.astype(c_cache.dtype), c_cache,
                         preferred_element_type=jnp.float32)
    return jnp.einsum("bhr,rhv->bhv", out_lat.astype(q_nope.dtype), w_uv)
