"""Transformer/SSM blocks: param specs + apply paths (train, prefill,
decode) with KV/SSM cache handling.

A layer is described by :class:`LayerCfg` (mixer ∈ {attn, mamba} × ffn ∈
{dense, moe, none}); the unified model (model.py) stacks layers as
``prefix + period × n_periods`` and scans over periods.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.distributed.sharding import shard_act
from repro.models.attention import (AttnCfg, decode_attention, gqa_attention,
                                    mla_decode_attention)
from repro.models.common import ParamSpec, apply_rope, norm_spec, rms_norm
from repro.models.mamba2 import (MambaCfg, _causal_conv, mamba_specs,
                                 ssd_chunked, ssd_decode_step)
from repro.models.mlp import DenseFfnCfg, dense_ffn, dense_ffn_specs
from repro.models.moe import MoECfg, moe_ffn, moe_specs


@dataclass(frozen=True)
class LayerCfg:
    mixer: str                       # "attn" | "mamba"
    attn: AttnCfg | None = None
    mamba: MambaCfg | None = None
    ffn_kind: str = "none"           # "dense" | "moe" | "none"
    dense: DenseFfnCfg | None = None
    moe: MoECfg | None = None
    post_norm: bool = False          # gemma3 sandwich norms
    parallel: bool = False           # command-r parallel attn+ffn residual


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def _attn_specs(d: int, a: AttnCfg, dtype) -> dict:
    s: dict = {"ln": norm_spec(d)}
    if a.is_mla:
        qd = a.qk_nope_dim + a.qk_rope_dim
        s |= {
            "wq": ParamSpec((d, a.n_heads * qd), ("embed", "heads"), dtype),
            "w_dkv": ParamSpec((d, a.kv_lora_rank + a.qk_rope_dim),
                               ("embed", None), dtype),
            "ln_ckv": norm_spec(a.kv_lora_rank),
            "w_uk": ParamSpec((a.kv_lora_rank, a.n_heads, a.qk_nope_dim),
                              (None, "heads", None), dtype),
            "w_uv": ParamSpec((a.kv_lora_rank, a.n_heads, a.v_head_dim),
                              (None, "heads", None), dtype),
            "wo": ParamSpec((a.n_heads * a.v_head_dim, d), ("heads", "embed"),
                            dtype),
        }
    else:
        s |= {
            "wq": ParamSpec((d, a.n_heads * a.head_dim), ("embed", "heads"), dtype),
            "wk": ParamSpec((d, a.n_kv_heads * a.head_dim),
                            ("embed", "kv_heads"), dtype),
            "wv": ParamSpec((d, a.n_kv_heads * a.head_dim),
                            ("embed", "kv_heads"), dtype),
            "wo": ParamSpec((a.n_heads * a.head_dim, d), ("heads", "embed"), dtype),
        }
        if a.bias:
            s |= {
                "bq": ParamSpec((a.n_heads * a.head_dim,), ("heads",), dtype,
                                init="zeros"),
                "bk": ParamSpec((a.n_kv_heads * a.head_dim,), ("kv_heads",),
                                dtype, init="zeros"),
                "bv": ParamSpec((a.n_kv_heads * a.head_dim,), ("kv_heads",),
                                dtype, init="zeros"),
            }
        if a.qk_norm:
            s |= {"q_norm": norm_spec(a.head_dim), "k_norm": norm_spec(a.head_dim)}
    return s


def block_specs(d: int, lcfg: LayerCfg, dtype) -> dict:
    s: dict = {}
    if lcfg.mixer == "attn":
        s["attn"] = _attn_specs(d, lcfg.attn, dtype)
        if lcfg.post_norm:
            s["attn"]["post_ln"] = norm_spec(d)
    else:
        s["mamba"] = {"ln": norm_spec(d)} | mamba_specs(d, lcfg.mamba, dtype)
    if lcfg.ffn_kind == "dense":
        s["ffn"] = {"ln": norm_spec(d)} | dense_ffn_specs(d, lcfg.dense, dtype)
    elif lcfg.ffn_kind == "moe":
        s["ffn"] = {"ln": norm_spec(d)} | moe_specs(d, lcfg.moe, dtype)
    if lcfg.ffn_kind != "none" and lcfg.post_norm:
        s["ffn"]["post_ln"] = norm_spec(d)
    return s


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_specs(lcfg: LayerCfg, batch: int, cache_len: int, dtype) -> dict:
    if lcfg.mixer == "attn":
        a = lcfg.attn
        S = min(cache_len, a.window) if a.window > 0 else cache_len
        if a.is_mla:
            return {
                "c": ParamSpec((batch, S, a.kv_lora_rank),
                               ("batch", "kv_seq", None), dtype, init="zeros"),
                "kr": ParamSpec((batch, S, a.qk_rope_dim),
                                ("batch", "kv_seq", None), dtype, init="zeros"),
            }
        return {
            "k": ParamSpec((batch, S, a.n_kv_heads, a.head_dim),
                           ("batch", "kv_seq", "kv_heads", None), dtype,
                           init="zeros"),
            "v": ParamSpec((batch, S, a.n_kv_heads, a.head_dim),
                           ("batch", "kv_seq", "kv_heads", None), dtype,
                           init="zeros"),
        }
    m = lcfg.mamba
    gn = m.n_groups * m.d_state
    K = m.d_conv - 1
    return {
        "state": ParamSpec((batch, m.n_heads, m.head_dim, m.d_state),
                           ("batch", "heads", None, None), jnp.float32,
                           init="zeros"),
        "cx": ParamSpec((batch, K, m.d_inner), ("batch", None, "mlp"), dtype,
                        init="zeros"),
        "cB": ParamSpec((batch, K, gn), ("batch", None, None), dtype,
                        init="zeros"),
        "cC": ParamSpec((batch, K, gn), ("batch", None, None), dtype,
                        init="zeros"),
    }


# ---------------------------------------------------------------------------
# Attention paths
# ---------------------------------------------------------------------------

def _qkv(h, p, a: AttnCfg, positions, eps: float):
    B, T, _ = h.shape
    q = (h @ p["wq"] + p.get("bq", 0)).reshape(B, T, a.n_heads, a.head_dim)
    k = (h @ p["wk"] + p.get("bk", 0)).reshape(B, T, a.n_kv_heads, a.head_dim)
    v = (h @ p["wv"] + p.get("bv", 0)).reshape(B, T, a.n_kv_heads, a.head_dim)
    if a.qk_norm:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    q = apply_rope(q, positions, a.rope_theta)
    k = apply_rope(k, positions, a.rope_theta)
    return q, k, v


def _mla_qkv(h, p, a: AttnCfg, positions, eps: float):
    B, T, _ = h.shape
    qd = a.qk_nope_dim + a.qk_rope_dim
    q = (h @ p["wq"]).reshape(B, T, a.n_heads, qd)
    q_nope, q_rope = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, a.rope_theta)
    dkv = h @ p["w_dkv"]
    c = rms_norm(dkv[..., :a.kv_lora_rank], p["ln_ckv"], eps)
    kr = apply_rope(dkv[..., None, a.kv_lora_rank:], positions, a.rope_theta)
    return q_nope, q_rope, c, kr[..., 0, :]


def attn_core(p, h, lcfg: LayerCfg, pos0: int = 0, want_cache: bool = False,
              q_chunk: int = 512, kv_chunk: int = 512, *, eps: float):
    """Attention on already-normed input ``h``; returns (out, cache)."""
    a = lcfg.attn
    B, T, _ = h.shape
    positions = pos0 + jnp.arange(T)[None, :]
    cache = None
    if a.is_mla:
        q_nope, q_rope, c, kr = _mla_qkv(h, p, a, positions, eps)
        k_nope = jnp.einsum("btr,rhn->bthn", c, p["w_uk"])
        v = jnp.einsum("btr,rhv->bthv", c, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                      (B, T, a.n_heads, a.qk_rope_dim))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        q = shard_act(q, ("attn_batch", "seq", "heads", None))
        out = gqa_attention(q, k, v, a, q_offset=pos0,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)
        out = out.reshape(B, T, -1) @ p["wo"]
        if want_cache:
            cache = {"c": c, "kr": kr}
    else:
        q, k, v = _qkv(h, p, a, positions, eps)
        q = shard_act(q, ("attn_batch", "seq", "heads", None))
        out = gqa_attention(q, k, v, a, q_offset=pos0,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)
        out = out.reshape(B, T, -1) @ p["wo"]
        if want_cache:
            cache = {"k": k, "v": v}
    if lcfg.post_norm:
        out = rms_norm(out, p["post_ln"], eps)
    return out, cache


def attn_train(p, x, lcfg: LayerCfg, pos0: int = 0, want_cache: bool = False,
               q_chunk: int = 512, kv_chunk: int = 512, *, eps: float):
    out, cache = attn_core(p, rms_norm(x, p["ln"], eps), lcfg, pos0,
                           want_cache, q_chunk, kv_chunk, eps=eps)
    return x + out, cache


def _ring_store(full, window: int):
    """Reorder the last ``window`` entries so entry at absolute position p
    sits at slot p % window (decode-compatible ring layout)."""
    T = full.shape[1]
    W = min(window, T)
    tail = full[:, T - W:]
    pos = (T - W + jnp.arange(W)) % W
    out = jnp.zeros_like(tail)
    return out.at[:, pos].set(tail)


def attn_cache_from_prefill(cache_full: dict, lcfg: LayerCfg) -> dict:
    a = lcfg.attn
    if a.window <= 0:
        return cache_full
    return {k: _ring_store(v, a.window) for k, v in cache_full.items()}


def _attn_decode_core(p, h, cache, cur_len, lcfg: LayerCfg, *, eps: float):
    """h: (B, d) already normed. Returns (out (B, d), cache')."""
    a = lcfg.attn
    B = h.shape[0]
    positions = jnp.full((B, 1), cur_len, jnp.int32)
    h = h[:, None]                               # (B,1,d)
    if a.is_mla:
        q_nope, q_rope, c, kr = _mla_qkv(h, p, a, positions, eps)
        S = cache["c"].shape[1]
        idx = jnp.mod(cur_len, S)
        cache = {
            "c": jax.lax.dynamic_update_slice_in_dim(cache["c"], c, idx, 1),
            "kr": jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr, idx, 1),
        }
        valid = jnp.minimum(cur_len + 1, S)
        out = mla_decode_attention(q_nope[:, 0], q_rope[:, 0], cache["c"],
                                   cache["kr"], p["w_uk"], p["w_uv"], valid, a)
        out = out.reshape(B, -1) @ p["wo"]
    else:
        q, k, v = _qkv(h, p, a, positions, eps)
        S = cache["k"].shape[1]
        idx = jnp.mod(cur_len, S)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, 1),
        }
        valid = jnp.minimum(cur_len + 1, S)
        out = decode_attention(q[:, 0], cache["k"], cache["v"], valid, a)
        out = out.reshape(B, -1) @ p["wo"]
    if lcfg.post_norm:
        out = rms_norm(out, p["post_ln"], eps)
    return out, cache


def attn_decode(p, x, cache, cur_len, lcfg: LayerCfg, *, eps: float):
    """x: (B, d); cur_len: scalar — tokens already in cache."""
    out, cache = _attn_decode_core(p, rms_norm(x, p["ln"], eps), cache,
                                   cur_len, lcfg, eps=eps)
    return x + out, cache


# ---------------------------------------------------------------------------
# Mamba paths
# ---------------------------------------------------------------------------

def _mamba_proj(h, p):
    return (h @ p["w_z"], h @ p["w_x"], h @ p["w_B"], h @ p["w_C"],
            h @ p["w_dt"])


def mamba_train(p, x, lcfg: LayerCfg, want_cache: bool = False, *,
                eps: float):
    """Mamba-2 mixer over a whole sequence. The SSD (the chunked scan
    and its D skip) runs under the ``acan.ssd`` named scope, so its ops
    carry that scope in the device trace, and each trace records an
    ``acan.model.ssd`` instant (ids ``impl``, ``seq``, ``chunk``,
    ``heads``, ``d_state``)."""
    m = lcfg.mamba
    B, T, _ = x.shape
    h = rms_norm(x, p["ln"], eps)
    z, xin, B_, C_, dt_raw = _mamba_proj(h, p)
    xin_pre, B_pre, C_pre = xin, B_, C_
    xin = jax.nn.silu(_causal_conv(xin, p["conv_x"], p["conv_x_bias"]))
    B_ = jax.nn.silu(_causal_conv(B_, p["conv_B"], p["conv_B_bias"]))
    C_ = jax.nn.silu(_causal_conv(C_, p["conv_C"], p["conv_C_bias"]))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    x4 = xin.reshape(B, T, m.n_heads, m.head_dim)
    x4 = shard_act(x4, ("batch", "seq", "heads", None))
    B5 = B_.reshape(B, T, m.n_groups, m.d_state)
    C5 = C_.reshape(B, T, m.n_groups, m.d_state)
    trace.instant("acan.model.ssd", impl="chunked", seq=T,
                  chunk=min(m.chunk, T), heads=m.n_heads, d_state=m.d_state)
    with jax.named_scope("acan.ssd"):
        y, state = ssd_chunked(x4, dt, A, B5, C5, p["D"], m.chunk)
    y = y.reshape(B, T, m.d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["norm_gate"], eps)
    out = y @ p["w_out"]
    cache = None
    if want_cache:
        K = m.d_conv - 1
        cache = {"state": state,
                 "cx": xin_pre[:, T - K:], "cB": B_pre[:, T - K:],
                 "cC": C_pre[:, T - K:]}
    return x + out, cache


def _conv_step(buf, new, kernel, bias):
    """buf: (B, K-1, C) past pre-conv inputs; new: (B, C). Returns conv
    output (B, C) and updated buf."""
    window = jnp.concatenate([buf, new[:, None]], axis=1)     # (B, K, C)
    dt = window.dtype
    out = jnp.einsum("bkc,kc->bc", window, kernel.astype(dt)) + bias.astype(dt)
    return out, window[:, 1:]


def mamba_decode(p, x, cache, lcfg: LayerCfg, *, eps: float):
    m = lcfg.mamba
    B, _ = x.shape
    h = rms_norm(x, p["ln"], eps)
    z, xin, B_, C_, dt_raw = (h @ p["w_z"], h @ p["w_x"], h @ p["w_B"],
                              h @ p["w_C"], h @ p["w_dt"])
    cx_out, ncx = _conv_step(cache["cx"], xin, p["conv_x"], p["conv_x_bias"])
    cB_out, ncB = _conv_step(cache["cB"], B_, p["conv_B"], p["conv_B_bias"])
    cC_out, ncC = _conv_step(cache["cC"], C_, p["conv_C"], p["conv_C_bias"])
    xin = jax.nn.silu(cx_out)
    B_ = jax.nn.silu(cB_out)
    C_ = jax.nn.silu(cC_out)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, state = ssd_decode_step(
        cache["state"], xin.reshape(B, m.n_heads, m.head_dim), dt, A,
        B_.reshape(B, m.n_groups, m.d_state),
        C_.reshape(B, m.n_groups, m.d_state), p["D"])
    y = y.reshape(B, m.d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["norm_gate"], eps)
    out = y @ p["w_out"]
    return x + out, {"state": state, "cx": ncx, "cB": ncB, "cC": ncC}


# ---------------------------------------------------------------------------
# FFN + full block
# ---------------------------------------------------------------------------

def ffn_core(p, h, lcfg: LayerCfg, *, eps: float):
    """FFN on already-normed input; returns (out, aux)."""
    if lcfg.ffn_kind == "dense":
        out = dense_ffn(h, p, lcfg.dense)
        aux = jnp.float32(0.0)
    else:
        B, T, d = h.shape
        flat = shard_act(h.reshape(B * T, d), ("moe_tokens", "embed"))
        out, aux = moe_ffn(flat, p, lcfg.moe)
        out = out.reshape(B, T, d)
    if lcfg.post_norm:
        out = rms_norm(out, p["post_ln"], eps)
    return out, aux


def ffn_apply(p, x, lcfg: LayerCfg, *, eps: float):
    """Pre-norm residual FFN. Returns (x', aux_loss)."""
    if lcfg.ffn_kind == "none":
        return x, jnp.float32(0.0)
    out, aux = ffn_core(p, rms_norm(x, p["ln"], eps), lcfg, eps=eps)
    return x + out, aux


def block_train(p, x, lcfg: LayerCfg, pos0: int = 0, want_cache: bool = False,
                q_chunk: int = 512, kv_chunk: int = 512, *, eps: float):
    """Full block for train/prefill. Returns (x, aux, cache|None).
    ``eps`` is every norm's epsilon (``ModelConfig.norm_eps``)."""
    if lcfg.parallel and lcfg.mixer == "attn" and lcfg.ffn_kind != "none":
        # Command-R parallel residual: shared input norm, summed branches.
        h = rms_norm(x, p["attn"]["ln"], eps)
        a_out, cache = attn_core(p["attn"], h, lcfg, pos0, want_cache,
                                 q_chunk, kv_chunk, eps=eps)
        f_out, aux = ffn_core(p["ffn"], h, lcfg, eps=eps)
        x = x + a_out + f_out
        return shard_act(x, ("batch", "seq", "embed")), aux, cache
    if lcfg.mixer == "attn":
        x, cache = attn_train(p["attn"], x, lcfg, pos0, want_cache,
                              q_chunk, kv_chunk, eps=eps)
    else:
        x, cache = mamba_train(p["mamba"], x, lcfg, want_cache, eps=eps)
    x = shard_act(x, ("batch", "seq", "embed"))
    x, aux = ffn_apply(p.get("ffn"), x, lcfg, eps=eps)
    return x, aux, cache


def block_decode(p, x, cache, cur_len, lcfg: LayerCfg, *, eps: float):
    if lcfg.parallel and lcfg.mixer == "attn" and lcfg.ffn_kind != "none":
        h = rms_norm(x, p["attn"]["ln"], eps)
        a_out, cache = _attn_decode_core(p["attn"], h, cache, cur_len, lcfg,
                                         eps=eps)
        f_out, _ = ffn_core(p["ffn"], h[:, None], lcfg, eps=eps)
        return x + a_out + f_out[:, 0], cache
    if lcfg.mixer == "attn":
        x, cache = attn_decode(p["attn"], x, cache, cur_len, lcfg, eps=eps)
    else:
        x, cache = mamba_decode(p["mamba"], x, cache, lcfg, eps=eps)
    x2, _ = ffn_apply(p.get("ffn"), x[:, None], lcfg, eps=eps)
    return x2[:, 0], cache
