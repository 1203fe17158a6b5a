"""The Llama-layout decoder, for the reference and the counts: RMSNorm,
rotary GQA attention with a full causal softmax, SwiGLU, tied
embeddings. A configuration names its family in ``family``; the
benchmark loads ``bench/families/<family>.py`` and reads from it the
layer's parameters, its equations and its forward FLOPs."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import F32, mm, rms


def layer_layout(cfg: dict) -> dict:
    """One layer's parameters as ``(shape, dtype, init)``, stacked over
    the configuration's layers, in the tree the program trains."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd, ff = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"], cfg["intermediate_size"])
    bf, f32 = cfg["torch_dtype"], "float32"
    return {
        "attn": {"ln": ((L, d), f32, "ones"),
                 "wq": ((L, d, H * hd), bf, "normal"),
                 "wk": ((L, d, Hkv * hd), bf, "normal"),
                 "wv": ((L, d, Hkv * hd), bf, "normal"),
                 "wo": ((L, H * hd, d), bf, "normal")},
        "ffn": {"ln": ((L, d), f32, "ones"),
                "w_gate": ((L, d, ff), bf, "normal"),
                "w_up": ((L, d, ff), bf, "normal"),
                "w_down": ((L, ff, d), bf, "normal")},
    }


def _rope(x, theta: float):
    """x: (T, H, D); rotates the pairs (i, i + D/2) by position."""
    T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(cfg: dict, q8: bool, h, p):
    """One layer on one row ``h`` (T, d), float32."""
    T = h.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a, f = p["attn"], p["ffn"]
    x = rms(h, a["ln"], eps)
    q = mm("td,de->te", x, a["wq"], q8).reshape(T, H, hd)
    k = mm("td,de->te", x, a["wk"], q8).reshape(T, Hkv, hd)
    v = mm("td,de->te", x, a["wv"], q8).reshape(T, Hkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = mm("qhd,khd->hqk", q, k, q8) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, q8)
    h = h + mm("te,ed->td", o.reshape(T, H * hd), a["wo"], q8)
    x = rms(h, f["ln"], eps)
    g = jax.nn.silu(mm("td,df->tf", x, f["w_gate"], q8))
    u = mm("td,df->tf", x, f["w_up"], q8)
    return h + mm("tf,fd->td", g * u, f["w_down"], q8)


def layers_forward_flops_per_token(cfg: dict, seq: int) -> float:
    """The layers' forward FLOPs per token at sequence length ``seq``:
    the matrix products and the causal attention."""
    d, H, Hkv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"],
                         cfg["intermediate_size"])
    proj = 2 * d * H * hd * 2 + 2 * d * Hkv * hd * 2      # q, o; k, v
    ffn = 2 * d * ff * 3                                  # gate, up, down
    # Causal attention: a token at position t attends to t + 1 keys,
    # (seq + 1) / 2 on average, for the scores and again for the values.
    attn = 2 * 2 * H * hd * (seq + 1) / 2
    return cfg["num_hidden_layers"] * (proj + ffn + attn)
