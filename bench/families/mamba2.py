"""The Mamba-2 language model (state-spaces/mamba2, arXiv:2405.21060), for
the reference and the counts: per layer an RMSNorm, five input
projections (z, x, B, C, dt), a depthwise causal conv with a bias and
SiLU on x, B and C, the SSD selective state-space layer with a D skip, a
gated RMSNorm ``norm(y * silu(z))`` and an output projection, on a
residual stream; tied embeddings. A configuration names its family in
``family``; the benchmark loads ``bench/families/<family>.py`` and reads
from it the layer's parameters, its equations and its forward FLOPs.

The SSD here is its quadratic dual form over the whole row, a block of
heads at a time: ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A)
dt_s x_s + D x_t``, the same function as the recurrence ``h_t =
exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t . h_t + D x_t``. The
decay of each pair ``(t, s)`` is a masked cumulative sum of the ``dt_r
A`` between them, never a difference of two long cumulative sums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import mm, rms

#: Heads per block of the SSD: a block's (heads, T, T) decay and mixing
#: matrices are what the reference holds at once (268 MB at T = 2048).
HEAD_BLOCK = 16


def _sizes(cfg: dict) -> tuple[int, int, int, int, int]:
    """d_inner, heads, head_dim, groups, d_state."""
    di, P = cfg["d_inner"], cfg["head_dim"]
    return di, di // P, P, cfg["n_groups"], cfg["d_state"]


def layer_layout(cfg: dict) -> dict:
    """One layer's parameters as ``(shape, dtype, init)``, stacked over
    the configuration's layers, in the tree the program trains.

    The inits are the benchmark's two kinds (``normal`` 0.02 N(0, 1),
    ``ones``), chosen so that the SSD, and the state it carries, decide
    the layer's output: conv taps ``ones`` sum four projected inputs, so
    x, B and C are of order one and ``C . B`` over 128 states is far
    above the D skip; ``A_log`` and ``dt_bias`` ``normal`` give A near -1
    and dt = softplus(x . w_dt) of order one. The configuration's file
    states them under ``assumed``. Matrices and the embedding are in the
    configuration's dtype; the per-channel vectors (norms, conv taps and
    bias, A_log, D, dt_bias) in float32, as the program holds them."""
    d, L, K = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["d_conv"]
    di, H, _, G, N = _sizes(cfg)
    bf, f32 = cfg["torch_dtype"], "float32"
    return {"mamba": {
        "ln": ((L, d), f32, "ones"),
        "w_z": ((L, d, di), bf, "normal"),
        "w_x": ((L, d, di), bf, "normal"),
        "w_B": ((L, d, G * N), bf, "normal"),
        "w_C": ((L, d, G * N), bf, "normal"),
        "w_dt": ((L, d, H), bf, "normal"),
        "conv_x": ((L, K, di), f32, "ones"),
        "conv_B": ((L, K, G * N), f32, "ones"),
        "conv_C": ((L, K, G * N), f32, "ones"),
        "conv_x_bias": ((L, di), f32, "normal"),
        "conv_B_bias": ((L, G * N), f32, "normal"),
        "conv_C_bias": ((L, G * N), f32, "normal"),
        "A_log": ((L, H), f32, "normal"),
        "D": ((L, H), f32, "ones"),
        "dt_bias": ((L, H), f32, "normal"),
        "norm_gate": ((L, di), f32, "ones"),
        "w_out": ((L, di, d), bf, "normal"),
    }}


def _conv(x, w, b):
    """Depthwise causal conv of ``x`` (T, C) with taps ``w`` (K, C), the
    last tap on the current position, and bias ``b`` (C,)."""
    T, K = x.shape[0], w.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[k:k + T] * w[k] for k in range(K)) + b


def _decay(a):
    """``a`` (h, T) -> (h, T, T): ``exp(sum_{s<r<=t} a_r)`` at ``[t, s]``
    for ``s <= t``, else 0, from a masked cumulative sum."""
    T = a.shape[-1]
    r = jnp.arange(T)
    seg = jnp.where(r[:, None] > r[None, :], a[:, :, None], 0.0)  # [h, r, s]
    cs = jnp.cumsum(seg, axis=1)
    return jnp.where(r[:, None] >= r[None, :], jnp.exp(cs), 0.0)


def _ssd(q8: bool, x, dt, A, B, C):
    """The SSD without its skip on one row: x (T, H, P), dt (T, H), A
    (H,), B and C (T, G, N) -> y (T, H, P), a block of heads at a time."""
    T, H, P = x.shape
    G = B.shape[1]
    hb = max(k for k in range(1, min(H, HEAD_BLOCK) + 1) if H % k == 0)
    scores = mm("tgn,sgn->gts", C, B, q8)                  # (G, T, T)
    group = jnp.arange(H).reshape(H // hb, hb) // (H // G)

    @jax.checkpoint
    def block(args):
        xb, dtb, Ab, gb = args                  # (T,hb,P), (T,hb), (hb,), (hb,)
        m = scores[gb] * _decay((dtb * Ab).T) * dtb.T[:, None, :]
        return mm("hts,shp->thp", m, xb, q8)

    ys = jax.lax.map(block, (
        x.reshape(T, H // hb, hb, P).transpose(1, 0, 2, 3),
        dt.reshape(T, H // hb, hb).transpose(1, 0, 2),
        A.reshape(H // hb, hb), group))
    return ys.transpose(1, 0, 2, 3).reshape(T, H, P)


def layer(cfg: dict, q8: bool, h, p):
    """One layer on one row ``h`` (T, d), float32."""
    T = h.shape[0]
    di, H, P, G, N = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    m = p["mamba"]
    u = rms(h, m["ln"], eps)
    z = mm("td,de->te", u, m["w_z"], q8)
    x = jax.nn.silu(_conv(mm("td,de->te", u, m["w_x"], q8), m["conv_x"],
                          m["conv_x_bias"]))
    B = jax.nn.silu(_conv(mm("td,dn->tn", u, m["w_B"], q8), m["conv_B"],
                          m["conv_B_bias"]))
    C = jax.nn.silu(_conv(mm("td,dn->tn", u, m["w_C"], q8), m["conv_C"],
                          m["conv_C_bias"]))
    dt = jax.nn.softplus(mm("td,dh->th", u, m["w_dt"], q8) + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    x = x.reshape(T, H, P)
    y = _ssd(q8, x, dt, A, B.reshape(T, G, N), C.reshape(T, G, N))
    y = (y + m["D"][:, None] * x).reshape(T, di)
    y = rms(y * jax.nn.silu(z), m["norm_gate"], eps)
    return h + mm("te,ed->td", y, m["w_out"], q8)


def layers_forward_flops_per_token(cfg: dict, seq: int) -> float:  # noqa: ARG001
    """The layers' forward FLOPs per token: the five input projections
    and the output projection, and the SSD as its recurrence's work,
    ``4 H P N`` a token (the state's decay and update, and its read by
    C), which no chunking or kernel changes; the same at every ``seq``."""
    d = cfg["hidden_size"]
    di, H, P, G, N = _sizes(cfg)
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    return cfg["num_hidden_layers"] * (proj + 4 * H * P * N)
