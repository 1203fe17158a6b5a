"""Mamba-2 SSD (state-space duality) mixer — chunked scan formulation
[arXiv:2405.21060], plus the O(1)-state decode step.

The chunked algorithm *is* the paper's fixed-size-task discipline applied
along time (DESIGN.md §4): the sequence splits into uniform chunks; each
chunk is an independent task (intra-chunk quadratic part) plus a small
state hand-off (inter-chunk recurrence) — exactly the shape a Pallas grid
wants (see ``kernels/ssd_scan``).

Projections are kept **unfused** (separate z/x/B/C/dt matrices) so each can
carry its own sharding axis cleanly under GSPMD — semantically identical to
the fused in_proj of the reference implementation; noted in DESIGN.md.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec


@dataclass(frozen=True)
class MambaCfg:
    d_inner: int
    d_state: int = 128
    d_conv: int = 4
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


def mamba_specs(d_model: int, cfg: MambaCfg, dtype) -> dict:
    gn = cfg.n_groups * cfg.d_state
    return {
        "w_z": ParamSpec((d_model, cfg.d_inner), ("embed", "mlp"), dtype),
        "w_x": ParamSpec((d_model, cfg.d_inner), ("embed", "mlp"), dtype),
        "w_B": ParamSpec((d_model, gn), ("embed", None), dtype),
        "w_C": ParamSpec((d_model, gn), ("embed", None), dtype),
        "w_dt": ParamSpec((d_model, cfg.n_heads), ("embed", "heads"), dtype),
        # The depthwise conv's taps and its bias (published, split as the
        # taps are) are per-channel vectors, held in float32 as the norms,
        # A_log, D and dt_bias are and applied in the activations' dtype:
        # a bfloat16 tap near 1 has an ulp of 2^-7, above most SGD steps.
        "conv_x": ParamSpec((cfg.d_conv, cfg.d_inner), (None, "mlp"),
                            jnp.float32, init="small"),
        "conv_B": ParamSpec((cfg.d_conv, gn), (None, None), jnp.float32,
                            init="small"),
        "conv_C": ParamSpec((cfg.d_conv, gn), (None, None), jnp.float32,
                            init="small"),
        "conv_x_bias": ParamSpec((cfg.d_inner,), ("mlp",), jnp.float32,
                                 init="zeros"),
        "conv_B_bias": ParamSpec((gn,), (None,), jnp.float32, init="zeros"),
        "conv_C_bias": ParamSpec((gn,), (None,), jnp.float32, init="zeros"),
        "A_log": ParamSpec((cfg.n_heads,), ("heads",), jnp.float32, init="zeros"),
        "D": ParamSpec((cfg.n_heads,), ("heads",), jnp.float32, init="ones"),
        "dt_bias": ParamSpec((cfg.n_heads,), ("heads",), jnp.float32,
                             init="zeros"),
        "norm_gate": ParamSpec((cfg.d_inner,), ("mlp",), jnp.float32,
                               init="ones"),
        "w_out": ParamSpec((cfg.d_inner, d_model), ("mlp", "embed"), dtype),
    }


def _causal_conv(x, kernel, bias):
    """x: (B, T, C); kernel: (K, C), bias: (C,) depthwise causal conv, in
    x's dtype."""
    K = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, kernel.astype(x.dtype)[:, None, :],
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + bias.astype(x.dtype)


def _segsum(dA):
    """dA: (..., Q) → (..., Q, Q) lower-tri cumulative sums
    L[i, j] = Σ_{j < s ≤ i} dA_s  (i ≥ j), -inf above diagonal."""
    Q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # (..., i, j)
    i = jnp.arange(Q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """SSD forward.

    x: (Bt, T, H, P); dt: (Bt, T, H) (post-softplus, ≥0)
    A: (H,) (negative); B, C: (Bt, T, G, N); D: (H,)
    returns y: (Bt, T, H, P), final_state: (Bt, H, P, N)
    """
    Bt, T, H, P = x.shape
    G, N = B.shape[-2], B.shape[-1]
    rep = H // G
    Q = min(chunk, T)
    # Pad ragged tails with dt=0 steps (decay 1, zero input weight) — they
    # leave the state untouched; padded outputs are sliced off.
    T_real = T
    pad = (-T) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        T += pad
    nc = T // Q

    xc = x.reshape(Bt, nc, Q, H, P)
    dtc = dt.reshape(Bt, nc, Q, H)
    Bc = B.reshape(Bt, nc, Q, G, N)
    Cc = C.reshape(Bt, nc, Q, G, N)

    dA = dtc * A[None, None, None, :]                       # (Bt,nc,Q,H) ≤0

    def chunk_step(state, inp):
        xq, dtq, dAq, Bq, Cq = inp
        # (Bt,Q,H,P), (Bt,Q,H), (Bt,Q,H), (Bt,Q,G,N), (Bt,Q,G,N)
        L = jnp.exp(_segsum(dAq.transpose(0, 2, 1)))        # (Bt,H,Q,Q)
        scores = jnp.einsum("bqgn,bkgn->bgqk", Cq, Bq,
                            preferred_element_type=jnp.float32)
        scores = jnp.repeat(scores, rep, axis=1)            # (Bt,H,Q,Q)
        M = scores * L * dtq.transpose(0, 2, 1)[:, :, None, :]
        y_diag = jnp.einsum("bhqk,bkhp->bqhp", M.astype(x.dtype), xq,
                            preferred_element_type=jnp.float32)
        # inter-chunk: contribution of carried state
        cum = jnp.cumsum(dAq, axis=1)                       # (Bt,Q,H)
        decay_in = jnp.exp(cum)                             # (Bt,Q,H)
        Cq_h = jnp.repeat(Cq, rep, axis=2)                  # (Bt,Q,H,N)
        y_off = jnp.einsum("bqhn,bhpn,bqh->bqhp", Cq_h, state, decay_in,
                           preferred_element_type=jnp.float32)
        # state update: S' = exp(total_dA) S + Σ_q exp(total - cum_q) B_q dt_q x_q
        total = cum[:, -1]                                  # (Bt,H)
        w = jnp.exp(total[:, None] - cum) * dtq             # (Bt,Q,H)
        Bq_h = jnp.repeat(Bq, rep, axis=2)                  # (Bt,Q,H,N)
        s_new = jnp.einsum("bqhn,bqhp,bqh->bhpn", Bq_h, xq, w,
                           preferred_element_type=jnp.float32)
        state = jnp.exp(total)[..., None, None] * state + s_new
        return state, (y_diag + y_off).astype(x.dtype)

    state0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          dA.transpose(1, 0, 2, 3), Bc.transpose(1, 0, 2, 3, 4),
          Cc.transpose(1, 0, 2, 3, 4))
    state, yc = jax.lax.scan(chunk_step, state0, xs)
    y = yc.transpose(1, 0, 2, 3, 4).reshape(Bt, T, H, P)
    y = (y + x * D[None, None, :, None]).astype(x.dtype)
    return y[:, :T_real], state


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t, D):
    """Single-token SSD update.

    state: (Bt, H, P, N); x_t: (Bt, H, P); dt_t: (Bt, H);
    B_t, C_t: (Bt, G, N) → y_t: (Bt, H, P), new state.
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    Bh = jnp.repeat(B_t, rep, axis=1)                       # (Bt,H,N)
    Ch = jnp.repeat(C_t, rep, axis=1)
    dA = jnp.exp(dt_t * A[None, :])                         # (Bt,H)
    upd = jnp.einsum("bhn,bhp,bh->bhpn", Bh, x_t, dt_t,
                     preferred_element_type=jnp.float32)
    state = dA[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch,
                   preferred_element_type=jnp.float32)
    return (y + x_t * D[None, :, None]).astype(x_t.dtype), state
