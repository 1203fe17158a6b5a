"""A plain float32 Mamba-2 language model in ``jax.numpy``, for the tests:
no kernels, no chunking, no cache, and nothing from ``repro.models``.

Per layer (state-spaces/mamba2, arXiv:2405.21060): RMSNorm; the five
input projections z, x, B, C, dt; a depthwise causal conv with a bias
and SiLU on x, B and C; dt = softplus(dt + dt_bias), A = -exp(A_log);
the SSD as its sequential recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

(per head; ``h`` is head_dim x d_state, B and C shared within a group);
a gated RMSNorm ``norm(y * silu(z))`` and the output projection, added to
the residual stream. Then the final norm and the tied logits. The loss is
the mean next-token cross-entropy over every position of every row.

It reads the program's parameter tree (the interface both sides share)
and runs under ``jax.default_matmul_precision("highest")``.

The benchmark keeps a second float32 Mamba-2 layer in
``bench/families/mamba2.py`` on purpose. That one computes the SSD in its
quadratic dual form, at the benchmark's sizes, and is changed only with
the benchmark's own yardstick. This one is the sequential recurrence, the
definition, at test sizes. The unit tests do not import the benchmark,
and the benchmark imports nothing from the tests, so the same mistake in
a shared helper cannot pass both checks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """``x`` (T, C), taps ``w`` (K, C) with tap K-1 on the current
    position, bias ``b`` (C,)."""
    T, K = x.shape[0], w.shape[0]
    return sum(w[k] * jnp.pad(x, ((K - 1 - k, 0), (0, 0)))[:T]
               for k in range(K)) + b


def ssd(x, dt, A, B, C, D):
    """x (T, H, P), dt (T, H), A and D (H,), B and C (T, G, N)."""
    T, H, P = x.shape
    rep = H // B.shape[1]
    Bh, Ch = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + dt_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    h0 = jnp.zeros((H, P, B.shape[2]), F32)
    _, y = jax.lax.scan(step, h0, (x, dt, Bh, Ch))
    return y + D[:, None] * x


def layer(p, h, *, head_dim: int, d_state: int, eps: float):
    """One Mamba-2 layer on one row ``h`` (T, d)."""
    T = h.shape[0]
    u = rms(h, p["ln"], eps)
    z = u @ p["w_z"]
    x = jax.nn.silu(causal_conv(u @ p["w_x"], p["conv_x"], p["conv_x_bias"]))
    B = jax.nn.silu(causal_conv(u @ p["w_B"], p["conv_B"], p["conv_B_bias"]))
    C = jax.nn.silu(causal_conv(u @ p["w_C"], p["conv_C"], p["conv_C_bias"]))
    dt = jax.nn.softplus(u @ p["w_dt"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = ssd(x.reshape(T, -1, head_dim), dt, A,
            B.reshape(T, -1, d_state), C.reshape(T, -1, d_state), p["D"])
    y = rms(y.reshape(T, -1) * jax.nn.silu(z), p["norm_gate"], eps)
    return h + y @ p["w_out"]


def loss(params, tokens, labels, *, head_dim: int, d_state: int,
         eps: float):
    """Mean next-token loss over ``tokens`` (rows, T); ``params`` in the
    program's tree (``period[0]["mamba"]`` stacked over layers)."""
    params = jax.tree.map(lambda a: a.astype(F32), params)
    emb = params["embed"]["tok"]
    stack = params["period"][0]["mamba"]
    n_layers = stack["ln"].shape[0]

    def row(tok, lab):
        h = emb[tok]
        for i in range(n_layers):
            h = layer(jax.tree.map(lambda a, i=i: a[i], stack), h,
                      head_dim=head_dim, d_state=d_state, eps=eps)
        logits = rms(h, params["final_ln"], eps) @ emb.T
        gold = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.vmap(row)(tokens, labels))
