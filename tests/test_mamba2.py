"""Mamba-2 on the program's train path against a plain float32 reference
(``mamba2_reference.py``: the SSD as its sequential recurrence, no
chunks), on seeded random weights at a small size on the CPU; a planted
fault (the carried state dropped between chunks) that the comparison
must catch; the configuration's norm epsilon in every norm; and the
``acan.ssd`` scope in the lowered gradient program."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mamba2_reference as ref
from repro.configs import ARCH_IDS, get_config
from repro.models import blocks
from repro.models import model as M

#: Loss: relative gap. Every float32 sum runs in another order in the
#: chunked scan than in the recurrence: on seeds 0-3 at T 40 and 48 the
#: gap was at most 1.7e-7; a dropped hand-off read 8e-5 to 2.4e-4.
LOSS_TOL = 2e-6
#: Gradients: per leaf |g - g_ref| / |g_ref|. The largest gap seen was
#: 2.8e-6 (dt_bias, A_log and D, whose gradients sum over every position);
#: a dropped hand-off read 0.29 to 0.95 on the worst leaf (A_log, w_dt).
GRAD_TOL = 3e-5


def _config(chunk: int = 16):
    """Two layers of d_model 32, d_inner 64 (4 heads x 16), d_state 16,
    one group, in float32, with the chunk given."""
    cfg = get_config("mamba2_2_7b", reduced=True)
    layer = cfg.period[0]
    return dataclasses.replace(cfg, period=(dataclasses.replace(
        layer, mamba=dataclasses.replace(layer.mamba, chunk=chunk)),))


def _weights(cfg, seed: int):
    """Seeded weights in the published init ranges, so that the state
    carries across many chunks: A = -U[1, 16], dt = softplus(dt_bias)
    log-uniform in [1e-3, 0.1], conv taps and biases U(-0.5, 0.5),
    matrices N(0, 1/fan_in), norm scales near 1."""
    rng = np.random.default_rng(seed)
    tree = M.abstract_params(cfg)

    def init(path, s):
        name, shape = path[-1].key, s.shape
        if name == "A_log":
            return np.log(rng.uniform(1, 16, shape))
        if name == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            return dt + np.log(-np.expm1(-dt))         # softplus^-1
        if name.startswith("conv"):
            return rng.uniform(-0.5, 0.5, shape)
        if name == "D":
            return np.ones(shape)
        if name in ("ln", "norm_gate", "final_ln"):
            return 1 + 0.1 * rng.standard_normal(shape)
        return rng.standard_normal(shape) / np.sqrt(shape[-2])

    return jax.tree_util.tree_map_with_path(
        lambda p, s: jnp.asarray(init(p, s), jnp.float32), tree)


def _batch(cfg, rows: int, T: int, seed: int):
    rng = np.random.default_rng(seed + 100)
    toks = rng.integers(0, cfg.vocab, (rows, T + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def _system(cfg, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: M.train_loss(p, cfg, batch)[0]))(params)


def _reference(cfg, params, batch):
    m = cfg.period[0].mamba
    fn = functools.partial(ref.loss, head_dim=m.head_dim,
                           d_state=m.d_state, eps=cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(fn))(params, batch["tokens"],
                                               batch["labels"])


def _gaps(cfg, seed: int, T: int):
    params = _weights(cfg, seed)
    batch = _batch(cfg, 2, T, seed)
    loss, g = _system(cfg, params, batch)
    loss_r, g_r = _reference(cfg, params, batch)
    leaf = {jax.tree_util.keystr(p): float(
        jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                             jax.tree.leaves(g_r))}
    return abs(float(loss) - float(loss_r)) / abs(float(loss_r)), leaf


@pytest.mark.parametrize("T", [40, 48], ids=["ragged", "whole_chunks"])
@pytest.mark.parametrize("seed", [0, 1])
def test_train_loss_and_grads_match_the_reference(seed, T):
    """Three chunks of 16 (the last one short at T = 40) against the
    recurrence: the loss and every gradient leaf."""
    loss_gap, leaf = _gaps(_config(), seed, T)
    assert loss_gap < LOSS_TOL
    assert max(leaf.values()) < GRAD_TOL, leaf


def test_dropped_state_between_chunks_fails_the_comparison(monkeypatch):
    """A planted fault: each chunk starts from a zero state, as if the
    hand-off between chunks were lost. The same comparison must fail."""
    orig = blocks.ssd_chunked

    def dropped(x, dt, A, B, C, D, chunk):
        T = x.shape[1]
        ys = [orig(x[:, s:s + chunk], dt[:, s:s + chunk], A,
                   B[:, s:s + chunk], C[:, s:s + chunk], D, chunk)[0]
              for s in range(0, T, chunk)]
        return jnp.concatenate(ys, axis=1), None

    monkeypatch.setattr(blocks, "ssd_chunked", dropped)
    loss_gap, leaf = _gaps(_config(), 0, 40)
    assert loss_gap > LOSS_TOL
    assert max(leaf.values()) > 1000 * GRAD_TOL, leaf


def test_one_chunk_equals_many():
    """The chunk is a tiling choice: one chunk over the whole row and
    chunks of 16 give the same loss and gradients."""
    params = _weights(_config(), 2)
    batch = _batch(_config(), 2, 48, 2)
    (l1, g1), (l2, g2) = (_system(_config(c), params, batch)
                          for c in (16, 48))
    assert abs(float(l1) - float(l2)) < LOSS_TOL * abs(float(l2))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert float(jnp.linalg.norm(a - b)) <= GRAD_TOL * float(
            jnp.linalg.norm(b))


def test_decode_through_the_conv_bias_continues_the_sequence():
    """Prefill then one decode step, with non-zero conv biases, gives
    the logits a prefill over the longer sequence gives."""
    cfg = _config()
    params = _weights(cfg, 3)
    toks = _batch(cfg, 2, 21, 3)["tokens"]
    cache, _ = M.prefill(params, cfg, {"tokens": toks[:, :20]})
    want = M.prefill(params, cfg, {"tokens": toks})[1]
    got, _ = M.decode_step(params, cfg, cache, {
        "token": toks[:, 20], "cur_len": jnp.asarray(20, jnp.int32)})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- norm eps
def _abstract_batch(cfg, B: int = 2, T: int = 8):
    i32 = jnp.int32
    labels = jax.ShapeDtypeStruct((B, T), i32)
    if cfg.frontend == "embeds":
        return {"embeds": jax.ShapeDtypeStruct((B, T, cfg.d_model),
                                               jnp.float32),
                "labels": labels}
    if cfg.frontend == "codebooks":
        toks = jax.ShapeDtypeStruct((B, T, cfg.n_codebooks), i32)
        return {"tokens": toks, "labels": toks}
    return {"tokens": jax.ShapeDtypeStruct((B, T), i32), "labels": labels}


def _decode_batch(cfg, B: int = 2):
    i32 = jnp.int32
    if cfg.frontend == "embeds":
        tok = {"embed": jax.ShapeDtypeStruct((B, cfg.d_model), jnp.float32)}
    elif cfg.frontend == "codebooks":
        tok = {"token": jax.ShapeDtypeStruct((B, cfg.n_codebooks), i32)}
    else:
        tok = {"token": jax.ShapeDtypeStruct((B,), i32)}
    return tok | {"cur_len": jax.ShapeDtypeStruct((), i32)}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_norm_eps_reaches_every_norm(arch, monkeypatch):
    """Every RMSNorm of the train, prefill and decode paths is given
    ``ModelConfig.norm_eps`` (a norm left at the function's own default
    would be seen here as a call without it)."""
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              norm_eps=3.25e-4)
    seen = []
    orig = blocks.rms_norm

    def spy(x, scale, eps=None):
        seen.append(eps)
        return orig(x, scale, eps)

    monkeypatch.setattr(blocks, "rms_norm", spy)
    monkeypatch.setattr(M, "rms_norm", spy)
    params = M.abstract_params(cfg)
    jax.eval_shape(lambda p, b: M.train_loss(p, cfg, b), params,
                   _abstract_batch(cfg))
    prompt = {k: v for k, v in _abstract_batch(cfg).items() if k != "labels"}
    jax.eval_shape(lambda p, b: M.prefill(p, cfg, b), params, prompt)
    cache = M.abstract_cache(cfg, 2, 16)
    jax.eval_shape(lambda p, c, b: M.decode_step(p, cfg, c, b), params,
                   cache, _decode_batch(cfg))
    assert len(seen) >= 3 and set(seen) == {3.25e-4}, seen


def test_default_norm_eps_leaves_the_smollm_program_unchanged(monkeypatch):
    """smollm-360m's gradient program lowers to the same text as when
    every norm takes the norm function's own default epsilon (as before
    the field existed); another epsilon lowers to other text."""
    cfg = get_config("smollm_360m", reduced=True)

    def lowered(c):
        fn = jax.jit(jax.value_and_grad(
            lambda p, b: M.train_loss(p, c, b)[0]))
        return fn.lower(M.abstract_params(c), _abstract_batch(c, T=32)
                        ).as_text()

    now = lowered(cfg)
    assert lowered(dataclasses.replace(cfg, norm_eps=1e-5)) != now
    orig = blocks.rms_norm

    def default(x, scale, eps=None):  # noqa: ARG001
        return orig(x, scale)

    monkeypatch.setattr(blocks, "rms_norm", default)
    monkeypatch.setattr(M, "rms_norm", default)
    assert lowered(cfg) == now


# ------------------------------------------------------------- scope
def test_lowered_grad_carries_the_ssd_scope():
    """The SSD's ops, forward, recomputed and backward, carry the
    ``acan.ssd`` scope in their metadata; the projections do not."""
    cfg = _config()
    fn = jax.jit(jax.value_and_grad(
        lambda p, b: M.train_loss(p, cfg, b)[0]))
    hlo = fn.lower(M.abstract_params(cfg), _abstract_batch(cfg, T=40)
                   ).compile().as_text()
    names = [line.split('op_name="')[1].split('"')[0]
             for line in hlo.splitlines() if 'op_name="' in line]
    ssd = [n for n in names if "acan.ssd" in n]
    assert any(n.split("/acan.ssd")[0].endswith(("jvp()/while/body/closed_call",
                                                   "rematted_computation"))
               for n in ssd)
    assert any("transpose(" in n for n in ssd)
    assert not any("acan.ssd" in n for n in names
                   if n.endswith(("conv_general_dilated", "softplus")))
