"""Per-kernel allclose vs pure-jnp oracles, with hypothesis shape/dtype
sweeps — all with ``interpret=True`` (the kernels compile for the TPU by
default; the Pallas interpreter validates them here on the CPU). The
TPU compiles themselves are in test_tpu_compile.py."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.tile_matmul.ops import matmul
from repro.kernels.tile_matmul.ref import tile_matmul_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_scan_ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- matmul
@given(m=st.sampled_from([8, 32, 128, 256]),
       n=st.sampled_from([8, 64, 128]),
       k=st.sampled_from([16, 128, 384]),
       act=st.sampled_from(["none", "tanh", "silu", "gelu", "relu"]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       bias=st.booleans())
@settings(max_examples=24, deadline=None)
def test_tile_matmul_sweep(m, n, k, act, dtype, bias):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (k, n), jnp.float32).astype(dtype) * 0.1
    b = jax.random.normal(k3, (n,), jnp.float32).astype(dtype) if bias else None
    out = matmul(x, w, b, activation=act, bm=128, bn=64, bk=128,
                 interpret=True)
    ref = tile_matmul_ref(x, w, b, activation=act)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_tile_matmul_accumulates_over_k_blocks():
    # K split across 4 blocks — accumulation across grid steps must be exact
    x = jnp.ones((16, 512), jnp.float32)
    w = jnp.ones((512, 16), jnp.float32)
    out = matmul(x, w, bm=16, bn=16, bk=128, interpret=True)
    np.testing.assert_allclose(out, np.full((16, 16), 512.0), rtol=1e-6)


# ------------------------------------------------------------- attention
@given(bh=st.sampled_from([1, 3]),
       g=st.sampled_from([1, 4]),
       tq=st.sampled_from([64, 128]),
       tk=st.sampled_from([64, 256]),
       d=st.sampled_from([16, 64]),
       window=st.sampled_from([0, 32]),
       softcap=st.sampled_from([0.0, 30.0]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
@settings(max_examples=24, deadline=None)
def test_flash_attention_sweep(bh, g, tq, tk, d, window, softcap, dtype):
    if tq > tk:
        tq = tk
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (bh, g, tq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (bh, tk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (bh, tk, d), jnp.float32).astype(dtype)
    q_off = tk - tq
    out = flash_attention(q, k, v, window=window, softcap=softcap,
                          q_offset=q_off, bq=32, bk=32, interpret=True)
    ref = flash_attention_ref(q, k, v, window=window, softcap=softcap,
                              q_offset=q_off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_block_skip_correctness():
    """Causal + window with many blocks: skipped blocks must not corrupt
    the running softmax."""
    bh, g, t, d = 2, 2, 256, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (bh, g, t, d), jnp.float32)
    k = jax.random.normal(ks[1], (bh, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (bh, t, d), jnp.float32)
    out = flash_attention(q, k, v, window=64, bq=32, bk=32, interpret=True)
    ref = flash_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_matches_model_attention():
    """Kernel ↔ model-layer chunked attention agreement (same math).
    The kernel keeps the grouped (per-KV-head) layout; the model path is
    flat-headed with repeated KV (see attention.py docstring)."""
    from repro.models.attention import gqa_attention, AttnCfg
    B, T, Hkv, G, D = 2, 128, 2, 3, 16
    Hq = Hkv * G
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    cfg = AttnCfg(n_heads=Hq, n_kv_heads=Hkv, head_dim=D)
    model_out = gqa_attention(q, k, v, cfg, q_chunk=64, kv_chunk=64)
    qf = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B * Hkv, G, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    kern = flash_attention(qf, kf, vf, bq=32, bk=32, interpret=True)
    kern = kern.reshape(B, Hkv, G, T, D).transpose(0, 3, 1, 2, 4)
    kern = kern.reshape(B, T, Hq, D)
    np.testing.assert_allclose(model_out, kern, rtol=2e-4, atol=2e-4)
    # The (B, T, H, D) wrapper folds the same grouping itself.
    wrapped = attention(q, k, v, bq=32, bk=32, interpret=True)
    np.testing.assert_allclose(model_out, wrapped, rtol=2e-4, atol=2e-4)


# ------------------------------------------- flash path of the model step
def _dense_gqa(q, k, v):
    """Causal GQA attention in f32 with the whole score matrix."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    s = s / q.shape[-1] ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _chunked_gqa(q, k, v):
    from repro.models.attention import chunked_attention
    G = q.shape[2] // k.shape[2]
    return chunked_attention(q, jnp.repeat(k, G, axis=2),
                             jnp.repeat(v, G, axis=2),
                             q_chunk=128, kv_chunk=128)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)],
                         ids=["two_blocks", "one_block_two_kv_slices"])
def test_flash_path_matches_chunked_and_dense(blocks):
    """The model's flash path (the Pallas splash kernel, interpreted)
    gives the output and dq/dk/dv of the jnp chunked path and of a dense
    f32 reference: GQA with G = 3, hd 64, T = 256, causal, two batch
    rows and two KV heads; in two blocks, or in one whose KV is computed
    in two slices."""
    from repro.models.attention import flash_attention
    B, T, Hkv, G, D = 2, 256, 2, 3, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, T, Hkv * G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    cot = jax.random.normal(ks[3], (B, T, Hkv * G, D), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, blocks=blocks, interpret=True)

    def out_and_grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(cot))

    got = out_and_grads(flash)
    for ref in (out_and_grads(_dense_gqa), out_and_grads(_chunked_gqa)):
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                       err_msg=name)
    # The same through jax.grad of a scalar loss.
    dq, dk, dv = jax.grad(lambda *a: jnp.sum(flash(*a) * cot),
                          argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), got[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _dispatch_cases():
    from repro.configs import get_config
    smollm = get_config("smollm_360m").period[0].attn
    danube = get_config("h2o_danube_1_8b").period[0].attn     # window 4096
    mla = get_config("deepseek_v2_lite_16b").prefix[0].attn
    mla_dk = mla.qk_nope_dim + mla.qk_rope_dim
    # (id, T, dk, dv, cfg, q_offset, backend, meshed, expected)
    return [
        ("smollm_train_tpu", 2048, 64, 64, smollm, 0, "tpu", False, "flash"),
        ("smollm_train_cpu", 2048, 64, 64, smollm, 0, "cpu", False,
         "chunked"),
        ("smollm_under_mesh", 2048, 64, 64, smollm, 0, "tpu", True,
         "chunked"),
        ("mla_dk_ne_dv", 2048, mla_dk, mla.v_head_dim, mla, 0, "tpu",
         False, "chunked"),
        ("q_offset", 2048, 64, 64, smollm, 16, "tpu", False, "chunked"),
        ("t_off_the_lanes", 1000, 64, 64, smollm, 0, "tpu", False,
         "chunked"),
        ("t_under_a_lane", 100, 64, 64, smollm, 0, "tpu", False, "chunked"),
        ("t_one_block", 384, 64, 64, smollm, 0, "tpu", False, "flash"),
        ("t_in_768_blocks", 1536, 64, 64, smollm, 0, "tpu", False, "flash"),
        ("window_covers_t", 2048, 80, 80, danube, 0, "tpu", False, "flash"),
        ("window_under_t", 8192, 80, 80, danube, 0, "tpu", False,
         "chunked"),
        ("softcap", 2048, 64, 64, replace(smollm, softcap=30.0), 0, "tpu",
         False, "chunked"),
    ]


@pytest.mark.parametrize("case", _dispatch_cases(), ids=lambda c: c[0])
def test_attention_dispatch(case):
    from repro.models.attention import attention_impl
    _, T, dk, dv, cfg, q_offset, backend, meshed, expected = case
    assert attention_impl(T, dk, dv, cfg, q_offset, backend=backend,
                          meshed=meshed) == expected


def test_attention_dispatch_sees_an_active_mesh():
    """Under ``use_rules`` the model is traced for GSPMD: the flash path
    is refused there even on a TPU backend."""
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.models.attention import attention_impl
    cfg = get_config("smollm_360m").period[0].attn
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    assert not shd.rules_active()
    with shd.use_rules(shd.DEFAULT_RULES, mesh):
        assert shd.rules_active()
        assert attention_impl(2048, 64, 64, cfg, 0, backend="tpu",
                              meshed=shd.rules_active()) == "chunked"
    assert not shd.rules_active()


def test_cpu_train_grad_has_no_pallas_call():
    """On the CPU the gradient of the loss traces the same jnp attention
    as before the flash path existed: no ``pallas_call`` anywhere."""
    from repro.configs import get_config
    from repro.configs.base import Shape, input_specs
    from repro.models import model as M
    cfg = get_config("smollm_360m", reduced=True)
    params = M.abstract_params(cfg)
    batch = input_specs(cfg, Shape("micro", "train", 256, 2))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, b: M.train_loss(p, cfg, b)[0]))(params, batch)
    assert "pallas_call" not in str(jaxpr)


# ------------------------------------------------------------------- ssd
@given(bt=st.sampled_from([1, 2]),
       t=st.sampled_from([32, 64, 128]),
       h=st.sampled_from([2, 4]),
       p=st.sampled_from([8, 16]),
       g=st.sampled_from([1, 2]),
       n=st.sampled_from([8, 16]),
       chunk=st.sampled_from([16, 32]))
@settings(max_examples=20, deadline=None)
def test_ssd_sweep(bt, t, h, p, g, n, chunk):
    if h % g:
        g = 1
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (bt, t, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, t, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    B = jax.random.normal(ks[3], (bt, t, g, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (bt, t, g, n), jnp.float32) * 0.5
    D = jnp.ones((h,))
    y, s = ssd(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(bt * h, t, n)
    Ch = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(bt * h, t, n)
    yr, sr = ssd_scan_ref(x.transpose(0, 2, 1, 3).reshape(bt * h, t, p),
                          dt.transpose(0, 2, 1).reshape(bt * h, t),
                          jnp.tile(A, bt), Bh, Ch, jnp.tile(D, bt))
    np.testing.assert_allclose(y, yr.reshape(bt, h, t, p).transpose(0, 2, 1, 3),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s, sr.reshape(bt, h, n, p), rtol=1e-3, atol=1e-3)


def test_ssd_decode_continues_chunked():
    """ssd_chunked final state + ssd_decode_step ≡ one longer ssd_chunked
    (prefill→decode continuity for the SSM cache)."""
    from repro.models.mamba2 import ssd_chunked, ssd_decode_step
    bt, t, h, p, g, n = 2, 32, 4, 8, 2, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (bt, t + 1, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, t + 1, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    B = jax.random.normal(ks[3], (bt, t + 1, g, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (bt, t + 1, g, n), jnp.float32) * 0.5
    D = jnp.ones((h,))
    y_full, s_full = ssd_chunked(x, dt, A, B, C, D, chunk=16 if (t+1) % 16 == 0 else t + 1)
    _, s_pre = ssd_chunked(x[:, :t], dt[:, :t], A, B[:, :t], C[:, :t], D, chunk=16)
    y_step, s_step = ssd_decode_step(s_pre, x[:, t], dt[:, t], A, B[:, t],
                                     C[:, t], D)
    np.testing.assert_allclose(y_step, y_full[:, t], rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s_step, s_full, rtol=1e-3, atol=1e-3)
