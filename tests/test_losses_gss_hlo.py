"""Losses (chunked/vocab-sharded CE), GSS controllers, HLO census."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.gss import PouchController, TimeoutController, gss_chunk
from repro.models.losses import chunked_softmax_xent, multi_head_xent


# ------------------------------------------------------------------ loss
@given(t=st.sampled_from([32, 64, 128]),
       d=st.sampled_from([8, 16]),
       v=st.sampled_from([16, 64]),
       chunk=st.sampled_from([16, 32]))
@settings(max_examples=16, deadline=None)
def test_chunked_ce_matches_naive(t, d, v, chunk):
    key = jax.random.PRNGKey(t + d + v)
    h = jax.random.normal(key, (t, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (t,), 0, v)
    got, _ = chunked_softmax_xent(h, w, labels, chunk=chunk)
    logits = h @ w
    naive = -jax.nn.log_softmax(logits)[jnp.arange(t), labels].mean()
    np.testing.assert_allclose(float(got), float(naive), rtol=1e-5)


def test_chunked_ce_mask():
    h = jnp.ones((8, 4))
    w = jnp.eye(4, 6)
    labels = jnp.zeros((8,), jnp.int32)
    mask = jnp.array([1, 1, 0, 0, 0, 0, 0, 0], jnp.float32)
    full, _ = chunked_softmax_xent(h, w, labels, chunk=8)
    masked, aux = chunked_softmax_xent(h, w, labels, chunk=8, mask=mask)
    assert float(aux["tokens"]) == 2.0
    np.testing.assert_allclose(float(masked), float(full), rtol=1e-6)


def test_multi_head_xent():
    t, d, v, k = 16, 8, 10, 4
    h = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, k * v))
    labels = jax.random.randint(jax.random.PRNGKey(2), (t, k), 0, v)
    loss, aux = multi_head_xent(h, w, labels, k, chunk=8)
    assert np.isfinite(float(loss)) and aux["books"] == k


# ------------------------------------------------------------------- gss
def test_timeout_controller_tracks_completion_time():
    c = TimeoutController(timeout=1.0)
    for _ in range(10):
        c.update(True, 0.05, 1.0)       # fast completions
    fast = c.timeout
    for _ in range(10):
        c.update(False, fast, 0.3)      # slow rounds
    assert c.timeout > fast
    assert c.timeout <= c.max_timeout


def test_timeout_controller_inverse_to_power():
    """Round time ∝ 1/power ⇒ timeout should order inversely with power."""
    outs = {}
    for power in (1.0, 5.0, 10.0):
        c = TimeoutController(timeout=1.0)
        for _ in range(20):
            c.update(True, 0.5 / power, 1.0)
        outs[power] = c.timeout
    assert outs[10.0] < outs[5.0] < outs[1.0]


def test_pouch_controller_bounds():
    p = PouchController(pouch=100, min_pouch=10, max_pouch=200)
    for _ in range(20):
        p.update(False, 0.1)
    assert p.pouch == 10
    for _ in range(20):
        p.update(True, 1.0)
    assert p.pouch == 200


def test_gss_chunk():
    assert gss_chunk(100, 4) == 25
    assert gss_chunk(3, 4) == 1
    assert gss_chunk(0, 4) == 0


# ------------------------------------------------------------------- hlo
def test_hlo_census_loop_multiplier():
    """Scan over 7 matmuls: census must count 7×, unlike cost_analysis."""
    from repro.launch.hlo_analysis import analyze_hlo

    def f(x, w):
        def body(c, ww):
            return jnp.tanh(c @ ww), 0
        out, _ = jax.lax.scan(body, x, w)
        return out

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((7, 256, 256), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    census = analyze_hlo(compiled.as_text(), total_devices=1)
    expected = 2 * 128 * 256 * 256 * 7
    assert 0.95 * expected <= census.flops <= 1.1 * expected
    assert 7.0 in census.trip_counts.values()


def test_hlo_shape_bytes():
    from repro.launch.hlo_analysis import shape_info
    assert shape_info("bf16[2,3]{1,0}")[0] == 12
    assert shape_info("(f32[4], s32[2])")[0] == 24
    assert shape_info("pred[]")[0] == 1
