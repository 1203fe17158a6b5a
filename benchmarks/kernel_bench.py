"""Kernel micro-benchmarks of the pure-jnp reference paths (the math the
TPU kernels implement), timed compiled on whatever backend JAX has. The
Pallas kernels themselves compile for the TPU only; chip_smoke.py runs
them there."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6     # µs


def bench_tile_matmul() -> list[tuple[str, float, str]]:
    from repro.kernels.tile_matmul.ref import tile_matmul_ref
    key = jax.random.PRNGKey(0)
    rows = []
    for (m, k, n) in [(256, 256, 256), (512, 1024, 512)]:
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(key, (k, n), jnp.float32)
        us_ref = _time(jax.jit(lambda a, b: tile_matmul_ref(a, b,
                                                            activation="tanh")),
                       x, w)
        flops = 2 * m * k * n
        rows.append((f"tile_matmul_ref_{m}x{k}x{n}", us_ref,
                     f"{flops / (us_ref * 1e-6) / 1e9:.1f}GFLOP/s"))
    return rows


def bench_attention() -> list[tuple[str, float, str]]:
    from repro.models.attention import chunked_attention
    key = jax.random.PRNGKey(0)
    rows = []
    for (b, t, h, d) in [(1, 1024, 8, 64), (2, 2048, 8, 64)]:
        q = jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
        v = jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v: chunked_attention(q, k, v, q_chunk=256,
                                                       kv_chunk=256))
        us = _time(fn, q, k, v)
        flops = 4 * b * t * t * h * d / 2            # causal half
        rows.append((f"flash_ref_b{b}_t{t}", us,
                     f"{flops / (us * 1e-6) / 1e9:.1f}GFLOP/s"))
    return rows


def bench_ssd() -> list[tuple[str, float, str]]:
    from repro.models.mamba2 import ssd_chunked
    key = jax.random.PRNGKey(0)
    rows = []
    for (b, t, h, p, n) in [(2, 1024, 8, 64, 64)]:
        x = jax.random.normal(key, (b, t, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(key, (b, t, h)))
        A = -jnp.exp(jax.random.normal(key, (h,)) * 0.3)
        B = jax.random.normal(key, (b, t, 1, n))
        C = jax.random.normal(key, (b, t, 1, n))
        D = jnp.ones((h,))
        fn = jax.jit(lambda *a: ssd_chunked(*a, 128)[0])
        us = _time(fn, x, dt, A, B, C, D)
        rows.append((f"ssd_chunked_b{b}_t{t}", us,
                     f"{b * t / (us * 1e-6) / 1e6:.2f}Mtok/s"))
    return rows


def bench_tuplespace() -> list[tuple[str, float, str]]:
    # Single-thread facade rates per space backend; the full multi-threaded
    # comparison (contention, blocking, pattern matching) lives in
    # benchmarks/ts_bench.py.
    from repro.core import TupleSpace
    rows = []
    N = 20000
    for spec in ("local", "sharded"):
        ts = TupleSpace(backend=spec)
        t0 = time.perf_counter()
        for i in range(N):
            ts.put(("k", i), i)
        put_us = (time.perf_counter() - t0) / N * 1e6
        t0 = time.perf_counter()
        for i in range(N):
            ts.get(("k", i))
        get_us = (time.perf_counter() - t0) / N * 1e6
        rows.append((f"tuplespace_put_{spec}", put_us,
                     f"{1e6 / put_us:.0f}ops/s"))
        rows.append((f"tuplespace_get_exact_{spec}", get_us,
                     f"{1e6 / get_us:.0f}ops/s"))
    return rows
