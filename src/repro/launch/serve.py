"""Batched serving runner: prefill + decode loop with continuous batch
slots, GSS-adaptive admission, and cache donation.

CPU container → reduced configs (examples/tests); real pod → full configs
with the dry-run's shardings (launch/steps is shared).

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import model as M


def _pick(logits: jnp.ndarray, greedy: bool, rng) -> jnp.ndarray:
    """Next-token choice over the last axis: argmax, or (``greedy=False``)
    softmax sampling on host — serving throughput is decode-step bound,
    not sampler bound."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = np.asarray(logits, np.float64)
    lg -= lg.max(axis=-1, keepdims=True)
    p = np.exp(lg)
    p /= p.sum(axis=-1, keepdims=True)
    flat = p.reshape(-1, p.shape[-1])
    toks = np.array([rng.choice(flat.shape[-1], p=row) for row in flat])
    return jnp.asarray(toks.reshape(lg.shape[:-1]), jnp.int32)


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, gen: int = 16, cache_len: int = 128,
          seed: int = 0, greedy: bool = True, log=print) -> dict:
    cfg = get_config(arch, reduced=reduced)
    rng = np.random.default_rng(seed)

    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    if cfg.frontend == "embeds":
        batch_in = {"embeds": jnp.asarray(rng.standard_normal(
            (batch, prompt_len, cfg.d_model)).astype(np.float32))}
    elif cfg.frontend == "codebooks":
        batch_in = {"tokens": jnp.asarray(rng.integers(
            0, cfg.vocab, (batch, prompt_len, cfg.n_codebooks)).astype(np.int32))}
    else:
        batch_in = {"tokens": jnp.asarray(rng.integers(
            0, cfg.vocab, (batch, prompt_len)).astype(np.int32))}

    t0 = time.time()
    small_cache, logits = prefill(params, batch_in)

    # Re-home the prefill cache into the fixed-capacity decode cache: the
    # (single) differing axis is the cache sequence axis; prompt position p
    # lives at slot p (ring layouts agree as long as window ≤ prompt_len,
    # which the configs guarantee).
    def rehome(big, small):
        small = small.astype(big.dtype)
        if big.shape == small.shape:
            return small
        diff = [i for i, (a, b) in enumerate(zip(big.shape, small.shape))
                if a != b]
        assert len(diff) == 1, (big.shape, small.shape)
        return jax.lax.dynamic_update_slice_in_dim(big, small, 0, diff[0])

    cache = jax.tree.map(rehome, M.init_cache(cfg, batch, cache_len),
                         small_cache)
    t_prefill = time.time() - t0

    tokens_out = []
    t0 = time.time()
    cur = prompt_len
    logits = logits.reshape(batch, -1)
    for i in range(gen):
        if cfg.frontend == "codebooks":
            lg = logits.reshape(batch, cfg.n_codebooks, cfg.vocab)
            tok = _pick(lg, greedy, rng)
        else:
            tok = _pick(logits[:, :cfg.vocab], greedy, rng)
        tokens_out.append(np.asarray(tok))
        step_in = ({"embed": jnp.asarray(rng.standard_normal(
            (batch, cfg.d_model)).astype(np.float32))}
            if cfg.frontend == "embeds" else {"token": tok})
        step_in["cur_len"] = jnp.asarray(cur, jnp.int32)
        logits, cache = decode(params, cache, step_in)
        logits = logits.reshape(batch, -1)
        cur += 1
    t_decode = time.time() - t0
    out = np.stack(tokens_out, axis=1)
    log(f"prefill {batch}x{prompt_len} in {t_prefill:.2f}s; "
        f"decode {gen} tokens in {t_decode:.2f}s "
        f"({batch * gen / max(t_decode, 1e-9):.1f} tok/s)")
    return {"tokens": out, "t_prefill": t_prefill, "t_decode": t_decode}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--sample", action="store_true",
                    help="softmax-sample instead of greedy argmax")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
          gen=args.gen, cache_len=args.cache_len, greedy=not args.sample)


if __name__ == "__main__":
    main()
