"""The plain float32 reference of the models the benchmark trains, and
the benchmark's own weights and batches.

Nothing here imports the program. A configuration names its family;
``bench/families/<family>.py`` holds that family's layer in
straightforward ``jax.numpy``, following the published equations, with
every constant (the norm's epsilon among them) read from the
configuration. The reference holds the parameters in the tree layout the
program trains, so the two can be compared leaf by leaf, and keeps its
state in the dtypes the configuration states (matrices in the
configuration's dtype, bf16 here; norms f32): an SGD step is
``(p.f32 - lr * mean_grad).astype(p.dtype)``.

Every matrix product runs at ``highest`` precision in float32. With
``quantize=True`` every operand of every matrix product, forward and
backward, is first rounded to float8 e4m3 with a per-tensor scale: the
control, one precision step below the configuration's bfloat16.

A micro-batch's loss is the mean over its rows of each row's mean
next-token loss; rows are run one at a time (equal token counts, so the
mean is the same) to keep the reference inside one chip's memory.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
F8_MAX = 448.0          # largest finite float8_e4m3fn
FAMILIES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "families")

_families: dict = {}


def family(cfg: dict):
    """The module ``bench/families/<cfg['family']>.py``."""
    name = cfg["family"]
    mod = _families.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + name, os.path.join(FAMILIES, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _families[name] = mod
    return mod


# ------------------------------------------------------------- seeds
def key_from_seed(seed: int, *words: int):
    """A threefry key from any non-negative integer seed (a run's
    seeds need more than 32 bits) and optional sub-stream words."""
    state = np.random.SeedSequence([int(seed), *map(int, words)]
                                   ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state), impl="threefry2x32")


# ------------------------------------------------------------- layout
def param_layout(cfg: dict) -> dict:
    """The parameter tree the program trains, as ``(shape, dtype, init)``
    triples: the layout is the interface both sides share."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": {"tok": ((V, d), cfg["torch_dtype"], "normal")},
            "prefix": (), "period": (family(cfg).layer_layout(cfg),),
            "final_ln": ((d,), "float32", "ones")}


def is_layout_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def layout_shapes(cfg: dict):
    """The layout as a tree of ``jax.ShapeDtypeStruct``."""
    return jax.tree.map(lambda t: jax.ShapeDtypeStruct(t[0], t[1]),
                        param_layout(cfg), is_leaf=is_layout_leaf)


def _init_leaf(key, shape, dtype, init):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


def make_weights(cfg: dict, seed: int):
    """The run's weights, made on the default device in one jitted call
    from ``seed``, in the dtypes they are trained in."""
    leaves, treedef = jax.tree.flatten(param_layout(cfg), is_leaf=is_layout_leaf)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _init_leaf(k, s, jnp.dtype(dt), init)
            for k, (s, dt, init) in zip(keys, leaves)])

    return build(key_from_seed(seed, 1))


# ------------------------------------------------------------- data
def make_batch(seed: int, index: int, rows: int, seq: int,
               vocab: int) -> dict:
    """Micro-batch ``index`` of the run: host int32 ``tokens`` and
    ``labels`` (the next token), each ``(rows, seq)``. Each row counts up
    from a random start (token t+1 = t + 1 mod vocab, a learnable rule),
    so every row of every index differs."""
    rng = np.random.default_rng([int(seed), 2, int(index)])
    start = rng.integers(0, vocab, (rows, 1))
    toks = ((start + np.arange(seq + 1)[None, :]) % vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ------------------------------------------------------------- precision
def _quant(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


@jax.custom_vjp
def _q_in(x):
    """Forward: quantize an operand; backward: pass the cotangent on."""
    return _quant(x)


_q_in.defvjp(lambda x: (_quant(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_out(x):
    """Forward: identity; backward: quantize the incoming cotangent, the
    operand the two backward products of a matrix product share."""
    return x


_q_out.defvjp(lambda x: (x, None), lambda _, g: (_quant(g),))


def mm(spec: str, a, b, quantize: bool):
    """A matrix product; with ``quantize`` the control's float8 one."""
    if quantize:
        return _q_out(jnp.einsum(spec, _q_in(a), _q_in(b)))
    return jnp.einsum(spec, a, b)


def rms(x, w, eps: float):
    """RMSNorm with the configuration's epsilon."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ------------------------------------------------------------- model
def row_loss(params, tokens, labels, cfg: dict, quantize: bool = False):
    """Mean next-token loss of one row (float32 ``params``)."""
    layer = family(cfg).layer
    emb = params["embed"]["tok"]
    h = emb[tokens]

    def body(h, p):
        return layer(cfg, quantize, h, p), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, params["period"][0])
    h = rms(h, params["final_ln"], cfg["rms_norm_eps"])
    logits = mm("td,vd->tv", h, emb, quantize)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


class Reference:
    """Runs the reference's SGD steps on one device, row by row."""

    def __init__(self, cfg: dict, lr: float, quantize: bool = False):
        def vg(params, tokens, labels):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(row_loss)(
                    params, tokens, labels, cfg, quantize)

        self._vg = jax.jit(vg)
        self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        def update(state, g):
            return jax.tree.map(
                lambda p, gl: (p.astype(F32) - lr * gl).astype(p.dtype),
                state, g)

        self._update = jax.jit(update)

    def grad(self, state, batches: list[dict]):
        """Mean loss and mean float32 gradient over ``batches`` (each a
        micro-batch; rows weigh equally) at the stored ``state``."""
        params = jax.tree.map(lambda p: p.astype(F32), state)
        gsum, losses = None, []
        for b in batches:
            for tok, lab in zip(b["tokens"], b["labels"]):
                loss, g = self._vg(params, tok, lab)
                losses.append(loss)
                gsum = g if gsum is None else self._acc(gsum, g)
        n = len(losses)
        mean_g = jax.tree.map(lambda g: g / n, gsum)
        return float(np.mean([float(x) for x in losses])), mean_g

    def step(self, state, batches: list[dict]):
        """One SGD step from ``state``: ``(loss, mean_grad, new_state)``."""
        loss, g = self.grad(state, batches)
        return loss, g, self._update(state, g)
