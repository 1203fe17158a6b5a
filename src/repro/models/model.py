"""Unified causal LM over the block zoo: ``prefix`` layers + ``period``
layers scanned ``n_periods`` times (stacked params → bounded HLO size and
compile time even for the 104B/398B archs).

Three entry points (pure functions of (params, batch)):

- :func:`train_loss`      — next-token loss (chunked CE + MoE aux)
- :func:`prefill`         — build KV/SSM caches, return last-token logits
- :func:`decode_step`     — one token in, one token out, cache updated

Frontends: ``tokens`` (LM), ``embeds`` (VLM stub — precomputed patch/frame
embeddings, per assignment), ``codebooks`` (MusicGen stub — sum of
EnCodec codebook embeddings; per-codebook output heads).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.blocks import (LayerCfg, attn_cache_from_prefill,
                                 block_decode, block_specs, block_train,
                                 cache_specs)
from repro.models.common import (ParamSpec, norm_spec, rms_norm, stack_specs,
                                 tree_abstract, tree_axes, tree_initialize)
from repro.models.losses import chunked_softmax_xent, multi_head_xent

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    vocab: int
    prefix: tuple[LayerCfg, ...]
    period: tuple[LayerCfg, ...]
    n_periods: int
    frontend: str = "tokens"          # tokens | embeds | codebooks
    n_codebooks: int = 4
    tie_embeddings: bool = True
    embed_scale: bool = False         # gemma: h *= sqrt(d)
    param_dtype: str = "bfloat16"
    remat: str = "nothing"            # nothing | dots | none
    q_chunk: int = 512
    kv_chunk: int = 512
    loss_chunk: int = 32768   # global flat tokens per CE chunk; large
                              # chunks amortise the per-chunk head-grad
                              # all-reduce (§Perf it4) — per-device logits
                              # stay small (chunk/data × vocab/model)
    rules_name: str = "tp"            # tp | fsdp  (sharding profile)
    norm_eps: float = 1e-6            # every RMSNorm's epsilon
    long_context_ok: bool = False     # eligible for long_500k
    notes: str = ""

    @property
    def n_layers(self) -> int:
        return len(self.prefix) + self.n_periods * len(self.period)

    @property
    def dtype(self):
        return DTYPES[self.param_dtype]

    @property
    def head_width(self) -> int:
        return (self.vocab * self.n_codebooks
                if self.frontend == "codebooks" else self.vocab)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def param_specs(cfg: ModelConfig) -> dict:
    dt = cfg.dtype
    specs: dict = {}
    if cfg.frontend == "tokens":
        specs["embed"] = {"tok": ParamSpec((cfg.vocab, cfg.d_model),
                                           ("vocab", "embed"), dt)}
    elif cfg.frontend == "codebooks":
        # codebook tables are tiny (n_books × 2048 rows) — replicated;
        # vocab-sharding them makes the per-book head slices in
        # multi_head_xent straddle shard boundaries (reshard churn).
        specs["embed"] = {"tok": ParamSpec(
            (cfg.n_codebooks * cfg.vocab, cfg.d_model), (None, "embed"), dt)}
    else:  # embeds: no input table
        specs["embed"] = {}
    specs["prefix"] = tuple(block_specs(cfg.d_model, l, dt) for l in cfg.prefix)
    period = tuple(block_specs(cfg.d_model, l, dt) for l in cfg.period)
    specs["period"] = tuple(stack_specs(p, cfg.n_periods) for p in period)
    specs["final_ln"] = norm_spec(cfg.d_model)
    tied = cfg.tie_embeddings and cfg.frontend == "tokens"
    if not tied:
        head_axes = ("embed", None) if cfg.frontend == "codebooks" \
            else ("embed", "vocab")
        specs["head"] = ParamSpec((cfg.d_model, cfg.head_width),
                                  head_axes, dt)
    return specs


def abstract_params(cfg: ModelConfig):
    return tree_abstract(param_specs(cfg))


def init_params(cfg: ModelConfig, key, dtype_override=None):
    return tree_initialize(param_specs(cfg), key, dtype_override)


def param_axes(cfg: ModelConfig):
    return tree_axes(param_specs(cfg))


def _head_matrix(params, _cfg: ModelConfig):
    if "head" in params:
        return params["head"]
    return params["embed"]["tok"].T


def _embed(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    if cfg.frontend == "embeds":
        h = batch["embeds"].astype(cfg.dtype)
    elif cfg.frontend == "codebooks":
        tok = batch["tokens"]                       # (B, T, K)
        offs = jnp.arange(cfg.n_codebooks) * cfg.vocab
        h = jnp.take(params["embed"]["tok"], tok + offs, axis=0).sum(axis=2)
    else:
        h = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return shard_act(h, ("batch", "seq", "embed"))


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)   # "nothing": save nothing, recompute all


# ---------------------------------------------------------------------------
# Train / prefill backbone
# ---------------------------------------------------------------------------

def _backbone(params, cfg: ModelConfig, h, want_cache: bool = False):
    """Returns (h, aux, caches|None)."""
    aux0 = jnp.float32(0.0)
    prefix_caches = []
    aux = aux0
    for lcfg, p in zip(cfg.prefix, params["prefix"]):
        h, a, c = block_train(p, h, lcfg, want_cache=want_cache,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              eps=cfg.norm_eps)
        aux = aux + a
        prefix_caches.append(c)

    def period_body(carry, p_stack):
        h, aux = carry
        caches = []
        for j, lcfg in enumerate(cfg.period):
            h, a, c = block_train(p_stack[j], h, lcfg, want_cache=want_cache,
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                  eps=cfg.norm_eps)
            aux = aux + a
            caches.append(c)
        return (h, aux), (tuple(caches) if want_cache else 0)

    body = period_body if want_cache else _remat(period_body, cfg)
    (h, aux), period_caches = jax.lax.scan(body, (h, aux), params["period"])
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    caches = None
    if want_cache:
        caches = {"prefix": tuple(prefix_caches), "period": period_caches}
    return h, aux, caches


def train_loss(params, cfg: ModelConfig, batch):
    """batch: tokens/embeds + labels (+ optional loss_mask). Returns
    (loss, metrics)."""
    h, aux, _ = _backbone(params, cfg, _embed(params, cfg, batch))
    B, T, d = h.shape
    flat = shard_act(h.reshape(B * T, d), ("loss_tokens", "embed"))
    head = _head_matrix(params, cfg)
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask.reshape(B * T).astype(jnp.float32)
    if cfg.frontend == "codebooks":
        labels = batch["labels"].reshape(B * T, cfg.n_codebooks)
        nll, _ = multi_head_xent(flat, head, labels, cfg.n_codebooks,
                                 chunk=cfg.loss_chunk)
    else:
        labels = batch["labels"].reshape(B * T)
        nll, _ = chunked_softmax_xent(flat, head, labels,
                                      chunk=cfg.loss_chunk, mask=mask)
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux}


def prefill(params, cfg: ModelConfig, batch):
    """Returns (cache, last_logits (B, head_width))."""
    h, _, caches = _backbone(params, cfg, _embed(params, cfg, batch),
                             want_cache=True)
    # ring-reorder sliding-window attn caches (prefix only; period caches
    # were produced inside scan — reorder here, vectorised over periods)
    pfx = []
    for lcfg, c in zip(cfg.prefix, caches["prefix"]):
        if lcfg.mixer == "attn":
            c = attn_cache_from_prefill(c, lcfg)
        pfx.append(c)
    per = list(caches["period"])
    for j, lcfg in enumerate(cfg.period):
        if lcfg.mixer == "attn" and lcfg.attn.window > 0:
            per[j] = jax.vmap(lambda cc: attn_cache_from_prefill(cc, lcfg))(
                per[j])
    cache = {"prefix": tuple(pfx), "period": tuple(per)}
    logits = (h[:, -1] @ _head_matrix(params, cfg)).astype(jnp.float32)
    return cache, logits


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_step(params, cfg: ModelConfig, cache, batch):
    """batch: {"token": (B,) or (B,K) or "embed": (B,d); "cur_len": scalar}.
    Returns (logits, new_cache)."""
    cur = batch["cur_len"]
    if cfg.frontend == "embeds":
        h = batch["embed"].astype(cfg.dtype)
    elif cfg.frontend == "codebooks":
        offs = jnp.arange(cfg.n_codebooks) * cfg.vocab
        h = jnp.take(params["embed"]["tok"], batch["token"] + offs,
                     axis=0).sum(axis=1)
    else:
        h = jnp.take(params["embed"]["tok"], batch["token"], axis=0)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)

    new_prefix = []
    for lcfg, p, c in zip(cfg.prefix, params["prefix"], cache["prefix"]):
        h, c = block_decode(p, h, c, cur, lcfg, eps=cfg.norm_eps)
        new_prefix.append(c)

    def body(h, xs):
        p_stack, c_stack = xs
        new_c = []
        for j, lcfg in enumerate(cfg.period):
            h, cj = block_decode(p_stack[j], h, c_stack[j], cur, lcfg,
                                 eps=cfg.norm_eps)
            new_c.append(cj)
        return h, tuple(new_c)

    h, new_period = jax.lax.scan(body, h, (params["period"], cache["period"]))
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = (h @ _head_matrix(params, cfg)).astype(jnp.float32)
    return logits, {"prefix": tuple(new_prefix), "period": new_period}


# ---------------------------------------------------------------------------
# Cache spec tree (for dry-run decode lowering and serving)
# ---------------------------------------------------------------------------

def cache_spec_tree(cfg: ModelConfig, batch: int, cache_len: int):
    dt = cfg.dtype
    pfx = tuple(cache_specs(l, batch, cache_len, dt) for l in cfg.prefix)
    per = tuple(stack_specs(cache_specs(l, batch, cache_len, dt),
                            cfg.n_periods) for l in cfg.period)
    return {"prefix": pfx, "period": per}


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int):
    return tree_abstract(cache_spec_tree(cfg, batch, cache_len))


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, cache_len))


def param_count(cfg: ModelConfig) -> int:
    total = 0
    for s in jax.tree.leaves(param_specs(cfg),
                             is_leaf=lambda x: isinstance(x, ParamSpec)):
        n = 1
        for d in s.shape:
            n *= d
        total += n
    return total


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: routed experts scaled by top_k/E).
    Used for MODEL_FLOPS = 6·N_active·D in §Roofline."""
    def layer_active(lcfg) -> int:
        full = 0
        for s in jax.tree.leaves(block_specs(cfg.d_model, lcfg, cfg.dtype),
                                 is_leaf=lambda x: isinstance(x, ParamSpec)):
            n = 1
            for d in s.shape:
                n *= d
            full += n
        if lcfg.ffn_kind == "moe":
            m = lcfg.moe
            per_expert = 3 * cfg.d_model * m.d_ff
            full -= m.n_experts * per_expert          # remove all routed
            full += m.top_k * per_expert              # add back active
        return full

    total = sum(layer_active(l) for l in cfg.prefix)
    total += cfg.n_periods * sum(layer_active(l) for l in cfg.period)
    total += cfg.d_model                               # final norm
    if cfg.frontend == "tokens":
        total += cfg.vocab * cfg.d_model               # embed (≈head if tied)
        if not cfg.tie_embeddings:
            total += cfg.d_model * cfg.head_width
    else:
        total += cfg.d_model * cfg.head_width
        if cfg.frontend == "codebooks":
            total += cfg.n_codebooks * cfg.vocab * cfg.d_model
    return total
