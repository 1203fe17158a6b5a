"""Logical-axis sharding with automatic divisibility fallback.

Model code annotates params/activations with *logical* axes ("batch",
"heads", "mlp", …). A per-arch rule table maps logical → mesh axes; this
module resolves them to ``PartitionSpec``\\ s with two safety rules:

1. **divisibility fallback** — a mesh axis whose size does not divide the
   dim is skipped (greedily, left to right). This is what lets e.g.
   smollm's 15 q-heads coexist with a 16-way "model" axis: ``heads →
   "model"`` silently degrades to replicated, and the d_ff/vocab dims keep
   their 16-way sharding.
2. **single-use** — a mesh axis may appear at most once per array spec
   (PartitionSpec requirement); later dims lose the contested axis.

Dropped mappings are recorded in ``FALLBACK_LOG`` (the dry-run prints
them), because a silent fallback that nobody ever sees is how sharding
bugs ship.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import common as _common

FALLBACK_LOG: list[str] = []

# Default logical→mesh rules (tensor-parallel profile, single- or multi-pod;
# missing/None = replicated).
DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data", "model"),     # flattened B*T (MoE dispatch)
    "loss_tokens": ("pod", "data"),         # CE chunks: must NOT contest the
                                            # "model" axis with "vocab", or
                                            # GSPMD reshards the head matrix
                                            # per loss chunk (§Perf it3)
    "moe_tokens": ("pod", "data"),          # MoE dispatch: tokens/groups keep
    "moe_groups": ("pod", "data"),          # to data; "model" belongs to the
                                            # experts dim (2-D dispatch
                                            # sharding, §Perf it6)
    "seq": None,
    "attn_batch": ("pod", "data"),          # batch inside attention; the
                                            # dp_attn profile adds "model"
                                            # (archs whose head count does
                                            # not divide the model axis)
    "kv_seq": ("model",),                   # decode cache sequence axis
    "heads": ("model",),
    "kv_heads": ("model",),
    "embed": None,
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "stack": None,
    "kv_seq_long": ("data", "model"),       # batch=1 long-context decode
}

FSDP_RULES: dict = dict(DEFAULT_RULES, embed=("data",))

# DP profile for small models whose head counts do not divide the model
# axis (smollm 15H, musicgen 24H): ALL activations shard batch/tokens over
# every mesh axis (256/512-way pure DP); params keep TP shardings where
# divisible (XLA gathers the small weights per layer — cheaper than 16×
# replicated attention compute). Measured §Perf it8: smollm dominant term
# 96 s (flat+tp) → ~0.3 s.
DP_ATTN_RULES: dict = dict(
    DEFAULT_RULES,
    batch=("pod", "data", "model"),
    attn_batch=("pod", "data", "model"),
    loss_tokens=("pod", "data", "model"),
    moe_tokens=("pod", "data", "model"),
    moe_groups=("pod", "data", "model"))


def resolve_pspec(shape, logical_axes, rules, mesh: Mesh) -> PartitionSpec:
    used: set[str] = set()
    entries = []
    for dim, ax in zip(shape, logical_axes):
        mesh_axes = rules.get(ax) if ax is not None else None
        if mesh_axes is None:
            entries.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        picked = []
        prod = 1
        for m in mesh_axes:
            if m not in mesh.shape or m in used:
                continue
            sz = mesh.shape[m]
            if dim % (prod * sz) == 0:
                picked.append(m)
                prod *= sz
            else:
                FALLBACK_LOG.append(
                    f"drop {m}({sz}) for logical '{ax}' dim {dim} of {shape}")
        used.update(picked)
        entries.append(tuple(picked) if picked else None)
    return PartitionSpec(*entries)


def spec_sharding(spec, rules, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, resolve_pspec(spec.shape, spec.axes, rules, mesh))


def tree_shardings(spec_tree, rules, mesh: Mesh):
    """ParamSpec tree → NamedSharding tree."""
    return jax.tree.map(lambda s: spec_sharding(s, rules, mesh), spec_tree,
                        is_leaf=_common.is_spec)


# ---------------------------------------------------------------------------
# Activation-sharding context (used by model code via shard_act)
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextmanager
def use_rules(rules: dict, mesh: Mesh):
    prev = getattr(_CTX, "v", None)
    _CTX.v = (rules, mesh)
    try:
        yield
    finally:
        _CTX.v = prev


def rules_active() -> bool:
    """Whether a ``use_rules`` context is active: the program is being
    traced for a mesh under GSPMD."""
    return getattr(_CTX, "v", None) is not None


def shard_act(x, logical_axes):
    """with_sharding_constraint against the active rules; no-op outside a
    ``use_rules`` context (single-device tests/examples)."""
    ctx = getattr(_CTX, "v", None)
    if ctx is None:
        return x
    rules, mesh = ctx
    ps = resolve_pspec(x.shape, logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))
