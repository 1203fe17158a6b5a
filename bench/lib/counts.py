"""Operations and bytes the benchmark's model steps need, from shapes.

Model FLOPs count the matrix products a forward pass needs, plus the
family's other work (causal attention for ``llama``), times three for
forward and backward; ``bench/families/<family>.py`` counts its layers.
Recomputation (rematerialised layers) does not count: these are the
operations the algorithm requires, the numerator of ``mfu`` and of a
roofline's least time."""

from __future__ import annotations

import math

import jax

from reference import family, is_layout_leaf, param_layout


def forward_flops_per_token(c: dict, seq: int) -> float:
    """The layers' (from the family's file) and the tied logits'."""
    return (family(c).layers_forward_flops_per_token(c, seq)
            + 2 * c["hidden_size"] * c["vocab_size"])


def model_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops_per_token(c, seq)


def param_count(c: dict) -> int:
    return sum(math.prod(t[0]) for t in jax.tree.leaves(
        param_layout(c), is_leaf=is_layout_leaf))


def grad_call_bytes(c: dict, rows: int, seq: int) -> float:
    """Least HBM traffic of one gradient call: the bf16 parameters read
    once, the bf16 gradient written once, the int32 tokens and labels."""
    return 2 * param_count(c) * 2 + 2 * rows * seq * 4


def grad_call_flops(c: dict, rows: int, seq: int) -> float:
    return model_flops_per_token(c, seq) * rows * seq


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time a call could take on a chip of ``peak``, and which
    bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
