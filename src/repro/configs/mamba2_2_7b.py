"""mamba2-2.7b [ssm] — attention-free SSD. 64L d_model=2560,
d_inner=5120 (expand 2), d_state=128, head_dim=64 (→ 80 heads), one
group, conv width 4 with a bias, RMSNorm epsilon 1e-5, tied embeddings, no
FFN [arXiv:2405.21060; state-spaces/mamba2-2.7b]. The embedding holds
50,288 rows (vocab 50,277 padded to a multiple of 16).

SSD chunked scan (the TPU-native adaptation of the paper's fixed-size
task partition along time — DESIGN.md §4). Decode is O(1) state →
long_500k runs with constant-size cache."""

from dataclasses import replace

from repro.models.blocks import LayerCfg
from repro.models.mamba2 import MambaCfg
from repro.models.model import ModelConfig

_LAYER = LayerCfg(
    mixer="mamba",
    mamba=MambaCfg(d_inner=5120, d_state=128, d_conv=4, head_dim=64,
                   n_groups=1, chunk=128),
    ffn_kind="none",
)

CONFIG = ModelConfig(
    name="mamba2_2_7b",
    d_model=2560,
    vocab=50288,
    prefix=(),
    period=(_LAYER,),
    n_periods=64,
    tie_embeddings=True,
    norm_eps=1e-5,
    rules_name="tp",
    long_context_ok=True,
    notes="pure SSM (SSD); no attention, no FFN; O(1) decode state",
)


def reduced() -> ModelConfig:
    layer = replace(_LAYER,
                    mamba=MambaCfg(d_inner=64, d_state=16, d_conv=4,
                                   head_dim=16, n_groups=1, chunk=16))
    return replace(CONFIG, d_model=32, vocab=256, period=(layer,),
                   n_periods=2, param_dtype="float32", loss_chunk=64)
