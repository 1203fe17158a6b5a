"""The model-FLOP and byte counts against hand counts at reduced size."""

import tiny  # noqa: F401 — puts bench/lib on the path

import counts


def test_llama_hand_count():
    c = tiny.CONFIGS["tiny-llama"]           # d 48, 3/1 heads x 16, ff 96
    per_layer = (2 * 48 * 48 * 2             # q, o
                 + 2 * 48 * 16 * 2           # k, v
                 + 2 * 48 * 96 * 3           # gate, up, down
                 + 2 * 2 * 3 * 16 * 65 / 2)  # causal scores + values, seq 64
    fwd = 2 * per_layer + 2 * 48 * 256       # 2 layers + tied logits
    assert counts.forward_flops_per_token(c, 64) == fwd == 116928
    assert counts.model_flops_per_token(c, 64) == 3 * fwd


def test_matmuls_are_two_flops_per_weight():
    """Without attention, a token's forward matrix products are two FLOPs
    per weight of every matrix, the tied embedding counted once as the
    output head."""
    c = dict(tiny.CONFIGS["tiny-llama"])
    attn = 2 * 3 * 16 * 65 * c["num_hidden_layers"]
    norms = 2 * 2 * 48 + 48                  # two per layer, the final one
    weights = counts.param_count(c) - norms
    assert counts.forward_flops_per_token(c, 64) - attn == 2 * weights


def test_published_sizes():
    import json
    import os
    cfgs = os.path.join(tiny.BENCH, "configs")
    with open(os.path.join(cfgs, "smollm-360m.json")) as f:
        smol = json.load(f)
    assert counts.param_count(smol) == 361_821_120
    assert round(counts.model_flops_per_token(smol, 2048) / 1e9, 3) == 2.548


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000, 50, peak) == (10.0, "compute")
    assert counts.least_time(100, 500, peak) == (50.0, "memory")
