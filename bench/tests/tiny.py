"""Tiny cells for CPU tests: a copy of the benchmark in a temporary
directory with reduced configurations and short traffic added as files,
and a ``BENCHMARK.json`` that names them."""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "lib")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIGS = {
    "tiny-llama": {
        "name": "tiny-llama", "family": "llama",
        "program_config": "smollm_360m", "program_reduced": True,
        "hidden_size": 48, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 3, "num_key_value_heads": 1, "head_dim": 16,
        "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "torch_dtype": "bfloat16"},
}
TRAFFIC = {"seq": 64, "micro_batch": 2, "n_micro": 4, "lr": 0.5, "n_handlers": 3, "handler_batch": 1,
           "ts_backend": "sharded", "checked_steps": 3}
#: Limits for the tiny cells, from CPU readings on seeds 1-6: the program
#: reads at most loss 6.8e-5, grad 1.6e-3, change 1.9e-3; the float8
#: control reads over these limits on
#: at least one number on every seed (grad 2.3e-3 to 1.2e-2, change
#: 2.1e-3 to 5.2e-2, loss 7.6e-5 to 6.2e-4); half the batch reads loss
#: 4.7e-3 and more. At this size bf16 rounding is a large share of every
#: gap, so the margins are narrow; the cells' own limits come from the
#: chip at their own sizes.
LIMITS = {"loss": 1e-4, "grad": 2.5e-3, "change": 3e-3}


def make_root(tmp: str, cells: dict[str, tuple[str, dict]]) -> str:
    """A benchmark root under ``tmp`` whose ``BENCHMARK.json`` holds
    ``cells`` (name -> (config, traffic dict)) and the real metrics."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"], spec["workloads"] = [], []
    for name, (config, traffic) in cells.items():
        tname = name.replace(".", "_")
        _write(os.path.join(root, "bench", "traffic", tname + ".json"),
               traffic)
        _write(os.path.join(root, "bench", "workloads", name + ".json"),
               {"checks": LIMITS})
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": tname, "chips": 1,
                                  "why": "CPU test"})
        if not any(c["name"] == config for c in spec["configs"]):
            path = f"bench/configs/{config}.json"
            _write(os.path.join(root, path), CONFIGS[config])
            spec["configs"].append({"name": config, "source": "test",
                                    "file": path, "reduced": [],
                                    "why": "CPU test"})
    names = list(cells)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return root


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)
