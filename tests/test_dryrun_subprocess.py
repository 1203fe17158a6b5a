"""Dry-run integration: the real launch/dryrun.py machinery (XLA_FLAGS
device-count override, mesh build, lower+compile, HLO census, roofline
JSON) exercised in a subprocess with a scaled-down device count.

The 512-device production sweep lives in experiments/; this test keeps the
code path from rotting in CI without paying the full compile bill."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from dataclasses import replace
from repro.configs.base import SHAPES, get_config
from repro.launch.steps import build_cell, lower_cell
from repro.launch.hlo_analysis import analyze_hlo
from repro.optim.optimizer import OptConfig

from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = get_config("deepseek_v2_lite_16b", reduced=True)
shape = replace(SHAPES["train_4k"], seq=64, batch=8)
cell = build_cell(cfg, shape, mesh, OptConfig())
compiled = lower_cell(cell).compile()
census = analyze_hlo(compiled.as_text(), total_devices=8)
ma = compiled.memory_analysis()
peak = ma.peak_memory_in_bytes
out = {
    "flops": census.flops,
    "bytes": census.hbm_bytes,
    "coll": census.collective_bytes,
    "n_coll_ops": len(census.collectives),
    "trips": len(census.trip_counts),
    "peak": int(peak),
}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_pipeline_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=480)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT "):])
    assert out["flops"] > 1e6           # loop-corrected dots counted
    assert out["bytes"] > out["flops"] / 100
    assert out["n_coll_ops"] > 0        # SPMD emitted collectives
    assert out["trips"] >= 1            # scan trip counts inferred
    assert out["peak"] > 0
