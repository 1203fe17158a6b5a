"""Fault-tolerance substrates at the pjit layer: step watchdog
(timeout/retransmission), elastic re-mesh planning + resharding, and
journal-based train resume."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gss import TimeoutController
from repro.distributed.elastic import DevicePool, plan_mesh, reshard_tree
from repro.distributed.watchdog import StepTimeout, StepWatchdog
from repro.distributed import sharding as shd
from repro.models.common import ParamSpec


# ------------------------------------------------------------- watchdog
def test_watchdog_passthrough_and_adapt():
    wd = StepWatchdog(controller=TimeoutController(timeout=2.0))
    out = wd.run(lambda x: x + 1, 41)
    assert out == 42
    assert wd.timeouts_fired == 0
    # healthy steps shrink the timeout toward latency × slack
    for _ in range(5):
        wd.run(lambda: time.sleep(0.01))
    assert wd.controller.timeout < 2.0


def test_watchdog_reissues_straggler():
    wd = StepWatchdog(controller=TimeoutController(timeout=0.1,
                                                   min_timeout=0.05),
                      max_retries=3)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            time.sleep(0.5)       # straggler on first attempt
        return "ok"

    assert wd.run(flaky) == "ok"
    assert wd.timeouts_fired == 1
    assert len(calls) >= 2        # re-issued — the paper's retransmission


def test_watchdog_gives_up():
    wd = StepWatchdog(controller=TimeoutController(timeout=0.05,
                                                   min_timeout=0.01),
                      max_retries=1)
    with pytest.raises(StepTimeout):
        wd.run(lambda: time.sleep(2.0))


# ------------------------------------------------------------- elastic
def test_plan_mesh_shrinks_data_axis():
    devs = list(range(8))         # stand-in device objects
    pool = DevicePool(devs)
    mesh = plan_mesh(pool.alive(), model_axis=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    pool.fail([0, 5])             # 6 left
    mesh2 = plan_mesh(pool.alive(), model_axis=2)
    assert dict(mesh2.shape) == {"data": 3, "model": 2}
    pool.join(["n1", "n2"])
    mesh3 = plan_mesh(pool.alive(), model_axis=2)
    assert dict(mesh3.shape) == {"data": 4, "model": 2}


def test_reshard_tree_roundtrip():
    devs = jax.devices()
    mesh = plan_mesh(devs, model_axis=1)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    specs = {"w": ParamSpec((4, 4), ("embed", "mlp"))}
    out = reshard_tree(tree, specs, dict(shd.DEFAULT_RULES), mesh)
    np.testing.assert_array_equal(out["w"], tree["w"])


# ------------------------------------------------- journal-based resume
def test_train_resume_from_journal(tmp_path):
    from repro.launch.train import train
    kw = dict(reduced=True, steps=6, batch=2, seq=32, ckpt_every=2,
              ckpt_dir=str(tmp_path), log=lambda *a: None)
    first = train("smollm_360m", **kw)
    assert first["start_step"] == 0
    # "crash" after step 5 (run finished) → resume must be a no-op restart
    second = train("smollm_360m", **kw)
    assert second["start_step"] == 6
    assert second["losses"] == []
    # partial run: wipe journal tail to simulate crash at step 3
    jpath = tmp_path / "smollm_360m_reduced" / "journal.jsonl"
    lines = jpath.read_text().splitlines()
    jpath.write_text("\n".join(lines[:4]) + "\n")
    third = train("smollm_360m", **kw)
    assert third["start_step"] == 4
    assert len(third["losses"]) == 2
