"""chip_smoke.py on the CPU: it refuses to report success without a TPU
or without the repo, and each of its phases passes at a tiny size (the
kernels in the Pallas interpreter, the mesh on virtual CPU devices)."""

import importlib.util
import io
import json
import os
import pickle
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu_or_repo(tmp_path, where):
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script], cwd=os.path.dirname(script),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert not _ok_line(proc.stdout)
    assert '"ok": true' not in proc.stdout


def _spy_wire(monkeypatch) -> dict:
    """Count the frames this process encodes and the jax.Arrays in them."""
    import jax

    from repro.core.space import wire
    seen = {"frames": 0, "device_arrays": 0}

    class Spy(pickle.Pickler):
        def reducer_override(self, obj):
            if isinstance(obj, jax.Array):
                seen["device_arrays"] += 1
            return NotImplemented

    orig = wire.encode_segments

    def encode_segments(msg):
        seen["frames"] += 1
        Spy(io.BytesIO(), protocol=5).dump(msg)
        return orig(msg)

    monkeypatch.setattr(wire, "encode_segments", encode_segments)
    return seen


def test_sgd_phase_matches_reference_on_both_backends(monkeypatch):
    """The control-plane JAX-SGD runs finish, re-issue crashed tasks, and
    reproduce the plain reference's losses and final params. No jax.Array
    crosses the wire to the remote+sharded run's server process (decoding
    one there would device_put)."""
    from repro.configs import get_config
    smoke = _load()
    wire = _spy_wire(monkeypatch)
    report = smoke.sgd_phase(
        get_config("smollm_360m", reduced=True), steps=3, n_micro=4,
        micro_batch=2, seq=32, n_handlers=3, crash_prob=0.25,
        backends=["sharded", "remote+sharded"], seed=0, wall_limit=120.0)
    assert report["ok"], report
    for run in report["runs"].values():
        assert run["finished"] and run["reissues"] >= 1
        assert run["max_rel_gap"] <= smoke.SGD_RTOL
        assert run["param_rel_gap"] <= smoke.SGD_RTOL
        np.testing.assert_allclose(run["losses"], report["reference"],
                                   rtol=smoke.SGD_RTOL)
    assert wire["frames"] > 0
    assert wire["device_arrays"] == 0


def test_sgd_phase_matches_reference_with_sliced_gradients(monkeypatch):
    """The same with every gradient leaf over 1 KB crossing to the host
    in slices and joined again for the combine: the reference's losses
    and final params still come out."""
    import jax

    from repro.configs import get_config
    from repro.programs import jax_sgd
    smoke = _load()
    monkeypatch.setattr(jax_sgd, "FETCH_SLICE_BYTES", 1024)
    cfg = get_config("smollm_360m", reduced=True)
    sliced = jax.eval_shape(lambda t: jax_sgd.slice_leaves(t, 1024),
                            jax_sgd.M.abstract_params(cfg))
    assert max(len(s) for s in sliced) > 1
    report = smoke.sgd_phase(
        cfg, steps=3, n_micro=4, micro_batch=2, seq=32, n_handlers=3,
        crash_prob=0.25, backends=["sharded"], seed=0, wall_limit=120.0)
    assert report["ok"], report
    run = report["runs"]["sharded"]
    assert run["finished"] and run["param_rel_gap"] <= smoke.SGD_RTOL
    np.testing.assert_allclose(run["losses"], report["reference"],
                               rtol=smoke.SGD_RTOL)


def test_program_puts_host_arrays_only():
    import jax

    from repro.configs import get_config
    from repro.core.space import ANY, TupleSpace
    from repro.programs.jax_sgd import JAXSGDProgram
    prog = JAXSGDProgram(get_config("smollm_360m", reduced=True), steps=1)
    ts = TupleSpace(backend="sharded")
    prog.setup(ts)
    (_, version), params = ts.try_read(("params", ANY))
    assert version == 0
    leaves = jax.tree.leaves(params)
    assert leaves and all(isinstance(x, np.ndarray) for x in leaves)


def test_kernel_phase_tiny_interpret():
    from repro.kernels.cases import flash_case, matmul_case, ssd_case
    smoke = _load()
    cases = (matmul_case(64, 96, 256, interpret=True),
             flash_case(2, 3, 64, 16, 32, interpret=True),
             ssd_case(4, 64, 8, 16, 16, interpret=True))
    rows = smoke.kernel_phase(cases, seed=0)
    assert [r["ok"] for r in rows] == [True] * 3, rows
    assert not any(r["mosaic"] for r in rows)    # interpreted, not Mosaic


MESH_SCRIPT = r"""
import dataclasses, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {repo!r})
import importlib.util, json
import repro.launch.train as T
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
FAULT = {fault!r}
build_cell = T.build_cell


def planted(cfg, shape, mesh, opt):
    # The fault hits the many-device run only, as a broken mesh step would.
    cell = build_cell(cfg, shape, mesh, opt)
    step = cell.step
    if mesh.size == 1 or FAULT == "none":
        return cell
    if FAULT == "no_update":
        def bad(params, opt_state, batch):
            _, opt_state, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
    else:                                  # "half_batch"
        def bad(params, opt_state, batch):
            half = {{k: v[: v.shape[0] // 2] for k, v in batch.items()}}
            return step(params, opt_state, half)
    return dataclasses.replace(cell, step=bad)


T.build_cell = planted
rep = smoke.mesh_phase("smollm_360m", reduced=True, steps=2, batch=4,
                       seq=32, model_axis=2, n_devices=4, seed=0)
print("RESULT " + json.dumps({{"ok": rep["ok"],
      "devices": sorted(rep["per_device_bytes"]["params"]),
      "loss_gap": rep["max_rel_gap"], "update_gap": rep["update_gap"]}}))
"""


@pytest.mark.parametrize("fault", ["none", "no_update", "half_batch"])
def test_mesh_phase_on_four_virtual_devices(fault):
    """The mesh phase passes a sound mesh step, and its update check fails
    a step that does not update or sees half the batch — faults the
    losses alone barely show at random init."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = MESH_SCRIPT.format(repo=REPO, script=SCRIPT, fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["devices"] == [0, 1, 2, 3], proc.stdout[-3000:]
    smoke = _load()
    if fault == "none":
        assert out["ok"], proc.stdout[-3000:]
    else:
        assert not out["ok"]
        assert out["update_gap"] > smoke.MESH_UPDATE_RTOL
