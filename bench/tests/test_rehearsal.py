"""CPU rehearsal of a whole run at reduced size, and the command's
refusals without a chip or without the program."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import tiny

import harness

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def spec():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_file_is_found_by_name():
    s = spec()
    for w in s["workloads"]:
        cell = harness.load_cell(w["name"])
        assert set(cell.limits["checks"]) == {"loss", "grad", "change"}
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        harness.model_config(cell.config)      # widths match the program
    for m in s["end_to_end"] + s["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for c in s["configs"]:
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs(tmp_path, trace):
    root = tiny.make_root(str(tmp_path), {"t.sgd": ("tiny-llama", tiny.TRAFFIC)})
    cell = harness.load_cell("t.sgd", root)
    out = harness.run_cell(cell, 2**33 + 7, 1.0, trace, time.perf_counter(),
                           log=lambda m: None)
    assert RESULT_KEYS <= set(out) and list(out)[-1] == "checks"
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    got = set(out["metrics"])
    if trace:
        # No device trace on the CPU: the device readers find nothing.
        assert {"grad_runs_per_step", "ts_ops_per_step", "grad_task_s",
                "combine_s"} <= got
        assert not got & {"mfu", "grad_roofline", "device_idle_share"}
        assert out["metrics"]["grad_runs_per_step"]["value"] >= 4
    else:
        assert {"tokens_per_s", "setup_s"} <= got
        assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_traced_run_reads_the_traced_part(tmp_path, monkeypatch):
    """The trace, and every per-layer reader, cover the window up to the
    first commit ``TRACE_SECONDS`` after it opens, not the whole window."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.0)
    root = tiny.make_root(str(tmp_path), {"t.sgd": ("tiny-llama", tiny.TRAFFIC)})
    cell = harness.load_cell("t.sgd", root)
    lines = []
    out = harness.run_cell(cell, 3, 1.0, True, time.perf_counter(),
                           log=lines.append)
    window = next(x for x in lines if x.startswith("[window]"))
    part = next(x for x in lines if x.startswith("[traced part]"))
    assert part.startswith("[traced part] 1 steps in ")
    assert int(window.split()[1]) > 1
    assert out["correct"] and out["metrics"]["grad_runs_per_step"]["value"] >= 4


def test_added_cell_is_taken_without_an_edit(tmp_path):
    """A cell added as files (traffic, limits) and a BENCHMARK.json entry
    runs through the same harness, with faults from its traffic file."""
    traffic = dict(tiny.TRAFFIC, fault_plan={
        "interval": 0.3, "p_manager_crash": 1.0, "p_handler_crash": 1.0})
    root = tiny.make_root(str(tmp_path),
                          {"added.sgd_kills": ("tiny-llama", traffic)})
    cell = harness.load_cell("added.sgd_kills", root)
    out = harness.run_cell(cell, 11, 1.5, False, time.perf_counter(),
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["checks"]["missing_kills"]["value"] <= 1


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm360m.sgd",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _command(tiny.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr and not p.stdout.strip()


def test_command_refuses_without_the_program(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _command(str(tmp_path))
    assert p.returncode != 0 and not p.stdout.strip()
