"""End-to-end behaviour of the paper's system: the full ACAN pipeline
(tuple space → manager → handlers → SGD) reproduces plain-numpy training
exactly when faults are off, and the pieces compose into the training
framework (model zoo + ACAN JAX-SGD program + recovery)."""

import numpy as np
import pytest

from repro.core import (ACANCloud, CloudConfig, FaultPlan, LayerSpec,
                        TupleSpace, make_teacher_data)
from repro.core.executor import TaskExecutor, activation
from repro.core.tasks import TaskDesc


def _numpy_reference_training(layers, X, Y, lr, epochs):
    """Plain numpy SGD(bs=1) with the same init as the Manager."""
    rng = np.random.default_rng(0)
    Ws, bs = [], []
    for spec in layers:
        Ws.append((rng.standard_normal((spec.n_out, spec.n_in))
                   / np.sqrt(spec.n_in)).astype(np.float32))
        bs.append(np.zeros(spec.n_out, dtype=np.float32))
    losses = []
    for _ in range(epochs):
        for x, y in zip(X, Y):
            acts = [x]
            pres = []
            h = x
            for i, (W, b) in enumerate(zip(Ws, bs)):
                z = W @ h + b
                pres.append(z)
                h = activation(z) if i < len(Ws) - 1 else z
                acts.append(h)
            diff = h - y
            losses.append(float(np.sum(diff * diff) / len(diff)))
            dy = 2 * diff / len(diff)
            for i in reversed(range(len(Ws))):
                x_in = acts[i]
                gW = np.outer(dy, x_in)
                gB = dy.copy()
                if i > 0:
                    dx = Ws[i].T @ dy
                    dy = dx * (1 - acts[i] ** 2)
                Ws[i] = Ws[i] - lr * gW
                bs[i] = bs[i] - lr * gB
    return losses


@pytest.mark.parametrize("plane", [
    {},
    # One task per take_batch: how every JAX-SGD job drains its tasks.
    {"handler_batch": 1},
    # A frontier of four stages: the next sample's forward overlaps the
    # update of this one.
    {"max_inflight_stages": 4},
], ids=["default", "handler_batch_1", "frontier_4"])
def test_acan_training_matches_numpy_reference(plane):
    """With no faults the distributed tuple-space pipeline must produce
    the same loss trajectory as sequential numpy SGD — the strongest
    correctness statement for the paper's §5 task decomposition — however
    the control plane drains and overlaps the tasks."""
    layers = [LayerSpec(16, 16), LayerSpec(16, 1)]
    cfg = CloudConfig(layers=layers, n_handlers=3, epochs=1, n_samples=8,
                      task_cap=32.0, pouch_size=64, lr=0.05,
                      time_scale=5e-7, initial_timeout=0.1,
                      fault_plan=FaultPlan(interval=1e9), seed=0,
                      wall_limit=60.0, **plane)
    res = ACANCloud(cfg).run()
    X, Y = make_teacher_data(layers, 8, 0)
    ref = _numpy_reference_training(layers, X, Y, 0.05, 1)
    got = [l for _, l in res.loss_history]
    assert len(got) == len(ref)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_single_task_executor_forward():
    """One forward tile against TS computes exactly W[o,:i]·x[:i]."""
    ts = TupleSpace()
    rng = np.random.default_rng(1)
    W = rng.standard_normal((8, 8)).astype(np.float32)
    x = rng.standard_normal(8).astype(np.float32)
    ts.put(("w", 0), W)
    ts.put(("x", 0), x)
    ex = TaskExecutor(ts)
    t = TaskDesc("forward", 0, 0, 0, 0, 4, 2, 6)
    ex.execute(t)
    _, part = ts.read(("fpart", 0, 0, 2, 6, 0, 4))
    np.testing.assert_allclose(part, W[2:6, :4] @ x[:4], rtol=1e-6)


def test_duplicate_execution_is_idempotent():
    """Paper §5.4: re-executing a non-update task rewrites identical
    values — simulate a timeout re-issue and check TS state is unchanged."""
    ts = TupleSpace()
    rng = np.random.default_rng(2)
    ts.put(("w", 0), rng.standard_normal((8, 8)).astype(np.float32))
    ts.put(("x", 0), rng.standard_normal(8).astype(np.float32))
    ex = TaskExecutor(ts)
    t = TaskDesc("forward", 0, 0, 0, 0, 8, 0, 8)
    ex.execute(t)
    _, first = ts.read(("fpart", 0, 0, 0, 8, 0, 8))
    ex.execute(t)                       # duplicate (late straggler)
    _, second = ts.read(("fpart", 0, 0, 0, 8, 0, 8))
    np.testing.assert_array_equal(first, second)
    assert ts.count(("fpart", 0, 0, 0, 8, 0, 8)) == 1
