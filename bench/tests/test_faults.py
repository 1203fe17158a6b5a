"""A run with the timed path broken underneath must come out not
correct: the harness's look for a chip is skipped and everything else
of a run is driven, at reduced size on the CPU.

Faults a one-chip training cell can have: a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest; a
gradient altered where it is produced. (There is no exchange between
chips on one chip, and no served token.)"""

import time

import jax
import pytest

import tiny

import harness
from repro.programs.jax_sgd import JAXSGDProgram


def unchanged(prog):
    prog.sgd_update = jax.jit(lambda params, grads: params)


def half_batch(prog):
    update = prog.sgd_update
    prog.sgd_update = lambda params, grads: update(
        params, grads[:len(grads) // 2])


def altered_grad(prog):
    grad_fn = prog.grad_fn

    def altered(params, batch):
        loss, g = grad_fn(params, batch)
        return loss, jax.tree.map(lambda x: x * 1.25, g)

    prog.grad_fn = altered


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered_grad])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, fault):
    init = JAXSGDProgram.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        fault(self)

    monkeypatch.setattr(JAXSGDProgram, "__init__", broken_init)
    root = tiny.make_root(str(tmp_path), {"t.sgd": ("tiny-llama", tiny.TRAFFIC)})
    cell = harness.load_cell("t.sgd", root)
    out = harness.run_cell(cell, 5, 1.0, False, time.perf_counter(),
                           log=lambda m: None)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1
