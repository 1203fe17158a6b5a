"""The control: the reference computed one precision step below the
configuration's bfloat16 (float8 e4m3 operands, per-tensor scales) put in
the program's place must come out not correct, on three seeds, while the
program itself comes out correct. At reduced size on the CPU; the chip's
readings at the cells' own sizes are in PERF.md (bench/calibrate.py)."""

import time

import jax

import tiny

import check
import harness
import reference as R


def test_control_fails_program_passes(tmp_path):
    root = tiny.make_root(str(tmp_path), {"t.sgd": ("tiny-llama", tiny.TRAFFIC)})
    cell = harness.load_cell("t.sgd", root)
    lr = cell.traffic["lr"]
    limits = cell.limits["checks"]
    ref32 = R.Reference(cell.config, lr)
    ctl = R.Reference(cell.config, lr, quantize=True)
    for seed in (1, 2, 3):
        rec, losses, params0, _ = harness.drive(cell, seed, 0, None,
                                                time.perf_counter())
        ref = harness.reference_run(cell, seed, params0, ref=ref32)
        prog = check.numbers(lr, params0, harness.program_run(rec, losses),
                             ref)
        control = check.numbers(lr, params0, harness.reference_run(
            cell, seed, params0, ref=ctl), ref)
        assert all(prog[k] <= v for k, v in limits.items()), prog
        assert any(control[k] > v for k, v in limits.items()), control
        jax.clear_caches()
