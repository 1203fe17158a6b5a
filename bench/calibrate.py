"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 1 2 ... [--controls 3]

On the chip, at the cell's own sizes, in one process: for every seed the
program's checked steps through ``ACANCloud.run`` (no window) against the
float32 reference; for the first ``--controls`` seeds also the control
(the reference with float8 e4m3 operands, put in the program's place) and
the half-batch fault (the reference over half of each step's
micro-batches, the mean taken over the rest) against the same reference.
One JSON line per reading goes to standard output and to
``bench/.cache/calibrate_<cell>.jsonl``; the benchmark's runs never run
this. A state left unchanged reads 1 on ``change`` and needs no run."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(BENCH, "lib")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import check
    import harness
    import reference as R

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(harness.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    cell = harness.load_cell(args.workload)
    lr = cell.traffic["lr"]
    ref32 = R.Reference(cell.config, lr)
    ctl = R.Reference(cell.config, lr, quantize=True)
    os.makedirs(harness.CACHE, exist_ok=True)
    path = os.path.join(harness.CACHE, f"calibrate_{args.workload}.jsonl")
    with open(path, "a") as out:
        def emit(kind, seed, nums, **extra):
            line = json.dumps({"cell": args.workload, "kind": kind,
                               "seed": seed, **nums, **extra})
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()

        for i, seed in enumerate(args.seeds):
            t0 = time.perf_counter()
            rec, losses, params0, mem = harness.drive(
                cell, seed, 0, None, t0, log=lambda m: print(
                    m, file=sys.stderr, flush=True))
            t1 = time.perf_counter()
            ref = harness.reference_run(cell, seed, params0, ref=ref32)
            t2 = time.perf_counter()
            emit("program", seed, check.numbers(
                lr, params0, harness.program_run(rec, losses), ref),
                losses=losses, ref_losses=ref.losses, program_s=t1 - t0,
                reference_s=t2 - t1, memory_peak_bytes=mem)
            if i < args.controls:
                emit("control", seed, check.numbers(
                    lr, params0, harness.reference_run(
                        cell, seed, params0, ref=ctl), ref))
                emit("half_batch", seed, check.numbers(
                    lr, params0, harness.reference_run(
                        cell, seed, params0, ref=ref32,
                        micro=cell.traffic["n_micro"] // 2), ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
