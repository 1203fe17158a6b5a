"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit, which are also the last lines on standard
error. Without a TPU, or with fewer chips than the cell asks for, or
without the program's ``src/`` beside ``bench/``, it exits non-zero and
prints no result. See ``PERF.md`` for what each cell measures.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        log("run.py: --seed must be >= 0 and --seconds > 0")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"run.py: the program (src/repro) is not beside {BENCH}")
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(BENCH, "lib")]
    # libtpu logs to /tmp by default; keep every file inside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import harness

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(harness.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return 1
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, log=log)
    for name, c in out["checks"].items():
        log(f"{name} {c['value']:.6g} (limit {c['limit']:g})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
