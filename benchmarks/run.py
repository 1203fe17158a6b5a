"""Benchmark harness — one entry per paper table/figure plus framework
benches. Prints ``name,us_per_call,derived`` CSV rows and persists the
same rows machine-readably to ``runs/bench/BENCH_<n>.json`` (next free
``n`` — one immutable artifact per invocation, so regressions can be
diffed across runs without scraping stdout; each row records the
metric, the raw derived string, and the parsed ``pass=`` gate verdict
where the row carries one).

    PYTHONPATH=src python -m benchmarks.run [--paper-scale]

Paper experiments (§6, Figures 1-4) run at CI scale by default (compressed
intervals, smaller N — structure preserved: speed ratios 1:5:10, crash
probability 1.0); ``--paper-scale`` runs the exact paper setup (slower)."""

from __future__ import annotations

import json
import os
import re
import sys
import time

#: Where the per-invocation JSON artifacts land (repo-relative).
BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "runs", "bench")


def _row_record(us: float, derived: str) -> dict:
    """One row's machine-readable record. ``gate_pass`` is the parsed
    ``pass=True/False`` verdict for gate rows, None for plain metrics."""
    m = re.search(r"\bpass=(True|False)\b", derived)
    return {"us_per_call": round(us, 1), "derived": derived,
            "gate_pass": None if m is None else m.group(1) == "True"}


def write_bench_json(rows: list[tuple[str, float, str]],
                     scale: str, out_dir: str = BENCH_DIR) -> str:
    """Persist rows to the next free ``BENCH_<n>.json`` and return its
    path. ``n`` is one past the highest existing artifact number, so
    artifacts are append-only across invocations."""
    os.makedirs(out_dir, exist_ok=True)
    taken = []
    for fn in os.listdir(out_dir):
        m = re.fullmatch(r"BENCH_(\d+)\.json", fn)
        if m is not None:
            taken.append(int(m.group(1)))
    path = os.path.join(out_dir, f"BENCH_{max(taken, default=0) + 1}.json")
    doc = {
        "scale": scale,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "rows": {name: _row_record(us, derived)
                 for name, us, derived in rows},
        "gates_passed": all(
            r["gate_pass"] is not False
            for r in (_row_record(us, d) for _, us, d in rows)),
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def main() -> None:
    paper_scale = "--paper-scale" in sys.argv
    scale = "paper" if paper_scale else "ci"
    rows: list[tuple[str, float, str]] = []

    from benchmarks import paper_experiments as PE

    t0 = time.perf_counter()
    r1 = PE.exp1_feasibility(scale)
    rows.append(("exp1_feasibility_fig1", (time.perf_counter() - t0) * 1e6,
                 f"mse {r1['first_mse']:.3f}->{r1['last_mse']:.3f} "
                 f"decreased={r1['decreased']} pouches={r1['pouches']}"))

    t0 = time.perf_counter()
    r2 = PE.exp2_adaptability(scale)
    rows.append(("exp2_adaptability_fig2", (time.perf_counter() - t0) * 1e6,
                 f"corr(timeout,power)={r2['corr_timeout_power']:.3f} "
                 f"inverse={r2['inverse']} pouches={r2['pouches']}"))

    t0 = time.perf_counter()
    r3 = PE.exp3_robustness(scale)
    rows.append(("exp3_robustness_fig3_4", (time.perf_counter() - t0) * 1e6,
                 f"completed={r3['completed']} "
                 f"mse {r3['first_mse']:.3f}->{r3['last_mse']:.3f} "
                 f"mgr_revive={r3['manager_revivals']} "
                 f"hdl_revive={r3['handler_revivals']} "
                 f"corr={r3['corr_timeout_power']:.3f}"))

    t0 = time.perf_counter()
    r4 = PE.acan_overhead(scale)
    rows.append(("acan_vs_direct_overhead_s8", (time.perf_counter() - t0) * 1e6,
                 f"overhead={r4['overhead_x']:.1f}x ts_ops={r4['ts_ops']}"))

    t0 = time.perf_counter()
    for row in PE.ablation_task_pouch(scale):
        rows.append((f"ablation_cap{int(row['task_cap'])}_pouch{row['pouch']}",
                     row["wall"] * 1e6,
                     f"pouches={row['pouches']} ts_ops={row['ts_ops']} "
                     f"mse={row['final_mse']}"))

    # Control-plane scheduling rows: the event plane's ops per pouch on
    # the §6.1 workload, the adaptive pouch-size row against the fixed §6
    # baseline, and the pipeline/autotune/raced/process-fleet gates.
    from benchmarks import sched_bench as SB
    rows.extend(SB.bench_rows(smoke=not paper_scale))

    # Remote tuple-space rows (PR 10): pipelined contention, pouch
    # batching (2 round-trips per put_many/take_batch pair), and the
    # read-through cache — each against a private server process.
    from benchmarks import ts_bench as TB
    rows.extend(TB.bench_rows(smoke=not paper_scale))

    # WorkloadProgram rows (PR 3/4): the paper MLP, the non-regular MoE
    # routing program (with and without an exp3-style fault plan), the
    # MLP+MoE multi-tenant co-residency gate, and — at paper scale — the
    # JAX-SGD program.
    from benchmarks import program_bench as PB
    rows.extend(PB.bench_rows(smoke=not paper_scale,
                              include_jax=paper_scale))

    # Crash-point sweep row (PR 9): arm the deterministic crash backend
    # at registry sites (sampled per protection class at CI scale, the
    # full Manager/Handler/executor site list at paper scale) and gate
    # recovery on completion + bit-identical trajectories + zero
    # leaks/races + role revival.
    import tools.crash_sweep as CS
    rows.extend(CS.bench_rows(smoke=not paper_scale))

    from benchmarks import kernel_bench as KB
    rows.extend(KB.bench_tuplespace())
    rows.extend(KB.bench_tile_matmul())
    rows.extend(KB.bench_attention())
    rows.extend(KB.bench_ssd())

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    path = write_bench_json(rows, scale)
    print(f"# wrote {os.path.relpath(path)}", file=sys.stderr)


if __name__ == "__main__":
    main()
