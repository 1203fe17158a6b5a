"""Control-plane scheduling benchmark.

    PYTHONPATH=src python benchmarks/sched_bench.py [--smoke] [--backend B]

Runs the paper's §6.1 workload (2-layer MLP, 4 handlers) on the
tuple-space backend wrapped in ``InstrumentedBackend`` (blocking
``wait_count`` pouch barriers, batched ``take_batch`` task pickup,
blocking finished ``read``) and reports:

- **TS ops / pouch** — total instrumented tuple-space operations divided
  by completed pouch rounds (the control-plane cost of one unit of
  scheduling progress);
- **idle wakeups** — ``try_read``/``try_get`` misses plus blocking-op
  timeouts: wakeups that accomplished nothing;
- wallclock, and the same run with adaptive pouch sizing, whose loss
  trajectory must match the fixed-pouch run's.

It also reports the **pipeline** row (PR 5): the MoE routing workload —
whose per-expert stage DAG leaves handlers idle whenever one stage's
pouch does not fill the fleet — run once sequentially
(``max_inflight_stages=1``) and once under the frontier scheduler
(``max_inflight_stages=8``), comparing **makespan** and **handler
utilisation** (emulated busy seconds / fleet wallclock) with identical
loss trajectories.

And the **autotune** row (PR 7): the same MoE workload on a
heterogeneous fleet (speeds ``AUTOTUNE_SPEEDS``), static frontier-8
knobs vs the online cost model (``CloudConfig.autotune=True`` — learned
per-(op, handler) latencies drive drain order, slow-handler deferral,
frontier width and pouch sizing), with ``--autotune-only`` running just
that gate (the CI checked-backend leg).

Acceptance (exit code): the adaptive-pouch run must match the fixed
run's loss trajectory (1e-3 rtol — the batched executor may reassociate
float reductions); the pipelined MoE run must
beat the sequential makespan by **>= PIPELINE_SPEEDUP_FLOOR** with
higher handler utilisation and a bit-identical trajectory; the autotune
run must beat the static heterogeneous-fleet makespan by
**>= AUTOTUNE_SPEEDUP_FLOOR** with an identical trajectory and (under a
checked backend) zero protocol violations and zero leaks.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.core import (ACANCloud, CloudConfig, FaultPlan, LayerSpec,  # noqa: E402
                        MoERoutingProgram)
from repro.configs.paper_mlp import PAPER_LR  # noqa: E402

#: makespan improvement the frontier scheduler must deliver on the MoE
#: stage DAG (measured ~1.8x on 4 handlers; floor leaves CI timer slack).
PIPELINE_SPEEDUP_FLOOR = 1.25
#: makespan improvement the online cost model must deliver on the MoE DAG
#: over the static frontier-8 baseline when the fleet is heterogeneous
#: (speed ratios drawn from the paper's §6 1:5:10 palette): LPT drain
#: ordering plus slow-handler deferral keep the expert groups off the
#: slow boxes (measured 1.25–1.6x over 8 runs on both backends).
AUTOTUNE_SPEEDUP_FLOOR = 1.2
#: Heterogeneous speed ratios used by the autotune gate. Three slow
#: boxes + one 10x box maximises how much FIFO draining hurts the static
#: baseline, which is exactly the placement problem the model solves.
AUTOTUNE_SPEEDS = [1.0, 1.0, 1.0, 10.0]
#: makespan ceiling for stacking the happens-before race sanitizer
#: (``raced+``) onto the checked width-8 MoE pipeline run: the sanitizer
#: is metadata-only bookkeeping (vector clocks + access journals, no
#: payload copies), so it must stay within 15% of the checked makespan.
RACED_OVERHEAD_CEIL = 1.15
#: makespan improvement the out-of-process fleet (PR 10) must deliver
#: over the thread fleet on the autotuned width-8 MoE pipeline when
#: handler compute actually holds the GIL (``compute_mode="spin"``):
#: real processes overlap where threads serialise. Only meaningful with
#: >= PROCESS_FLEET_MIN_CORES cores — below that the gate skips (threads
#: and processes share one core and nothing can overlap).
PROCESS_FLEET_SPEEDUP_FLOOR = 1.5
PROCESS_FLEET_MIN_CORES = 4


def run_mlp(backend: str, layers, epochs: int, n_samples: int, seed: int,
            adaptive_pouch: bool = False) -> dict:
    cfg = CloudConfig(
        layers=layers, n_handlers=4, epochs=epochs, n_samples=n_samples,
        task_cap=256.0, pouch_size=100, lr=PAPER_LR, time_scale=2e-6,
        initial_timeout=0.25, fault_plan=FaultPlan(interval=1e9),
        seed=seed, wall_limit=600.0, ts_backend=f"instrumented:{backend}",
        adaptive_pouch=adaptive_pouch)
    cloud = ACANCloud(cfg)
    res = cloud.run()
    metrics = cloud.ts.backend.metrics()
    stats = cloud.ts.stats()
    ops = stats["instr_ops"]
    pouches = max(res.pouches, 1)
    return {
        "ops": ops,
        "pouches": res.pouches,
        "ops_per_pouch": ops / pouches,
        "idle_wakeups": stats["instr_misses"] + stats["instr_timeouts"],
        "wallclock": res.wallclock,
        "losses": [l for _, l in res.loss_history],
        "per_op": {op: int(m["calls"]) for op, m in sorted(metrics.items())},
    }


def run_pipeline_mode(max_inflight: int, backend: str, steps: int,
                      seed: int) -> dict:
    """One MoE run at the given frontier width. ``handler_batch=1`` keeps
    handlers from draining a whole narrow stage into one thread, so the
    comparison isolates *stage-level* concurrency (what the frontier
    adds) from batch-drain serialisation (orthogonal, PR 2)."""
    prog = MoERoutingProgram(steps=steps, seed=seed)
    cfg = CloudConfig(n_handlers=4, task_cap=128.0, pouch_size=64,
                      time_scale=2e-4, initial_timeout=0.25,
                      handler_batch=1, fault_plan=FaultPlan(interval=1e9),
                      wall_limit=600.0, ts_backend=backend,
                      max_inflight_stages=max_inflight)
    cloud = ACANCloud(cfg, program=prog)
    res = cloud.run()
    return {
        "max_inflight": max_inflight,
        "wallclock": res.wallclock,
        "utilisation": (cloud.handler_busy_time()
                        / max(cfg.n_handlers * res.wallclock, 1e-9)),
        "losses": [l for _, l in res.loss_history],
        "completed": len(res.loss_history) == steps,
        "pouches": res.pouches,
        "races": len(res.race_report),
    }


def run_autotune_mode(autotune: bool, backend: str, steps: int,
                      seed: int) -> dict:
    """One MoE run on the heterogeneous fleet, static frontier-8 knobs vs
    the online cost model. ``handler_batch=4`` gives the drain-order and
    deferral levers room to act (a 1-task batch has nothing to reorder);
    both runs share it, so the comparison isolates the model."""
    prog = MoERoutingProgram(steps=steps, seed=seed)
    cfg = CloudConfig(n_handlers=4, task_cap=128.0, pouch_size=64,
                      time_scale=2e-4, initial_timeout=0.25,
                      handler_batch=4, fault_plan=FaultPlan(interval=1e9),
                      wall_limit=600.0, ts_backend=backend,
                      max_inflight_stages=8,
                      handler_speeds=list(AUTOTUNE_SPEEDS),
                      autotune=autotune)
    cloud = ACANCloud(cfg, program=prog)
    res = cloud.run()
    return {
        "autotune": autotune,
        "wallclock": res.wallclock,
        "utilisation": (cloud.handler_busy_time()
                        / max(cfg.n_handlers * res.wallclock, 1e-9)),
        "losses": [l for _, l in res.loss_history],
        "completed": len(res.loss_history) == steps,
        "pouches": res.pouches,
        "deferred": res.cost_report.get("tasks_deferred", 0),
        "ts_violations": res.ts_violations,
        "ts_leaks": res.ts_leaks,
    }


def run_fleet_mode(fleet: str, backend: str, steps: int, seed: int) -> dict:
    """One autotuned width-8 MoE run with GIL-holding emulated compute
    (``compute_mode="spin"``) on the given fleet. The thread fleet
    serialises every spin slice on the GIL; the process fleet overlaps
    them for real — the contrast the PR 10 gate measures."""
    prog = MoERoutingProgram(steps=steps, seed=seed)
    cfg = CloudConfig(n_handlers=4, task_cap=128.0, pouch_size=64,
                      time_scale=2e-4, initial_timeout=0.25,
                      handler_batch=4, fault_plan=FaultPlan(interval=1e9),
                      wall_limit=600.0, ts_backend=backend,
                      max_inflight_stages=8, autotune=True,
                      fleet=fleet, compute_mode="spin")
    cloud = ACANCloud(cfg, program=prog)
    res = cloud.run()
    return {
        "fleet": fleet,
        "wallclock": res.wallclock,
        "losses": [l for _, l in res.loss_history],
        "completed": len(res.loss_history) == steps,
        "ts_violations": res.ts_violations,
        "ts_leaks": res.ts_leaks,
    }


def process_fleet_gate(smoke: bool, backend: str, seed: int = 0) -> dict:
    """Thread fleet vs out-of-process fleet (PR 10) on the autotuned MoE
    pipeline with spin compute: the GIL-escape acceptance gate. Loss
    trajectories must be bit-identical (the fleet is an execution detail,
    not a numerics one). Skips — passing, with a note — on boxes where
    no speedup is physically possible (< PROCESS_FLEET_MIN_CORES cores)."""
    cores = os.cpu_count() or 1
    if cores < PROCESS_FLEET_MIN_CORES:
        return {"skipped": (f"only {cores} core(s) — the GIL-escape "
                            f"contrast needs >= {PROCESS_FLEET_MIN_CORES}"),
                "ok": True}
    steps = 5 if smoke else 10
    thread = run_fleet_mode("thread", backend, steps, seed)
    proc = run_fleet_mode("process", backend, steps, seed)
    speedup = thread["wallclock"] / max(proc["wallclock"], 1e-9)
    loss_ok = (thread["completed"] and proc["completed"]
               and thread["losses"] == proc["losses"])   # bit-identical
    clean = proc["ts_violations"] == 0 and not proc["ts_leaks"]
    ok = speedup >= PROCESS_FLEET_SPEEDUP_FLOOR and loss_ok and clean
    return {"thread": thread, "process": proc, "speedup": speedup,
            "loss_ok": loss_ok, "clean": clean, "ok": ok}


def autotune_gate(smoke: bool, backend: str, seed: int = 0) -> dict:
    """Static frontier-8 vs cost-model autotune on the 1:1:1:10 fleet:
    the learned-latency acceptance gate. The trajectory must stay
    identical (the model only reorders/right-sizes scheduling; MoE is
    width-invariant), and under a checked backend the new cstats traffic
    must be violation- and leak-free."""
    # More steps than the pipeline gate: the model needs a few batches to
    # fit before deferral bites, and the amortised contrast is what the
    # floor protects — 5 steps is cold-start-dominated and noisy.
    steps = 10 if smoke else 15
    static = run_autotune_mode(False, backend, steps, seed)
    auto = run_autotune_mode(True, backend, steps, seed)
    speedup = static["wallclock"] / max(auto["wallclock"], 1e-9)
    loss_ok = (static["completed"] and auto["completed"]
               and static["losses"] == auto["losses"])   # identical
    clean = auto["ts_violations"] == 0 and not auto["ts_leaks"]
    ok = speedup >= AUTOTUNE_SPEEDUP_FLOOR and loss_ok and clean
    return {"static": static, "auto": auto, "speedup": speedup,
            "loss_ok": loss_ok, "clean": clean, "ok": ok}


def raced_overhead_gate(smoke: bool, backend: str, seed: int = 0) -> dict:
    """Checked vs raced+checked on the width-8 MoE pipeline run (PR 8):
    the happens-before sanitizer must stay within RACED_OVERHEAD_CEIL of
    the checked makespan, report zero races on the built-in DAG, and
    leave the loss trajectory bit-identical."""
    steps = 5 if smoke else 10
    checked = backend if "checked" in backend else f"checked+{backend}"
    raced_spec = checked if "raced" in checked else f"raced+{checked}"
    base = run_pipeline_mode(8, checked, steps, seed)
    raced = run_pipeline_mode(8, raced_spec, steps, seed)
    overhead = raced["wallclock"] / max(base["wallclock"], 1e-9)
    loss_ok = (base["completed"] and raced["completed"]
               and base["losses"] == raced["losses"])   # bit-identical
    ok = (overhead <= RACED_OVERHEAD_CEIL and loss_ok
          and raced["races"] == 0)
    return {"checked": base, "raced": raced, "overhead": overhead,
            "loss_ok": loss_ok, "ok": ok}


def pipeline_gate(smoke: bool, backend: str, seed: int = 0) -> dict:
    """Sequential vs pipelined MoE: the overlap-speedup acceptance gate."""
    steps = 5 if smoke else 10
    seq = run_pipeline_mode(1, backend, steps, seed)
    pipe = run_pipeline_mode(8, backend, steps, seed)
    speedup = seq["wallclock"] / max(pipe["wallclock"], 1e-9)
    loss_ok = (seq["completed"] and pipe["completed"]
               and seq["losses"] == pipe["losses"])   # bit-identical
    ok = (speedup >= PIPELINE_SPEEDUP_FLOOR
          and pipe["utilisation"] > seq["utilisation"]
          and loss_ok)
    return {"seq": seq, "pipe": pipe, "speedup": speedup,
            "loss_ok": loss_ok, "ok": ok}


def bench_rows(smoke: bool = True,
               backend: str = "sharded") -> list[tuple[str, float, str]]:
    """CSV rows for the benchmarks/run.py harness: the event plane's
    ops-per-pouch row, then one row per gate."""
    epochs, samples = (1, 8) if smoke else (2, 100)
    layers = [LayerSpec(256, 256), LayerSpec(256, 1)]
    fixed = run_mlp(backend, layers, epochs, samples, 0)
    rows = [(f"sched_event_{backend}", fixed["wallclock"] * 1e6,
             f"ts_ops={fixed['ops']} "
             f"ops_per_pouch={fixed['ops_per_pouch']:.1f} "
             f"idle_wakeups={fixed['idle_wakeups']} "
             f"pouches={fixed['pouches']}")]
    # Adaptive pouch sizing (PouchController in the Manager) vs the fixed
    # §6 pouch_size=100 baseline: measured, not gated — adaptation pays
    # off on wide stages/heterogeneous fleets, and the row keeps the
    # wiring honest (it must complete the same trajectory).
    adap = run_mlp(backend, layers, epochs, samples, 0,
                   adaptive_pouch=True)
    loss_ok = (len(adap["losses"]) == len(fixed["losses"])
               and np.allclose(adap["losses"], fixed["losses"],
                               rtol=1e-3, atol=1e-5))
    rows.append((f"sched_adaptive_pouch_{backend}", adap["wallclock"] * 1e6,
                 f"ts_ops={adap['ops']} "
                 f"ops_per_pouch={adap['ops_per_pouch']:.1f} "
                 f"pouches={adap['pouches']} "
                 f"(fixed: {fixed['pouches']}) loss_match={loss_ok}"))
    # Frontier scheduler vs sequential stage execution on the MoE DAG
    # (PR 5) — makespan + handler utilisation, trajectories bit-identical.
    pg = pipeline_gate(smoke, backend)
    rows.append((f"sched_pipeline_{backend}", pg["pipe"]["wallclock"] * 1e6,
                 f"seq={pg['seq']['wallclock']:.2f}s "
                 f"pipe={pg['pipe']['wallclock']:.2f}s "
                 f"speedup={pg['speedup']:.2f}x "
                 f"util={pg['seq']['utilisation']:.2f}->"
                 f"{pg['pipe']['utilisation']:.2f} "
                 f"loss_match={pg['loss_ok']} "
                 f"gate>={PIPELINE_SPEEDUP_FLOOR:.2f}x pass={pg['ok']}"))
    # Online cost model vs static knobs on the heterogeneous fleet (PR 7)
    # — learned latencies drive drain order, deferral, width and pouch.
    ag = autotune_gate(smoke, backend)
    rows.append((f"sched_autotune_{backend}",
                 ag["auto"]["wallclock"] * 1e6,
                 f"static={ag['static']['wallclock']:.2f}s "
                 f"auto={ag['auto']['wallclock']:.2f}s "
                 f"speedup={ag['speedup']:.2f}x "
                 f"deferred={ag['auto']['deferred']} "
                 f"loss_match={ag['loss_ok']} clean={ag['clean']} "
                 f"gate>={AUTOTUNE_SPEEDUP_FLOOR:.2f}x pass={ag['ok']}"))
    # Happens-before race sanitizer overhead (PR 8) — raced+checked vs
    # checked on the width-8 MoE pipeline: vector-clock bookkeeping only,
    # zero races on the built-in DAG, bit-identical trajectory.
    rg = raced_overhead_gate(smoke, backend)
    rows.append((f"sched_raced_overhead_{backend}",
                 rg["raced"]["wallclock"] * 1e6,
                 f"checked={rg['checked']['wallclock']:.2f}s "
                 f"raced={rg['raced']['wallclock']:.2f}s "
                 f"overhead={rg['overhead']:.2f}x "
                 f"races={rg['raced']['races']} "
                 f"loss_match={rg['loss_ok']} "
                 f"gate<={RACED_OVERHEAD_CEIL:.2f}x pass={rg['ok']}"))
    # Out-of-process fleet vs thread fleet (PR 10) — GIL-holding spin
    # compute, autotuned width-8 MoE, bit-identical trajectories.
    fg = process_fleet_gate(smoke, backend)
    if "skipped" in fg:
        rows.append((f"sched_process_fleet_{backend}", 0.0,
                     f"SKIPPED: {fg['skipped']}"))
    else:
        rows.append((f"sched_process_fleet_{backend}",
                     fg["process"]["wallclock"] * 1e6,
                     f"thread={fg['thread']['wallclock']:.2f}s "
                     f"process={fg['process']['wallclock']:.2f}s "
                     f"speedup={fg['speedup']:.2f}x "
                     f"loss_match={fg['loss_ok']} clean={fg['clean']} "
                     f"gate>={PROCESS_FLEET_SPEEDUP_FLOOR:.2f}x "
                     f"pass={fg['ok']}"))
    return rows


def _print_process_fleet(fg: dict) -> None:
    if "skipped" in fg:
        print(f"process fleet (MoE, spin compute): SKIPPED — "
              f"{fg['skipped']}")
        return
    print(f"process fleet (MoE, spin compute, autotune width 8): "
          f"thread={fg['thread']['wallclock']:.2f}s "
          f"process={fg['process']['wallclock']:.2f}s "
          f"speedup={fg['speedup']:.2f}x "
          f"(target >= {PROCESS_FLEET_SPEEDUP_FLOOR:.2f}x), "
          f"trajectory {'bit-identical' if fg['loss_ok'] else 'DIVERGES'}, "
          f"ts_violations={fg['process']['ts_violations']}, "
          f"ts_leaks={len(fg['process']['ts_leaks'])} "
          f"-> {'PASS' if fg['ok'] else 'FAIL'}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="sharded",
                    help="inner tuple-space backend spec (default: sharded)")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--samples", type=int, default=100)
    ap.add_argument("--dim", type=int, default=256,
                    help="hidden width (paper §6.1: 256)")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI run: same 256-wide §6.1 geometry, "
                         "1 epoch, 8 samples")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune-only", action="store_true",
                    help="run only the cost-model autotune gate (the CI "
                         "checked-backend leg: speedup + identical "
                         "trajectory + zero ts violations/leaks)")
    ap.add_argument("--process-fleet-only", action="store_true",
                    help="run only the PR 10 out-of-process fleet gate "
                         "(thread vs process, spin compute, bit-identical "
                         "trajectory; skips below "
                         f"{PROCESS_FLEET_MIN_CORES} cores)")
    args = ap.parse_args()

    if args.process_fleet_only:
        fg = process_fleet_gate(args.smoke, args.backend, args.seed)
        _print_process_fleet(fg)
        return 0 if fg["ok"] else 1

    if args.autotune_only:
        ag = autotune_gate(args.smoke, args.backend, args.seed)
        print(f"autotune (MoE, speeds {AUTOTUNE_SPEEDS}): "
              f"static={ag['static']['wallclock']:.2f}s "
              f"auto={ag['auto']['wallclock']:.2f}s "
              f"speedup={ag['speedup']:.2f}x "
              f"(target >= {AUTOTUNE_SPEEDUP_FLOOR:.2f}x), "
              f"deferred={ag['auto']['deferred']}, "
              f"trajectory {'identical' if ag['loss_ok'] else 'DIVERGES'}, "
              f"ts_violations={ag['auto']['ts_violations']}, "
              f"ts_leaks={len(ag['auto']['ts_leaks'])} "
              f"-> {'PASS' if ag['ok'] else 'FAIL'}")
        return 0 if ag["ok"] else 1

    if args.smoke:
        args.epochs, args.samples = 1, 8
    layers = [LayerSpec(args.dim, args.dim), LayerSpec(args.dim, 1)]

    fixed = run_mlp(args.backend, layers, args.epochs, args.samples,
                    args.seed)
    adap = run_mlp(args.backend, layers, args.epochs, args.samples,
                   args.seed, adaptive_pouch=True)

    width = 18
    for label, key in [("TS ops total", "ops"),
                       ("pouches", "pouches"),
                       ("TS ops / pouch", "ops_per_pouch"),
                       ("idle wakeups", "idle_wakeups"),
                       ("wallclock (s)", "wallclock")]:
        print(f"{label:<{width}}{fixed[key]:>14,.1f}")
    print(f"\nper-op calls: {fixed['per_op']}")
    adap_loss_ok = (len(adap["losses"]) == len(fixed["losses"])
                    and np.allclose(adap["losses"], fixed["losses"],
                                    rtol=1e-3, atol=1e-5))
    print(f"adaptive pouch: pouches={adap['pouches']} "
          f"(fixed: {fixed['pouches']}), "
          f"ops/pouch={adap['ops_per_pouch']:.1f} "
          f"(fixed: {fixed['ops_per_pouch']:.1f}), "
          f"wallclock={adap['wallclock']:.2f}s, "
          f"loss_match={adap_loss_ok}")

    pg = pipeline_gate(args.smoke, args.backend, args.seed)
    print(f"\npipeline (MoE stage DAG, frontier vs sequential): "
          f"seq={pg['seq']['wallclock']:.2f}s "
          f"pipe={pg['pipe']['wallclock']:.2f}s "
          f"speedup={pg['speedup']:.2f}x "
          f"(target >= {PIPELINE_SPEEDUP_FLOOR:.2f}x), "
          f"utilisation {pg['seq']['utilisation']:.2f} -> "
          f"{pg['pipe']['utilisation']:.2f}, "
          f"trajectory {'bit-identical' if pg['loss_ok'] else 'DIVERGES'}")

    ag = autotune_gate(args.smoke, args.backend, args.seed)
    print(f"autotune (MoE, heterogeneous speeds {AUTOTUNE_SPEEDS}): "
          f"static={ag['static']['wallclock']:.2f}s "
          f"auto={ag['auto']['wallclock']:.2f}s "
          f"speedup={ag['speedup']:.2f}x "
          f"(target >= {AUTOTUNE_SPEEDUP_FLOOR:.2f}x), "
          f"deferred={ag['auto']['deferred']}, "
          f"trajectory {'identical' if ag['loss_ok'] else 'DIVERGES'}")

    rg = raced_overhead_gate(args.smoke, args.backend, args.seed)
    print(f"raced sanitizer (MoE pipeline, width 8): "
          f"checked={rg['checked']['wallclock']:.2f}s "
          f"raced={rg['raced']['wallclock']:.2f}s "
          f"overhead={rg['overhead']:.2f}x "
          f"(ceiling <= {RACED_OVERHEAD_CEIL:.2f}x), "
          f"races={rg['raced']['races']}, "
          f"trajectory {'bit-identical' if rg['loss_ok'] else 'DIVERGES'}")

    fg = process_fleet_gate(args.smoke, args.backend, args.seed)
    _print_process_fleet(fg)

    ok = adap_loss_ok and pg["ok"] and ag["ok"] and rg["ok"] and fg["ok"]
    print(f"\nacceptance: adaptive pouch "
          f"{'matches' if adap_loss_ok else 'DIVERGES'}, "
          f"pipeline overlap {'PASS' if pg['ok'] else 'FAIL'}, "
          f"autotune {'PASS' if ag['ok'] else 'FAIL'}, "
          f"raced overhead {'PASS' if rg['ok'] else 'FAIL'}, "
          f"process fleet {'PASS' if fg['ok'] else 'FAIL'} "
          f"-> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
