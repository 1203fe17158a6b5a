"""WorkloadProgram benchmark — three workloads, one control plane (PR 3).

    PYTHONPATH=src python benchmarks/program_bench.py \
        [--smoke] [--backend B] [--programs mlp,moe,moe_faults,jax]

Runs each program through the *same* Manager/Handler plane and reports
wallclock, TS traffic, pouch rounds, and the loss trajectory ends:

- ``mlp``        — the paper's §6.1 workload (regular, 5 MLP ops);
- ``moe``        — the non-regular MoE routing program: data-dependent
                   per-expert task sizes (min/max cost spread reported);
- ``moe_faults`` — the MoE program under an **exp3-style fault plan**
                   (Manager AND all Handlers crash each interval with
                   p=1.0, speeds 1:5:10 re-drawn) — the non-regular
                   robustness gate;
- ``jax``        — the JAX-SGD program (reduced smollm) with 25%
                   per-task handler crashes;
- ``multi``      — MLP + MoE **co-resident on one tuple space** (each in
                   its own namespace) under a shared handler fleet and an
                   exp3-style p=1.0 fault plan — the multi-tenant gate.

Acceptance (exit code): every selected program's loss must decrease,
``moe`` must exhibit irregular (non-uniform) expert task costs,
``moe_faults`` must complete all rounds with ≥ 1 manager revival and
≥ 1 handler revival, and ``multi`` must complete both tenants with ≥ 1
manager revival, ≥ 1 handler revival, and **zero cross-namespace task
deletions** (no widened-subject deletes, nothing removed under an
unscoped task subject — InstrumentedBackend delete accounting).

Every leg additionally runs under the ``CheckedBackend`` protocol
sanitizer (PR 6) and gates on **zero schema/role violations and zero
tuple leaks** at shutdown. A ``raced+...`` backend spec (the PR 8 CI
leg) further widens the frontier to 8 in-flight stages with the
cost-model autotune on and gates on an **empty happens-before race
report** from the ``RacedBackend`` sanitizer.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.core import (ACANCloud, CloudConfig, FaultPlan, GLOBAL_OPS,  # noqa: E402
                        LayerSpec, MLPProgram, MoERoutingProgram)

DEFAULT_PROGRAMS = "mlp,moe,moe_faults,jax,multi"


def _ts_ops(res) -> int:
    s = res.ts_stats
    return s.get("puts", 0) + s.get("takes", 0) + s.get("reads", 0)


def _checked(spec: str | None) -> str:
    """Stack the protocol sanitizer onto ``spec`` (idempotent)."""
    inner = spec or os.environ.get("REPRO_TS_BACKEND", "") or "local"
    return inner if "checked" in inner else f"checked+{inner}"


def _ts_clean(res) -> bool:
    """Zero protocol violations, zero tuple leaks (CheckedBackend) and an
    empty happens-before race report (RacedBackend, PR 8 — trivially
    empty when the spec does not stack ``raced``)."""
    return (res.ts_violations == 0 and not res.ts_leaks
            and not getattr(res, "race_report", []))


def _race_kwargs(spec: str | None) -> dict:
    """Config overrides for the raced CI leg: widen the frontier to 8 and
    turn the cost-model autotune on, so the happens-before sanitizer
    watches real stage overlap rather than a serialized schedule."""
    inner = spec or os.environ.get("REPRO_TS_BACKEND", "") or "local"
    if "raced" not in inner:
        return {}
    return {"max_inflight_stages": 8, "autotune": True}


def run_mlp(smoke: bool, backend: str | None) -> dict:
    # The exp1 CI geometry (SGD bs=1 is noisy — single epochs over few
    # samples do not give a stable first/last comparison).
    epochs, n_samples = (2, 16) if smoke else (2, 100)
    cfg = CloudConfig(layers=[LayerSpec(64, 64), LayerSpec(64, 1)],
                      n_handlers=4, epochs=epochs, n_samples=n_samples,
                      task_cap=256.0, pouch_size=100, lr=0.01,
                      time_scale=1e-6, initial_timeout=0.12,
                      fault_plan=FaultPlan(interval=1e9), seed=0,
                      wall_limit=240.0, ts_backend=_checked(backend),
                      **_race_kwargs(backend))
    res = ACANCloud(cfg).run()
    losses = [l for _, l in res.loss_history]
    half = len(losses) // 2
    return {"name": "program_mlp", "wall": res.wallclock,
            "ts_ops": _ts_ops(res), "pouches": res.pouches,
            "first": float(np.mean(losses[:half])),
            "last": float(np.mean(losses[half:])),
            "completed": len(losses) == epochs * n_samples,
            "races": len(res.race_report),
            "ts_clean": _ts_clean(res),
            "ok": bool(np.mean(losses[half:]) < np.mean(losses[:half]))
            and _ts_clean(res)}


def _moe_cost_spread(prog: MoERoutingProgram) -> tuple[float, float]:
    """(min, max) expert task cost of one routing round — the measured
    irregularity of the non-regular program."""
    costs = [GLOBAL_OPS.cost(t) for t in prog.probe_expert_tasks()]
    return (min(costs), max(costs)) if costs else (0.0, 0.0)


def run_moe(smoke: bool, backend: str | None, faults: bool) -> dict:
    steps = (12 if smoke else 24) if faults else (8 if smoke else 16)
    prog = MoERoutingProgram(steps=steps, seed=0)
    plan = (FaultPlan(interval=0.1, speed_levels=(1.0, 5.0, 10.0),
                      p_speed_change=1.0, p_handler_crash=1.0,
                      p_manager_crash=1.0, seed=1)
            if faults else FaultPlan(interval=1e9))
    # The faults gate requires >= 1 manager AND handler revival, so the
    # workload must outlive several plan ticks on a machine of any speed:
    # scale the emulated per-task compute up for that leg instead of
    # trusting wallclock luck.
    time_scale = 2e-5 if faults else 1e-6
    cfg = CloudConfig(n_handlers=4, task_cap=256.0, pouch_size=64,
                      time_scale=time_scale, initial_timeout=0.1,
                      fault_plan=plan, wall_limit=240.0,
                      ts_backend=_checked(backend),
                      **_race_kwargs(backend))
    res = ACANCloud(cfg, program=prog).run()
    losses = [l for _, l in res.loss_history]
    lo, hi = _moe_cost_spread(prog)
    completed = len(losses) == steps
    decreased = bool(len(losses) >= 4
                     and np.mean(losses[-3:]) < np.mean(losses[:3]))
    out = {"name": "program_moe_faults" if faults else "program_moe",
           "wall": res.wallclock, "ts_ops": _ts_ops(res),
           "pouches": res.pouches, "first": float(np.mean(losses[:3])),
           "last": float(np.mean(losses[-3:])), "completed": completed,
           "cost_min": lo, "cost_max": hi,
           "mgr_revive": res.manager_revivals,
           "hdl_revive": res.handler_revivals,
           "races": len(res.race_report),
           "ts_clean": _ts_clean(res)}
    if faults:
        out["ok"] = (completed and decreased and res.manager_revivals >= 1
                     and res.handler_revivals >= 1 and _ts_clean(res))
    else:
        out["ok"] = completed and decreased and hi > lo and _ts_clean(res)
    return out


def run_multi(smoke: bool, backend: str | None) -> dict:
    """The multi-tenant co-residency gate: MLP + MoE on ONE space, one
    shared handler fleet, exp3-style faults — both must complete with
    revivals and zero deletes capable of crossing a namespace."""
    # 2 epochs like run_mlp: SGD bs=1 is noisy — a single epoch over few
    # samples does not give a stable first-half/second-half comparison.
    epochs, n_samples = (2, 8) if smoke else (2, 24)
    moe_steps = 10 if smoke else 20
    inner = _checked(backend)
    cfg = CloudConfig(layers=[LayerSpec(32, 32), LayerSpec(32, 1)],
                      n_handlers=4, epochs=epochs, n_samples=n_samples,
                      task_cap=256.0, pouch_size=64, lr=0.01,
                      time_scale=2e-5, initial_timeout=0.1,
                      fault_plan=FaultPlan(
                          interval=0.1, speed_levels=(1.0, 5.0, 10.0),
                          p_speed_change=1.0, p_handler_crash=1.0,
                          p_manager_crash=1.0, seed=1),
                      wall_limit=240.0, ts_backend=f"instrumented+{inner}",
                      **_race_kwargs(backend))
    programs = [MLPProgram(cfg.layers, epochs=epochs, n_samples=n_samples,
                           seed=0),
                MoERoutingProgram(steps=moe_steps, seed=0)]
    cloud = ACANCloud(cfg, programs=programs)
    res = cloud.run()
    mlp = res.per_program["mlp"]
    moe = res.per_program["moe_routing"]
    mlp_losses = [l for _, l in mlp.loss_history]
    moe_losses = [l for _, l in moe.loss_history]
    completed = (len(mlp_losses) == epochs * n_samples
                 and len(moe_losses) == moe_steps)
    # zero cross-namespace task deletions: no widened-subject deletes and
    # nothing removed under an unscoped "task" subject.
    dm = cloud.ts.backend.delete_metrics()
    cross_free = (cloud.ts.stats()["instr_widened_deletes"] == 0
                  and dm.get("task", {"removed": 0})["removed"] == 0)
    half = len(mlp_losses) // 2
    decreased = bool(
        mlp_losses and moe_losses and len(moe_losses) >= 6
        and np.mean(mlp_losses[half:]) < np.mean(mlp_losses[:half])
        and np.mean(moe_losses[-3:]) < np.mean(moe_losses[:3]))
    return {"name": "program_multi",
            "wall": res.wallclock,
            "ts_ops": res.ts_stats.get("puts", 0)
            + res.ts_stats.get("takes", 0) + res.ts_stats.get("reads", 0),
            "pouches": mlp.pouches + moe.pouches,
            "first": float(np.mean(mlp_losses[:half])) if half else 0.0,
            "last": float(np.mean(mlp_losses[half:])) if half else 0.0,
            "completed": completed,
            "mgr_revive": res.manager_revivals,
            "hdl_revive": res.handler_revivals,
            "cross_ns_free": cross_free,
            "races": len(res.race_report),
            "ts_clean": _ts_clean(res),
            "ok": (completed and decreased and cross_free
                   and res.manager_revivals >= 1
                   and res.handler_revivals >= 1 and _ts_clean(res))}


def run_jax(smoke: bool, backend: str | None) -> dict:
    from repro.configs import get_config
    from repro.core.space import ANY
    from repro.programs.jax_sgd import JAXSGDProgram
    steps = 4 if smoke else 8
    prog = JAXSGDProgram(get_config("smollm_360m", reduced=True),
                         steps=steps, n_micro=3, micro_batch=2, seq=32,
                         lr=0.05, handler_crash_prob=0.25, seed=0)
    cloud = ACANCloud(CloudConfig(
        n_handlers=3, handler_batch=1, ts_backend=_checked(backend),
        initial_timeout=20.0, fault_plan=FaultPlan(interval=1e9)),
        program=prog)
    res = cloud.run()
    losses = [l for _, l in sorted(res.loss_history)]
    (_, versions), _ = cloud.ts.try_read(("params", ANY))
    return {"name": "program_jax_sgd", "wall": res.wallclock,
            "ts_ops": _ts_ops(res), "pouches": versions,
            "first": losses[0], "last": losses[-1],
            "completed": len(losses) == steps,
            "crashes": prog.crashes, "reissues": res.reissues,
            "ts_clean": _ts_clean(res),
            "ok": bool(len(losses) == steps and losses[-1] < losses[0])
            and _ts_clean(res)}


def run_programs(programs: list[str], smoke: bool,
                 backend: str | None) -> list[dict]:
    out = []
    for name in programs:
        if name == "mlp":
            out.append(run_mlp(smoke, backend))
        elif name == "moe":
            out.append(run_moe(smoke, backend, faults=False))
        elif name == "moe_faults":
            out.append(run_moe(smoke, backend, faults=True))
        elif name == "jax":
            out.append(run_jax(smoke, backend))
        elif name == "multi":
            out.append(run_multi(smoke, backend))
        else:
            raise SystemExit(f"unknown program {name!r}")
    return out


def bench_rows(smoke: bool = True, backend: str | None = None,
               include_jax: bool = False) -> list[tuple[str, float, str]]:
    """CSV rows for the benchmarks/run.py harness."""
    programs = (["mlp", "moe", "moe_faults"]
                + (["jax"] if include_jax else []) + ["multi"])
    rows = []
    for r in run_programs(programs, smoke, backend):
        derived = (f"loss {r['first']:.3f}->{r['last']:.3f} "
                   f"completed={r['completed']} pouches={r['pouches']} "
                   f"ok={r['ok']}")
        if "cost_max" in r:
            derived += (f" cost_spread={r['cost_min']:.0f}"
                        f"..{r['cost_max']:.0f}")
        if "mgr_revive" in r and r["name"].endswith(("faults", "multi")):
            derived += (f" mgr_revive={r['mgr_revive']} "
                        f"hdl_revive={r['hdl_revive']}")
        if "cross_ns_free" in r:
            derived += f" cross_ns_free={r['cross_ns_free']}"
        if "races" in r:
            derived += f" races={r['races']}"
        if "ts_clean" in r:
            derived += f" ts_clean={r['ts_clean']}"
        rows.append((r["name"], r["wall"] * 1e6, derived))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default=None,
                    help="tuple-space backend spec (default: "
                         "$REPRO_TS_BACKEND or local)")
    ap.add_argument("--programs", default=DEFAULT_PROGRAMS,
                    help=f"comma list (default: {DEFAULT_PROGRAMS})")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI run: fewer rounds per program")
    args = ap.parse_args()

    results = run_programs([p.strip() for p in args.programs.split(",") if p],
                           args.smoke, args.backend)
    print(f"{'program':<22}{'wall(s)':>9}{'ts_ops':>10}{'pouches':>9}"
          f"{'loss first->last':>20}{'ok':>5}")
    print("-" * 75)
    for r in results:
        print(f"{r['name']:<22}{r['wall']:>9.2f}{r['ts_ops']:>10,}"
              f"{r['pouches']:>9}"
              f"{r['first']:>11.3f} ->{r['last']:>7.3f}{str(r['ok']):>5}")
        extras = {k: r[k] for k in
                  ("cost_min", "cost_max", "mgr_revive", "hdl_revive",
                   "crashes", "reissues", "cross_ns_free", "races",
                   "ts_clean")
                  if k in r}
        if extras:
            print(f"{'':<22}{extras}")
    ok = all(r["ok"] for r in results)
    print(f"\nacceptance: {'PASS' if ok else 'FAIL'} "
          f"({sum(r['ok'] for r in results)}/{len(results)} programs)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
