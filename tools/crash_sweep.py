"""Deterministic crash-point model checker (PR 9).

For every TS mutation site the crash lint enumerates (see
:mod:`tools.crash_lint` — the two tools share one site address space),
this sweep:

1. runs a small crash-free MLP training job as the **baseline**;
2. re-runs it with the :class:`~repro.core.space.crashpoint.
   CrashPointBackend` armed at the site (``nth=1``, ``when="after"`` —
   the first traversal dies right after the write lands);
3. lets the :class:`~repro.core.faults.MonitorDaemon` revive the dead
   thread through the normal plumbing; and
4. gates the recovered run on the repo's recovery invariants:

   - the run **completes** (the finished flag is published),
   - the **loss trajectory is bit-identical** to the crash-free
     baseline (determinism is the §5.4 guarantee, and it must hold
     through any single crash),
   - the **final weights are bit-identical** (the observable form of
     exactly-once commits: a re-combined commit writes the same bytes),
   - the shutdown leak scan is clean (``ts_leaks == {}``) and the
     happens-before race scan is empty (``race_report == []``) on the
     ``checked`` leg,
   - the crashed role was actually **revived** (daemon counters).

Sites whose code path the small job never takes (capability misses,
autotune deferrals, MoE/JAX program sites) are reported ``unreached`` —
the armed run must still match the baseline exactly, which is itself a
gate (an armed-but-silent backend must be transparent).

Usage::

    python -m tools.crash_sweep                  # full sweep, both backends
    python -m tools.crash_sweep --smoke          # one site per class+role
    python -m tools.crash_sweep --backends crashpoint+sharded
    python -m tools.crash_sweep --list           # show the sweep plan

Exit status: 0 all gates pass, 1 any gate failed, 2 usage error.
"""

from __future__ import annotations

import argparse
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO / "src") not in sys.path:
    sys.path.insert(0, str(_REPO / "src"))
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from tools.crash_lint import Site, site_registry  # noqa: E402

#: Files whose sites the sweep exercises (the single-tenant MLP job's
#: reachable universe). MoE/jax_sgd program sites are enumerated by the
#: lint but need their own workload to reach.
SWEEP_FILES = (
    "src/repro/core/manager.py",
    "src/repro/core/handler.py",
    "src/repro/core/executor.py",
    "src/repro/core/program.py",
    "src/repro/programs/mlp.py",
)

SWEEP_ROLES = ("manager", "handler", "executor")

#: Sites where a fired crash legitimately yields NO revival: the
#: finished-flag publish is the Manager's terminal TS op, and the
#: MonitorDaemon deliberately does not revive a finished Manager
#: (crash-after-publish is indistinguishable from a normal exit). Any
#: new site landing here must be reviewed, not blanket-exempted.
NO_REVIVAL_SITES = frozenset({
    "manager:manager.Manager._run:put[mstate]#0",
})

DEFAULT_BACKENDS = ("crashpoint+sharded", "crashpoint+checked+sharded")


def sweep_sites() -> list[Site]:
    return [s for s in site_registry()
            if s.path in SWEEP_FILES and s.role in SWEEP_ROLES]


def _sample_per_class(sites: list[Site], n: int) -> list[Site]:
    """Up to ``n`` sites per (protection class, role) pair — the CI
    smoke subset."""
    out: list[Site] = []
    seen: dict[tuple[str | None, str], int] = {}
    for s in sites:
        k = (s.protection, s.role)
        if seen.get(k, 0) < n:
            seen[k] = seen.get(k, 0) + 1
            out.append(s)
    return out


@dataclass
class SiteResult:
    site_id: str
    backend: str
    reached: bool
    ok: bool
    failures: list[str] = field(default_factory=list)
    revivals: int = 0
    seconds: float = 0.0


@dataclass
class _RunOut:
    finished: bool
    losses: list
    weights: list
    ts_leaks: dict
    race_report: list
    manager_revivals: int
    handler_revivals: int
    firings: list


def _run_once(backend: str, spec=None) -> _RunOut:
    from repro.core import ACANCloud, CloudConfig, FaultPlan, LayerSpec
    from repro.core.space import find_crashpoint

    cfg = CloudConfig(
        layers=[LayerSpec(8, 8), LayerSpec(8, 1)],
        # ONE handler: a crashed handler must then be revived for the
        # run to complete at all, which makes the revival gate sound —
        # with a fleet, a sub-liveness-quantum job can finish on the
        # survivors before the daemon ever notices the death.
        n_handlers=1, epochs=1, n_samples=4, task_cap=256.0,
        pouch_size=50, lr=0.02, time_scale=1e-6, initial_timeout=0.1,
        wall_limit=60.0, seed=0, ts_backend=backend,
        # Interval faults off: the crash point is the only fault.
        fault_plan=FaultPlan(interval=1e9),
    )
    cloud = ACANCloud(cfg)
    cp = find_crashpoint(cloud.ts.backend)
    if cp is None:
        raise SystemExit(f"backend spec {backend!r} has no crashpoint "
                         f"wrapper — stack it as crashpoint+...")
    if spec is not None:
        cp.arm(spec)
    res = cloud.run()
    finished = cloud.ts.try_read(("mstate", "finished")) is not None
    n_layers = len(cfg.layers)
    weights = [cloud.ts.try_read(("w", l)) for l in range(n_layers)]
    return _RunOut(
        finished=finished, losses=list(res.loss_history),
        weights=[None if w is None else w[1] for w in weights],
        ts_leaks=dict(res.ts_leaks), race_report=list(res.race_report),
        manager_revivals=res.manager_revivals,
        handler_revivals=res.handler_revivals,
        firings=list(cp.firings))


def _weights_equal(a: list, b: list) -> bool:
    import numpy as np
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x is None or y is None:
            return False
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape or not (x == y).all():
            return False
    return True


def _gate(site: Site, run: _RunOut, base: _RunOut, backend: str
          ) -> SiteResult:
    fails: list[str] = []
    reached = bool(run.firings)
    if not run.finished:
        fails.append("run did not complete")
    if run.losses != base.losses:
        fails.append(f"loss trajectory diverged "
                     f"({len(run.losses)} vs {len(base.losses)} points)")
    if not _weights_equal(run.weights, base.weights):
        fails.append("final weights differ from crash-free baseline")
    if run.ts_leaks:
        fails.append(f"ts_leaks={run.ts_leaks}")
    if run.race_report:
        fails.append(f"{len(run.race_report)} race(s) reported")
    if reached and site.site_id not in NO_REVIVAL_SITES:
        revived = (run.manager_revivals if site.role == "manager"
                   else run.handler_revivals)
        if revived < 1:
            fails.append(f"crash fired but no {site.role} revival "
                         f"was recorded")
    return SiteResult(
        site_id=site.site_id, backend=backend, reached=reached,
        ok=not fails, failures=fails,
        revivals=run.manager_revivals + run.handler_revivals)


def sweep(sites: list[Site], backends: tuple[str, ...] = DEFAULT_BACKENDS,
          verbose: bool = True) -> list[SiteResult]:
    from repro.core.space import CrashSpec

    results: list[SiteResult] = []
    for backend in backends:
        base = _run_once(backend)
        for site in sites:
            spec = CrashSpec(site_id=site.site_id, role=site.role,
                             path=site.path, line=site.line,
                             end_line=site.end_line, nth=1, when="after")
            t0 = time.perf_counter()
            run = _run_once(backend, spec)
            r = _gate(site, run, base, backend)
            r.seconds = time.perf_counter() - t0
            results.append(r)
            if verbose:
                mark = ("ok " if r.ok else "FAIL") + \
                       ("" if r.reached else " (unreached)")
                print(f"  [{mark}] {backend:28s} {site.site_id}"
                      + (f"  <- {'; '.join(r.failures)}" if r.failures
                         else ""),
                      flush=True)
    return results


def bench_rows(smoke: bool = True) -> list[tuple[str, float, str]]:
    """Benchmark-suite rows: sweep duration + verdict (see
    ``benchmarks/run.py``)."""
    sites = sweep_sites()
    if smoke:
        sites = _sample_per_class(sites, 1)
    t0 = time.perf_counter()
    results = sweep(sites, verbose=False)
    us = (time.perf_counter() - t0) * 1e6
    ok = all(r.ok for r in results)
    reached = sum(1 for r in results if r.reached)
    name = "crash_sweep_smoke" if smoke else "crash_sweep_full"
    return [(name, us,
             f"pass={ok} sites={len(sites)} runs={len(results)} "
             f"reached={reached}")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.crash_sweep",
        description="Crash every TS mutation site once and gate the "
                    "recovery invariants.")
    ap.add_argument("--backends", nargs="*", default=list(DEFAULT_BACKENDS),
                    help="crashpoint-stacked backend specs to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="one site per (protection class, role) pair")
    ap.add_argument("--sample-per-class", type=int, metavar="N",
                    help="at most N sites per (protection class, role)")
    ap.add_argument("--sites", nargs="*", metavar="SUBSTR",
                    help="only sites whose ID contains any SUBSTR")
    ap.add_argument("--list", action="store_true",
                    help="print the sweep plan and exit")
    args = ap.parse_args(argv)

    for b in args.backends:
        if "crashpoint" not in b:
            print(f"backend {b!r} lacks the crashpoint wrapper",
                  file=sys.stderr)
            return 2

    sites = sweep_sites()
    if args.sites:
        sites = [s for s in sites
                 if any(sub in s.site_id for sub in args.sites)]
    if args.smoke:
        sites = _sample_per_class(sites, 1)
    elif args.sample_per_class:
        sites = _sample_per_class(sites, args.sample_per_class)
    if not sites:
        print("no sites match the sweep plan", file=sys.stderr)
        return 2

    if args.list:
        for s in sites:
            print(f"{s.site_id}  {s.path}:{s.line}  "
                  f"[{s.protection}]")
        print(f"crash-sweep plan: {len(sites)} site(s) x "
              f"{len(args.backends)} backend(s)")
        return 0

    t0 = time.perf_counter()
    results = sweep(sites, backends=tuple(args.backends))
    dt = time.perf_counter() - t0
    bad = [r for r in results if not r.ok]
    reached = sum(1 for r in results if r.reached)
    print(f"crash-sweep: {len(results)} run(s) over {len(sites)} site(s), "
          f"{reached} reached, {len(bad)} failure(s), {dt:.1f}s")
    for r in bad:
        print(f"  FAIL {r.backend} {r.site_id}: {'; '.join(r.failures)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
