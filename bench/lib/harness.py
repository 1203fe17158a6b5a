"""One run of one cell: set-up, the measured window, the check.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. The harness
finds everything else by name:

- ``bench/configs/<config>.json``: the model (via the ``file`` of the
  ``configs`` entry), whose ``family`` names its reference layer and
  FLOP count, ``bench/families/<family>.py``;
- ``bench/traffic/<traffic>.json``: the job (sequence, micro-batches,
  handlers, tuple-space backend, fault plan, checked steps, lr);
- ``bench/workloads/<cell>.json``: the cell's limits for ``correct``;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number, or None where it finds nothing to read.

The run drives the program's own entry, ``ACANCloud.run`` with
``JAXSGDProgram``, through :class:`WindowedSGD`, a subclass that takes
its weights and batches from the benchmark, records host spans around
the gradient op and the combine, and ends the job at the first commit at
or after the window's deadline. The first ``checked_steps`` steps are
set-up; the window opens at the commit that ends them. In a traced run
the readers see the traced part of the window (``TRACE_SECONDS``) as
the window.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import statistics
import threading
import time
from collections import Counter
from dataclasses import dataclass, field, replace

import jax
import numpy as np

import check
import reference as R

LIB = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(LIB)
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


# ------------------------------------------------------------------ spec
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str = ROOT

    @property
    def tokens_per_step(self) -> int:
        t = self.traffic
        return t["n_micro"] * t["micro_batch"] * t["seq"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(root, cfg_entry["file"])),
        traffic=_json(os.path.join(bench, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(bench, "workloads", name + ".json")),
        end_to_end=e2e, per_layer=per_layer, root=root)


def load_reader(metric: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- program
def model_config(cfg: dict):
    """The program's ``ModelConfig`` for ``cfg``: the repo's named
    configuration at the file's depth, with every width checked against
    the file through the parameter layout."""
    from repro.configs import get_config
    from repro.models import model as M

    base = get_config(cfg["program_config"],
                      reduced=cfg.get("program_reduced", False))
    mc = replace(base, param_dtype=cfg["torch_dtype"],
                 n_periods=cfg["num_hidden_layers"] // len(base.period))
    got = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    want = R.layout_shapes(cfg)
    if (jax.tree.structure(got) != jax.tree.structure(want)
            or any((a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(want)))):
        raise ValueError(f"{cfg['program_config']} at depth "
                         f"{cfg['num_hidden_layers']} does not match "
                         f"the layout of {cfg['name']}")
    return mc


class Batches:
    """Micro-batch ``i`` of the run, made from the seed on demand."""

    def __init__(self, seed: int, traffic: dict, vocab: int):
        self.seed, self.t, self.vocab = seed, traffic, vocab

    def batch_at(self, i: int) -> dict:
        t = self.t
        return R.make_batch(self.seed, i, t["micro_batch"], t["seq"],
                            self.vocab)


@dataclass
class Record:
    """What one run observed, on the host's ``perf_counter`` clock."""

    t_start: float
    grads: list = field(default_factory=list)      # (step, t0, s, n)
    combines: list = field(default_factory=list)   # (rnd, t0, s, committed)
    manager_starts: list = field(default_factory=list)
    captured: dict = field(default_factory=dict)   # version -> host tree
    t_open: float | None = None
    t_close: float | None = None
    open_version: int | None = None
    close_version: int | None = None
    ts_open: dict | None = None
    ts_close: dict | None = None
    #: The commit that ended the traced part of the window.
    t_trace_end: float | None = None
    trace_end_version: int | None = None
    ts_trace_end: dict | None = None


def windowed_program(base_cls):
    """``base_cls`` (``JAXSGDProgram``) driven by the benchmark."""
    from jax.profiler import TraceAnnotation
    from repro.core.space import ANY

    class WindowedSGD(base_cls):
        """The program with the benchmark's weights and batches, host spans
        around its gradient op and its combine, and a job that ends at the
        first commit at or after the window's deadline (rounds past it
        have no stages)."""

        def __init__(self, mcfg, *, params0, batches, traffic, seconds,
                     rec, tracer, seed):
            self.checked = traffic["checked_steps"]
            # More rounds than the window can hold (no step, even of the
            # tests' tiny cells, takes under a millisecond); the rounds
            # past the window's end have no stages and nothing to clean.
            super().__init__(
                mcfg, steps=self.checked + 2 + 1000 * math.ceil(seconds),
                n_micro=traffic["n_micro"],
                micro_batch=traffic["micro_batch"], seq=traffic["seq"],
                lr=traffic["lr"], seed=seed & 0x7FFFFFFF)
            self.pipe = batches
            self._params0 = params0
            self._seconds = seconds
            self._rec = rec
            #: The tuple space's ``stats`` (set once the cloud exists).
            self.ts_stats = None
            self._tracer = tracer
            self.end = self.checked if seconds <= 0 else None
            self.deadline = math.inf

        def setup(self, ts) -> None:
            self._rec.manager_starts.append(time.perf_counter())
            with self._dev_lock:
                self._dev_params = None
            if ts.try_read(("params", ANY)) is None:
                ts.put(("params", 0), self._params0)

        def stage_names(self, rnd: int) -> list[str]:
            return [] if self.end is not None and rnd >= self.end \
                else ["grad"]

        def stage_deps(self, rnd: int) -> dict:
            return super().stage_deps(rnd) if self.stage_names(rnd) else {}

        def finish_round(self, ts, rnd: int) -> None:
            if self.stage_names(rnd):
                super().finish_round(ts, rnd)

        def _grad_parts(self, ctx, tasks):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.grad"):
                items = super()._grad_parts(ctx, tasks)
            self._rec.grads.append((tasks[0].step, t0,
                                    time.perf_counter() - t0, len(items)))
            return items

        def combine(self, ts, rnd: int, stage: str, mgr) -> None:
            before = mgr.window.committed_step.get(0, -1)
            t0 = time.perf_counter()
            with TraceAnnotation("bench.combine"):
                super().combine(ts, rnd, stage, mgr)
            t1 = time.perf_counter()
            done = before < rnd == mgr.window.committed_step.get(0, -1)
            self._rec.combines.append((rnd, t0, t1 - t0, done))
            if not done:
                return
            version = rnd + 1
            if version in (1, self.checked):
                self._rec.captured[version] = ts.try_read(
                    ("params", version))[1]
            if version == self.checked and self.end is None:
                self._open(version)
                return
            tr = self._tracer
            if tr is not None and tr.running and (
                    t1 >= self._rec.t_open + TRACE_SECONDS
                    or t1 >= self.deadline):
                with TraceAnnotation("bench.window_close"):
                    pass
                tr.stop()
                rec = self._rec
                rec.t_trace_end, rec.trace_end_version = t1, version
                rec.ts_trace_end = self.ts_stats()
            if self.end is None and t1 >= self.deadline:
                self._close(version, t1)

        def _open(self, version: int) -> None:
            if self._tracer is not None:
                self._tracer.start()
            with TraceAnnotation("bench.window_open"):
                pass
            rec = self._rec
            rec.ts_open = self.ts_stats()
            rec.open_version = version
            rec.t_open = time.perf_counter()
            self.deadline = rec.t_open + self._seconds

        def _close(self, version: int, t1: float) -> None:
            rec = self._rec
            rec.t_close, rec.close_version = t1, version
            rec.ts_close = self.ts_stats()
            self.end = version

    return WindowedSGD


#: How long the traced part of a window runs: the trace ends at the first
#: commit this long after the window opens (or at the window's end). A
#: trace of the whole window is too large to write within a run's time
#: (about a million device ops every 20 s of smollm360m.sgd). In a traced
#: run every per-layer metric is read over this part alone, so that the
#: trace's writer, which runs while the job goes on, is in none of them.
TRACE_SECONDS = 10.0


class Tracer:
    """The profiler over the first part of the window, writing under
    ``bench/.cache``. The trace is written by a thread of its own, so the
    job goes on to the window's end while it is collected."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.running = False
        self._writer: threading.Thread | None = None
        shutil.rmtree(log_dir, ignore_errors=True)

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.running = True

    def stop(self) -> None:
        self.running = False
        self._writer = threading.Thread(target=jax.profiler.stop_trace,
                                        name="bench-trace-writer")
        self._writer.start()

    def join(self) -> None:
        if self._writer is not None:
            self._writer.join()


# ------------------------------------------------------------------- run
@dataclass
class Run:
    """The numbers metric readers read."""

    cell: Cell
    rec: Record
    platform: str
    device_kind: str
    chips: int
    trace: dict | None = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def seq(self) -> int:
        return self.cell.traffic["seq"]

    @property
    def setup_s(self) -> float:
        return self.rec.t_open - self.rec.t_start

    @property
    def window_s(self) -> float:
        return self.rec.t_close - self.rec.t_open

    @property
    def window_steps(self) -> int:
        return self.rec.close_version - self.rec.open_version

    @property
    def window_tokens(self) -> int:
        return self.window_steps * self.cell.tokens_per_step

    def in_window(self, step: int) -> bool:
        return self.rec.open_version <= step < self.rec.close_version

    @property
    def window_grads(self) -> list:
        return [g for g in self.rec.grads if self.in_window(g[0])]

    @property
    def window_combines(self) -> list:
        return [c for c in self.rec.combines
                if c[3] and self.in_window(c[0])]

    def median(self, xs) -> float | None:
        return statistics.median(xs) if xs else None


def phase(log, name: str, t_start: float) -> None:
    """A progress line on standard error: seconds since start and the
    process's peak resident memory so far."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    log(f"[phase] {name} at {time.perf_counter() - t_start:.1f} s, "
        f"peak RSS {rss:.2f} GiB")


def device_info() -> tuple[str, str, int]:
    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def warm(prog, params_dev, n_micro: int) -> None:
    """Compile (or load from the cache) the two programs the window
    runs, at the cell's shapes, by running each once."""
    loss, grads = prog.grad_fn(params_dev, prog.pipe.batch_at(0))
    jax.block_until_ready(prog.sgd_update(params_dev, [grads] * n_micro))


def drive(cell: Cell, seed: int, seconds: float, tracer, t_start: float,
          log=print):
    """Set up and run the cell's job through ``ACANCloud.run``: its
    checked steps, then (for ``seconds`` > 0) the window. Returns the
    record, the checked steps' losses, the initial weights (host tree)
    and the device's peak bytes."""
    from repro.core import ACANCloud, CloudConfig
    from repro.core.faults import FaultPlan
    from repro.programs.jax_sgd import JAXSGDProgram

    t, cfg = cell.traffic, cell.config
    mcfg = model_config(cfg)
    params_dev = R.make_weights(cfg, seed)
    params0 = jax.device_get(params_dev)
    phase(log, "weights", t_start)
    rec = Record(t_start=t_start)
    plan = FaultPlan(seed=seed, **t.get("fault_plan", {}))
    cloud_cfg = CloudConfig(n_handlers=t["n_handlers"],
                            handler_batch=t["handler_batch"],
                            ts_backend=t["ts_backend"], fault_plan=plan,
                            seed=seed)
    prog = windowed_program(JAXSGDProgram)(
        mcfg, params0=params0, batches=Batches(seed, t, cfg["vocab_size"]),
        traffic=t, seconds=seconds, rec=rec, tracer=tracer, seed=seed)
    warm(prog, params_dev, t["n_micro"])
    del params_dev
    phase(log, "warm", t_start)
    cloud = ACANCloud(cloud_cfg, program=prog)
    prog.ts_stats = cloud.ts.stats
    try:
        res = cloud.run()
    finally:
        close = getattr(cloud.ts.backend, "close", None)
        if close is not None:
            close()
    if not res.finished:
        raise RuntimeError("the job stopped at its wall limit")
    if seconds > 0 and rec.t_close is None:
        raise RuntimeError("the window never closed")
    mem = peak_bytes()
    phase(log, "job", t_start)
    losses = [lo for s, lo in sorted(res.loss_history)
              if s < t["checked_steps"]]
    prog._dev_params = None
    del cloud, prog, res
    gc.collect()
    return rec, losses, params0, mem


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=print) -> dict:
    """One run of ``cell``; returns the result line's object."""
    t = cell.traffic
    platform, kind, count = device_info()
    tracer = Tracer(os.path.join(CACHE, "trace")) if trace else None
    rec, losses, params0, mem = drive(cell, seed, seconds, tracer, t_start,
                                      log)
    t0 = time.perf_counter()
    ref = reference_run(cell, seed, params0)
    ref_s = time.perf_counter() - t0
    phase(log, "reference", t_start)
    numbers = check.numbers(t["lr"], params0, program_run(rec, losses), ref)
    checks = {k: {"value": numbers[k], "limit": cell.limits["checks"][k]}
              for k in cell.limits["checks"]}
    run = Run(cell, rec, platform, kind, count)
    if seconds > 0 and "fault_plan" in t:
        checks["missing_kills"] = {
            "value": missing_kills(run, t["fault_plan"]["interval"]),
            "limit": 1}
    failed = sum(c["value"] > c["limit"] for c in checks.values())
    log(f"[check] reference {ref_s:.1f} s; leaves compared "
        f"{numbers['leaves_kept']} of {numbers['leaves']}")
    if seconds > 0:
        log(window_line(run, "window"))
    out = {"correct": failed == 0,
           "attempted": rec.close_version or t["checked_steps"],
           "failed": failed, "metrics": {},
           "device": {"platform": platform, "kind": kind, "count": count,
                      "memory_peak_bytes": mem}}
    if trace:
        from devtrace import read_xplane, summarize
        run = Run(cell, replace(rec, t_close=rec.t_trace_end,
                                close_version=rec.trace_end_version,
                                ts_close=rec.ts_trace_end),
                  platform, kind, count)
        log(window_line(run, "traced part"))
        tracer.join()
        run.trace = summarize(read_xplane(tracer.log_dir))
        if run.trace is not None:
            out["device"]["busy_s"] = run.trace["busy_s"]
            out["device"]["window_s"] = run.trace["window_s"]
            out["breakdown"] = {"device_ops": run.trace["top_ops"],
                                "idle_gaps": run.trace["idle_gaps"]}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = load_reader(m["name"], cell.root)(run)
        if v is not None:
            out["metrics"][m["name"]] = {"value": float(v),
                                         "unit": m["unit"]}
    out["checks"] = checks
    return out


def window_line(run: Run, what: str) -> str:
    """A line on the window (or its traced part): its steps, its rate,
    and how many of its steps ran each number of gradient calls."""
    runs_of = Counter()
    for g in run.window_grads:
        runs_of[g[0]] += g[3]
    steps_by_runs = Counter(runs_of.values())
    return (f"[{what}] {run.window_steps} steps in {run.window_s:.3f} s, "
            f"{run.window_tokens / run.window_s:.1f} tokens/s; steps by "
            f"grad runs {dict(sorted(steps_by_runs.items()))}")


def missing_kills(run: Run, interval: float) -> int:
    """Firings the schedule implies in the window, less the Manager
    revivals seen in it (each firing kills the Manager)."""
    rec = run.rec
    seen = sum(rec.t_open < s <= rec.t_close for s in rec.manager_starts)
    return max(0, int(run.window_s // interval) - seen)


def program_run(rec: Record, losses: list) -> check.Trajectory:
    versions = sorted(rec.captured)
    return check.Trajectory(losses, rec.captured[1],
                            rec.captured[versions[-1]])


def reference_run(cell: Cell, seed: int, params0, *, ref=None,
                  micro: int | None = None) -> check.Trajectory:
    """The reference's (``ref``, float32 by default) first checked steps
    from ``params0``, over the first ``micro`` micro-batches of each
    step (all by default)."""
    t = cell.traffic
    ref = ref or R.Reference(cell.config, t["lr"])
    batches = Batches(seed, t, cell.config["vocab_size"])
    n = t["n_micro"] if micro is None else micro
    state = jax.device_put(params0)
    losses, p1, g0 = [], None, None
    for step in range(t["checked_steps"]):
        loss, g, state = ref.step(state, [
            batches.batch_at(step * t["n_micro"] + m) for m in range(n)])
        losses.append(loss)
        if step == 0:
            g0, p1 = jax.device_get(g), jax.device_get(state)
        del g
    return check.Trajectory(losses, p1, jax.device_get(state), g0)
