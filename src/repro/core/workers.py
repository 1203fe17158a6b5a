"""Out-of-process handler fleet (PR 10).

One *worker* is a normal :class:`~repro.core.handler.Handler` — same
event loop, same capability/store/fence/autotune behaviour — running in
its own interpreter over a :class:`~repro.core.space.RemoteBackend`
connection to the cloud's tuple-space server. Nothing about the
ACAN protocol changes; only the thread boundary became a process
boundary, which is what takes the emulated compute off the cloud
process's GIL.

Three pieces:

- :func:`main` — the ``python -m repro.core.workers`` entrypoint: one
  Handler over one RemoteBackend, built entirely from flags (the op
  registry is always the built-in one — custom-registry programs cannot
  cross a process boundary and keep a thread fleet). SIGTERM = clean
  stop; SIGKILL = the crash the fault plane injects.
- :class:`HandlerProcess` — the ``subprocess.Popen`` wrapper that
  duck-types the slice of ``threading.Thread`` the
  :class:`~repro.core.faults.MonitorDaemon` supervises (``is_alive``/
  ``join``/``name``), so process revival IS thread revival to the
  daemon: a dead worker is noticed by the same poll and respawned by the
  same ``make_handler_thread(i)`` factory.
- :class:`ProcessCrashEvent` — the crash-axis shim: the daemon fires
  handler crashes by calling ``event.set()``; for a process fleet that
  delivers SIGKILL to the current worker — a *real* kill, taken tasks
  genuinely lost mid-flight, exactly the failure the
  timeout/retransmission discipline must absorb.

Speed re-draws are applied at (re)spawn time from the cloud's
``SpeedBox`` — a live worker keeps its spawn-time speed until the fault
plane kills it (documented divergence from the thread fleet, where
re-draws apply immediately).
"""

from __future__ import annotations

import signal
import subprocess
import sys
import threading

from repro.core.handler import Handler, HandlerCrash, HandlerTenant, SpeedBox
from repro.core.space import TupleSpace, as_scoped
from repro.core.space.remote import RemoteBackend

__all__ = ["HandlerProcess", "ProcessCrashEvent", "main", "spawn_worker"]


class HandlerProcess:
    """Popen wrapper exposing the Thread surface MonitorDaemon drives."""

    def __init__(self, proc: subprocess.Popen, name: str) -> None:
        self.proc = proc
        self.name = name

    def is_alive(self) -> bool:
        return self.proc.poll() is None

    def join(self, timeout: float | None = None) -> None:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            pass

    def terminate(self) -> None:
        """Clean stop (SIGTERM): the worker stops its handler and exits."""
        if self.proc.poll() is None:
            self.proc.terminate()

    def kill_hard(self) -> None:
        """SIGKILL — the injected crash. No cleanup runs in the worker:
        whatever tasks it had taken die with it."""
        if self.proc.poll() is None:
            self.proc.kill()


class ProcessCrashEvent:
    """Duck-types the ``threading.Event`` crash channel for one fleet
    slot. The daemon's fault firing calls ``set()``; here that means
    SIGKILL-ing whichever worker currently holds the slot (``proc`` is
    re-pointed by the cloud on every respawn). ``is_set``/``clear`` keep
    the Event surface for anything that polls."""

    def __init__(self) -> None:
        self.proc: HandlerProcess | None = None
        self.kills = 0

    def set(self) -> None:
        p = self.proc
        if p is not None and p.is_alive():
            self.kills += 1
            p.kill_hard()

    def clear(self) -> None:
        pass

    def is_set(self) -> bool:
        return False


def spawn_worker(addr: tuple | str, name: str, *, speed: float = 1.0,
                 capacity: float = 256.0, lr: float = 0.01,
                 time_scale: float = 2e-6, batch_size: int = 16,
                 scheduling: str = "event", compute_mode: str = "sleep",
                 autotune: bool = False, defer_ratio: float = 3.0,
                 namespaces: list[str] | None = None,
                 tenant_caps: dict | None = None) -> HandlerProcess:
    """Spawn one worker process connected to the server at ``addr``."""
    if not isinstance(addr, str):
        addr = f"{addr[0]}:{addr[1]}"
    import os

    import repro
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    # Host-only child: it must never claim the accelerator that the
    # cloud's process holds.
    env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, "-m", "repro.core.workers",
            "--addr", addr, "--name", name, "--speed", str(speed),
            "--capacity", str(capacity), "--lr", str(lr),
            "--time-scale", str(time_scale),
            "--batch-size", str(batch_size),
            "--scheduling", scheduling, "--compute-mode", compute_mode,
            "--defer-ratio", str(defer_ratio)]
    if autotune:
        argv.append("--autotune")
    if namespaces:
        argv += ["--namespaces", ",".join(namespaces)]
    if tenant_caps:
        argv += ["--tenant-caps",
                 ",".join(f"{ns}={cap}" for ns, cap in tenant_caps.items())]
    proc = subprocess.Popen(argv, env=env)
    return HandlerProcess(proc, name)


def _parse_caps(spec: str) -> dict[str, int]:
    out: dict[str, int] = {}
    for part in spec.split(","):
        if part:
            ns, _, cap = part.partition("=")
            out[ns] = int(cap)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="ACAN out-of-process handler worker (PR 10)")
    ap.add_argument("--addr", required=True, help="TS server host:port")
    ap.add_argument("--name", default="hproc")
    ap.add_argument("--speed", type=float, default=1.0)
    ap.add_argument("--capacity", type=float, default=256.0)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--time-scale", type=float, default=2e-6)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--scheduling", default="event")
    ap.add_argument("--compute-mode", default="sleep")
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--defer-ratio", type=float, default=3.0)
    ap.add_argument("--namespaces", default="",
                    help="comma-separated tenant namespaces (empty = "
                         "single-tenant fast path)")
    ap.add_argument("--tenant-caps", default="",
                    help="ns=cap,... per-tenant keep caps")
    args = ap.parse_args(argv)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())

    backend = RemoteBackend(addr=args.addr)
    ts = TupleSpace(backend=backend)

    tenants = None
    if args.namespaces:
        caps = _parse_caps(args.tenant_caps)
        # registry=None -> the built-in op registry (MLP + MoE): worker
        # processes can only run globally registered ops.
        tenants = {ns: HandlerTenant(as_scoped(ts, ns), None,
                                     max_tasks=caps.get(ns))
                   for ns in args.namespaces.split(",")}

    h = Handler(ts=ts, name=args.name, speed=SpeedBox(args.speed),
                capacity=args.capacity, lr=args.lr,
                time_scale=args.time_scale, batch_size=args.batch_size,
                scheduling=args.scheduling, registry=None,
                tenants=tenants, autotune=args.autotune,
                defer_ratio=args.defer_ratio,
                compute_mode=args.compute_mode, stop_event=stop)
    # The handler runs on the main thread: CPython delivers SIGTERM to
    # the main thread between bytecodes, the handler above sets `stop`,
    # and the event loop's bounded take_batch timeout observes it.
    try:
        h.run()
    except HandlerCrash:
        pass
    backend.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
