"""Smoke run of the system's main path on TPU chips.

    python chip_smoke.py              # one chip: JAX-SGD + kernel phases
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

One chip (the default):

1. **JAX-SGD through the control plane.** ``ACANCloud`` runs
   ``JAXSGDProgram`` on published-width smollm-360m (bf16, seq 2048,
   micro-batch 4, 4 micro-batches per step) on the thread fleet, with
   injected handler crashes so that the Manager re-issues tasks — once
   over the ``sharded`` tuple space and once over ``remote+sharded``
   (a host-only server process). Each run must finish (not stop at its
   wall limit), record one loss per step, and match a plain reference
   in its losses and its last committed params: the program's own
   jitted ``grad_fn`` and ``sgd_update`` over the same
   ``batch_at(step * M + m)`` batches in a loop, with no control plane.
   The reference runs first and warms the jit, so no cloud task waits on
   a compile.
2. **Kernels.** ``tile_matmul``, ``flash_attention`` and ``ssd_scan``
   run once each, compiled (their HLO must hold a ``tpu_custom_call``),
   at the widths of ``repro.kernels.cases.main_path_cases`` and against
   their pure-jnp references.

Four chips (``--chips 4``): ``repro.launch.train.train`` runs full-width
smollm-360m on a 2x2 ("data", "model") mesh of the host's chips, then the
same steps on a one-chip mesh in the same process; the losses and the
param updates must agree, and the params and optimizer state must be
spread over all four chips.

The weights and data are random, made from ``--seed``. The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every phase passed; without a TPU, or without the repo's
``src/`` next to this file, the script exits non-zero and prints no such
line."""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

#: Cloud losses and final params vs the plain reference, relative. Both
#: run the same jitted functions on the same bits (params and grads cross
#: the space as exact host copies), so any gap beyond float noise is a
#: control-plane fault.
SGD_RTOL = 1e-5
#: Four-chip vs one-chip train losses, relative. The sharded step reduces
#: in another order; the chip showed gaps near 1.5e-5.
MESH_LOSS_RTOL = 1e-3
#: Four-chip vs one-chip param updates: ||p_many - p_one|| / ||p_one -
#: p_init|| over every param. Losses at random init barely move in a few
#: steps, so a step that does not update (gap 1) or sees half the batch
#: (0.83 with bf16 params on four virtual CPU devices) shows here, not in
#: the losses. A sound step's gap comes from reduction order flipping
#: bf16 roundings and Adam's near-sign first updates: 0.036 there.
MESH_UPDATE_RTOL = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------ JAX-SGD phase
def _host_f32(tree) -> list:
    """``tree``'s leaves as float32 host arrays, in tree order."""
    import jax
    import numpy as np
    return [np.asarray(jax.device_get(x), np.float32)
            for x in jax.tree.leaves(tree)]


def max_rel_gap(got, want) -> float:
    """The largest leaf's max|got - want| / max|want|."""
    import numpy as np
    g, w = _host_f32(got), _host_f32(want)
    if len(g) != len(w):
        return float("inf")
    return max((float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
                for a, b in zip(g, w)), default=float("inf"))


def reference_run(prog, steps: int) -> dict:
    """The program's grad and update in a plain loop: no tuple space, no
    Manager, no handlers. Returns the losses, the final params (host
    arrays), and the first and a steady grad call's seconds."""
    import jax
    import numpy as np

    from repro.models import model as M

    params = jax.device_put(jax.device_get(
        M.init_params(prog.cfg, jax.random.PRNGKey(prog.seed))))
    losses, first_s, steady_s = [], 0.0, 0.0
    for step in range(steps):
        outs = []
        for m in range(prog.n_micro):
            t0 = time.perf_counter()
            loss, grads = prog.grad_fn(
                params, prog.pipe.batch_at(step * prog.n_micro + m))
            loss = float(loss)                      # waits for the device
            dt = time.perf_counter() - t0
            if step == 0 and m == 0:
                first_s = dt
            elif step == 0 and m == 1:
                steady_s = dt
            outs.append((loss, grads))
        losses.append(float(np.mean([lo for lo, _ in outs])))
        params = prog.sgd_update(params, [g for _, g in outs])
    return {"losses": losses, "params": jax.device_get(params),
            "first_call_s": first_s, "steady_call_s": steady_s}


def sgd_phase(cfg, *, steps: int, n_micro: int, micro_batch: int, seq: int,
              n_handlers: int, crash_prob: float, backends: list[str],
              seed: int, wall_limit: float) -> dict:
    """Run the control-plane JAX-SGD job once per backend and hold each
    run's losses and final committed params to the reference. Returns a
    report; ``report["ok"]`` says whether every check passed."""
    from repro.core import ACANCloud, CloudConfig
    from repro.programs.jax_sgd import JAXSGDProgram

    # One program object for the reference and every run: one jit, one
    # compile. setup() re-seeds each run's fresh tuple space.
    prog = JAXSGDProgram(cfg, steps=steps, n_micro=n_micro,
                         micro_batch=micro_batch, seq=seq,
                         handler_crash_prob=crash_prob, seed=seed)
    ref = reference_run(prog, steps)
    log(f"[sgd reference] losses {ref['losses']}")
    log(f"[sgd reference] first grad call {ref['first_call_s']:.3f} s "
        f"(compile + run), steady call {ref['steady_call_s']:.3f} s")
    report = {"ok": True, "reference": ref["losses"],
              "first_call_s": ref["first_call_s"],
              "steady_call_s": ref["steady_call_s"], "runs": {}}
    for backend in backends:
        crashes0 = prog.crashes
        cloud = ACANCloud(CloudConfig(
            n_handlers=n_handlers, handler_batch=1, wall_limit=wall_limit,
            ts_backend=backend, seed=seed), program=prog)
        try:
            res = cloud.run()
            final = cloud.ts.try_read(("params", steps))
        finally:
            close = getattr(cloud.ts.backend, "close", None)
            if close is not None:
                close()
        losses = [lo for _, lo in res.loss_history]
        gap = max((abs(a - b) / abs(b)
                   for a, b in zip(losses, ref["losses"])),
                  default=float("inf"))
        param_gap = (float("inf") if final is None
                     else max_rel_gap(final[1], ref["params"]))
        run = {"finished": res.finished, "losses": losses,
               "max_rel_gap": gap, "param_rel_gap": param_gap,
               "reissues": res.reissues, "crashes": prog.crashes - crashes0,
               "wallclock_s": res.wallclock}
        run["ok"] = (res.finished and len(losses) == steps
                     and gap <= SGD_RTOL and param_gap <= SGD_RTOL
                     and run["reissues"] >= 1)
        report["runs"][backend] = run
        report["ok"] &= run["ok"]
        log(f"[sgd {backend}] finished={res.finished} losses {losses}")
        log(f"[sgd {backend}] max |loss - ref| / ref = {gap:.3e}, final "
            f"params ('params', {steps}) max|p - ref| / max|ref| = "
            f"{param_gap:.3e} (limit {SGD_RTOL:g}); re-issues "
            f"{run['reissues']}, injected crashes {run['crashes']}, "
            f"wall {res.wallclock:.1f} s -> {'ok' if run['ok'] else 'FAIL'}")
    return report


# ------------------------------------------------------------ kernel phase
def kernel_phase(cases, seed: int) -> list[dict]:
    """Run each case once, compiled, against its reference. ``mosaic``
    says whether the compiled HLO holds a Mosaic ``tpu_custom_call``."""
    import jax

    from repro.kernels.cases import rel_err

    out = []
    for case in cases:
        args = [jax.device_put(a) for a in case.make_args(seed)]
        t0 = time.perf_counter()
        compiled = jax.jit(case.fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        got = jax.block_until_ready(compiled(*args))
        want = jax.block_until_ready(jax.jit(case.ref)(*args))
        err = rel_err(got, want)
        mosaic = "tpu_custom_call" in compiled.as_text()
        out.append({"name": case.name, "rel_err": err, "tol": case.tol,
                    "mosaic": mosaic, "compile_s": compile_s,
                    "ok": err <= case.tol})
        log(f"[kernel] {case.name}: tpu_custom_call={mosaic} "
            f"max|out-ref|/max|ref| = {err:.3e} (limit {case.tol:g}), "
            f"compile {compile_s:.2f} s")
    return out


# -------------------------------------------------------------- mesh phase
def device_bytes(tree) -> dict[int, int]:
    """Bytes each device holds of ``tree``'s arrays."""
    import jax
    out: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(out.items()))


def update_gap(got: list, want: list, init: list) -> float:
    """||got - want|| / ||want - init|| over every leaf (host float32
    arrays): how far one run's param update strays from another's."""
    import numpy as np
    num = sum(float(np.sum((g - w).astype(np.float64) ** 2))
              for g, w in zip(got, want))
    den = sum(float(np.sum((w - i).astype(np.float64) ** 2))
              for w, i in zip(want, init))
    return (num / den) ** 0.5 if den > 0 else float("inf")


def mesh_phase(arch: str, *, reduced: bool, steps: int, batch: int, seq: int,
               model_axis: int, n_devices: int, seed: int) -> dict:
    """``train`` on an ``n_devices`` mesh, then on a one-device mesh; the
    losses and the param updates must agree."""
    import jax

    from repro.configs import get_config
    from repro.distributed.elastic import plan_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train
    from repro.models import model as M

    run_root = os.path.join(HERE, "runs", "chip_smoke")
    shutil.rmtree(run_root, ignore_errors=True)
    kw = dict(reduced=reduced, steps=steps, batch=batch, seq=seq,
              ckpt_every=0, resume=False, seed=seed,
              log=lambda m: log(f"[mesh]   {m}"))
    mesh = make_host_mesh(model=model_axis)
    log(f"[mesh] {n_devices}-device mesh {dict(mesh.shape)}")
    many = train(arch, mesh=mesh, ckpt_dir=os.path.join(run_root, "many"),
                 **kw)
    param_total = sum(x.nbytes for x in jax.tree.leaves(many["params"]))
    per_dev = {"params": device_bytes(many["params"]),
               "opt_state": device_bytes(many["opt_state"])}
    log(f"[mesh] param bytes {param_total}; per device: params "
        f"{per_dev['params']}, optimizer state {per_dev['opt_state']}")
    spread = (len(per_dev["params"]) == n_devices
              and all(0 < b < param_total
                      for b in per_dev["params"].values()))
    many_params = _host_f32(many.pop("params"))
    del many["opt_state"]
    log("[mesh] one-device mesh")
    one = train(arch, mesh=plan_mesh(jax.devices()[:1], 1),
                ckpt_dir=os.path.join(run_root, "one"), **kw)
    one_params = _host_f32(one.pop("params"))
    del one["opt_state"]
    cfg = get_config(arch, reduced=reduced)
    init = _host_f32(jax.jit(lambda k: M.init_params(cfg, k))(
        jax.random.PRNGKey(seed)))
    gap = max((abs(a - b) / abs(b)
               for a, b in zip(many["losses"], one["losses"])),
              default=float("inf"))
    ugap = update_gap(many_params, one_params, init)
    ok = (spread and len(many["losses"]) == len(one["losses"]) == steps
          and gap <= MESH_LOSS_RTOL and ugap <= MESH_UPDATE_RTOL)
    log(f"[mesh] losses {n_devices} devices {many['losses']}")
    log(f"[mesh] losses 1 device  {one['losses']}")
    log(f"[mesh] max rel loss gap {gap:.3e} (limit {MESH_LOSS_RTOL:g}); "
        f"update gap ||p_{n_devices} - p_1|| / ||p_1 - p_init|| = "
        f"{ugap:.3e} (limit {MESH_UPDATE_RTOL:g}); params on every device, "
        f"none whole: {spread} -> {'ok' if ok else 'FAIL'}")
    return {"ok": ok, "losses": many["losses"], "one_device": one["losses"],
            "max_rel_gap": gap, "update_gap": ugap,
            "per_device_bytes": per_dev, "param_bytes": param_total}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: JAX-SGD + kernel phases; 4: the mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} libtpu={libtpu} "
        f"compile_cache={cache}")
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1

    ok = True
    if args.chips == 4:
        ok = mesh_phase("smollm_360m", reduced=False, steps=3, batch=8,
                        seq=2048, model_axis=2, n_devices=4,
                        seed=args.seed)["ok"]
    else:
        from repro.kernels.cases import main_path_cases
        # 3 handlers x (2.9 GB grads + temporaries) + 0.73 GB params
        # + a 4.4 GB combine fit one 16 GB v5e with room to spare
        # (tests/test_tpu_compile.py compiles the grad step).
        sgd = sgd_phase(get_config("smollm_360m"), steps=3, n_micro=4,
                        micro_batch=4, seq=2048, n_handlers=3,
                        crash_prob=0.25, backends=["sharded",
                                                   "remote+sharded"],
                        seed=args.seed, wall_limit=600.0)
        log(f"[sgd] peak_bytes_in_use {peak_bytes(dev)}")
        kernels = kernel_phase(main_path_cases(), args.seed)
        ok = sgd["ok"] and all(k["ok"] and k["mosaic"] for k in kernels)
        log(f"[device] peak_bytes_in_use {peak_bytes(dev)}")
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        rc = 1
    sys.exit(rc)
