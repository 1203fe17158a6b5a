"""Production training runner: journal + checkpoint + resume, over any
assigned arch.

The CPU tests and examples run reduced configs; on TPU chips the same
runner drives the full configs. Params, optimizer state and batches are
placed with the shardings ``launch/steps.build_cell`` computes for the
mesh, and the step is jitted with them — the same source of truth the
dry-run compiles. A step is pure in its inputs and donates the old
params/state, so it is never re-run: a failed step fails the run.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
        --reduced --steps 20 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import os
import time
from dataclasses import replace

import jax

from repro.checkpoint.checkpoint import load_checkpoint, save_checkpoint
from repro.checkpoint.journal import TrainJournal
from repro.configs.base import SHAPES, get_config
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_cell
from repro.models import model as M
from repro.optim.optimizer import OptConfig, init_opt_state


def train(arch: str, *, reduced: bool = True, steps: int = 20, batch: int = 8,
          seq: int = 64, ckpt_dir: str = "runs", ckpt_every: int = 10,
          model_axis: int = 1, mesh=None, resume: bool = True, seed: int = 0,
          data_mode: str = "cyclic", opt: OptConfig | None = None,
          log=print) -> dict:
    """Train ``steps`` steps on ``mesh`` (default: every device of this
    host, ``model_axis`` wide on "model")."""
    cfg = get_config(arch, reduced=reduced)
    opt = opt or OptConfig(peak_lr=1e-3, warmup_steps=5, decay_steps=steps,
                           weight_decay=0.0)
    if mesh is None:
        mesh = make_host_mesh(model=model_axis)
    cell = build_cell(cfg, replace(SHAPES["train_4k"], seq=seq, batch=batch),
                      mesh, opt)
    params_sh, opt_sh, batch_sh = cell.in_shardings

    run_dir = os.path.join(ckpt_dir, f"{arch}{'_reduced' if reduced else ''}")
    os.makedirs(run_dir, exist_ok=True)
    journal = TrainJournal(os.path.join(run_dir, "journal.jsonl"))

    pipe = TokenPipeline(PipelineConfig(
        vocab=cfg.vocab, batch=batch, seq=seq, seed=seed, mode=data_mode,
        n_codebooks=cfg.n_codebooks if cfg.frontend == "codebooks" else 0,
        embed_dim=cfg.d_model if cfg.frontend == "embeds" else 0))

    params = jax.jit(lambda k: M.init_params(cfg, k),
                     out_shardings=params_sh)(jax.random.PRNGKey(seed))
    opt_state = jax.jit(lambda p: init_opt_state(p, opt),
                        out_shardings=opt_sh)(params)
    start_step = 0

    # --- recovery: journal replay → (step cursor, checkpoint) -------------
    last = journal.latest() if resume else None
    if last is not None:
        ck = last.get("ckpt")
        if ck and os.path.exists(os.path.join(ck, "manifest.json")):
            _, params, opt_state = load_checkpoint(
                ck, params, opt_state,
                shardings={"params": params_sh, "opt": opt_sh})
        start_step = int(last["step"]) + 1
        log(f"[recover] resume at step {start_step} "
            f"(journal: {last['step']}, ckpt: {ck})")

    jitted = jax.jit(cell.step, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings,
                     donate_argnums=cell.donate_argnums)
    losses = []
    t0 = time.time()
    with shd.use_rules(cell.rules, mesh):
        for step in range(start_step, steps):
            batch_j = jax.device_put(pipe.batch_at(step), batch_sh)
            params, opt_state, metrics = jitted(params, opt_state, batch_j)
            loss = float(metrics["loss"])
            losses.append(loss)
            ckpt = None
            if ckpt_every and (step + 1) % ckpt_every == 0:
                ckpt = save_checkpoint(
                    os.path.join(run_dir, f"ckpt_{step}"), step, params,
                    opt_state)
            journal.append({"step": step, "loss": loss, "ckpt": ckpt,
                            "data_cursor": step})
            log(f"step {step:4d} loss {loss:.4f} "
                f"lr {float(metrics['lr']):.2e} "
                f"gnorm {float(metrics['grad_norm']):.3f}")
    return {"losses": losses, "params": params, "opt_state": opt_state,
            "wall": time.time() - t0, "start_step": start_step}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--no-resume", dest="resume", action="store_false")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(args.arch, reduced=args.reduced, steps=args.steps,
                batch=args.batch, seq=args.seq, model_axis=args.model_axis,
                ckpt_every=args.ckpt_every, resume=args.resume)
    print(f"done: {len(out['losses'])} steps in {out['wall']:.1f}s; "
          f"first loss {out['losses'][0]:.4f} → last {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
