"""ACAN-over-JAX as a :class:`WorkloadProgram` — real JAX training on the
generic Manager/Handler plane.

Data-parallel SGD where every microbatch gradient is one ACAN task:

- each round is one SGD step; the single ``grad`` stage holds one
  ``jaxgrad`` task per microbatch (``out_lo`` = microbatch index);
- the op computes ``grad(loss)`` with a jitted step on the
  *deterministic* microbatch ``batch_at(step·M + micro)`` and publishes
  the gradient tree keyed by content — duplicate execution rewrites
  identical values (bitwise: same jit, same data, same params);
- the combine averages exactly one gradient per micro key, applies the
  update, and commits the new param version through the §5.4 sliding
  window (handlers read params by version — a handler that crashed
  mid-task never corrupts anything; its task simply re-appears).

This replaces the pre-PR-3 ``ts_exec/step_runner.py`` control loop,
which re-implemented its own barrier/timeout/commit discipline: the
Manager's pouch barrier, GSS deadline, straggler re-issue, and cursor
checkpointing now come from the shared plane.

The op closes over the jitted grad function and the data pipeline, so it
registers in a **program-private** registry chained to the global one —
two concurrent programs never collide.

Everything the program puts into the space is host (numpy) data: the
space may live in a server process that must never touch the device
(unpickling a ``jax.Array`` would ``device_put`` there). Only the
process running the op and the combine moves arrays to the device: each
handler uploads a param version once (``_device_params``), and the
combine averages and applies the update with one jitted call.

Each move between host and device is a span of its own (``acan.jax_sgd.*``,
:mod:`repro.core.trace`) that carries the ``bytes`` it moved.

TS data-plane keys: ``("params", step)`` (current param tree),
``("gpart", step, micro)`` ((loss, grad-tree) per microbatch) — scoped
to the ``jax_sgd`` namespace when co-resident with other programs on a
multi-tenant cloud (the op's ``ctx.ts`` is then that tenant's
:class:`~repro.core.space.ScopedSpace`, so a handler fleet can serve
JAX training next to the numpy programs on one space).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import ExecContext, PreconditionUnmet
from repro.core.program import (FINISH_STAGE, OpRegistry, OpSpec,
                                StageEffect, WorkloadProgram, deletes,
                                ensure_builtin_ops, reads, record_loss,
                                writes)
from repro.core.space import ANY
from repro.core.space.schema import KeySchema, int_field
from repro.core.tasks import TaskDesc
from repro.core.trace import span
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.models import model as M

JAXGRAD = "jaxgrad"


def nbytes(tree) -> int:
    """The summed ``nbytes`` of ``tree``'s leaves."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


# Declared data-plane key protocol (PR 6). ("params", steps) — the final
# committed version — intentionally survives shutdown: persistent.
KEY_SCHEMAS: tuple[KeySchema, ...] = (
    KeySchema(subject="params", fields=(int_field("step"),),
              producers=frozenset({"manager"}),
              consumers=frozenset({"manager", "executor"}),
              deleters=frozenset({"manager"}), lifecycle="persistent",
              description="committed param tree at version step"),
    KeySchema(subject="gpart", fields=(int_field("step"),
                                       int_field("micro")),
              producers=frozenset({"executor"}),
              consumers=frozenset({"manager"}),
              deleters=frozenset({"manager", "handler"}),
              lifecycle="round_scoped",
              description="(loss, grad tree) per microbatch"),
)


class JAXSGDProgram(WorkloadProgram):
    """One microbatch-gradient task per handler trip; SGD combine."""

    name = "jax_sgd"

    def __init__(self, cfg: "M.ModelConfig", steps: int, n_micro: int = 4,
                 micro_batch: int = 2, seq: int = 64, lr: float = 0.05,
                 handler_crash_prob: float = 0.0, data_mode: str = "cyclic",
                 seed: int = 0) -> None:
        self.cfg = cfg
        self.steps = steps
        self.n_micro = n_micro
        self.lr = lr
        self.seed = seed
        self.handler_crash_prob = handler_crash_prob
        self.crashes = 0
        self._crash_rng = np.random.default_rng(seed + 7)
        # The op runs on every Handler thread; Generator is not
        # thread-safe and the counter would undercount unsynchronized.
        self._crash_lock = threading.Lock()
        self.pipe = TokenPipeline(PipelineConfig(
            vocab=cfg.vocab, batch=micro_batch, seq=seq,
            seed=seed, mode=data_mode,
            n_codebooks=cfg.n_codebooks if cfg.frontend == "codebooks" else 0,
            embed_dim=cfg.d_model if cfg.frontend == "embeds" else 0))

        def loss_fn(params, batch):
            return M.train_loss(params, cfg, batch)[0]

        #: ``(params, batch) -> (loss, grads)`` — what every op runs.
        self.grad_fn = jax.jit(jax.value_and_grad(loss_fn))

        def sgd_update(params, grads_list):
            def leaf(p, *gs):
                g = sum(x.astype(jnp.float32) for x in gs) / len(gs)
                return (p.astype(jnp.float32) - lr * g).astype(p.dtype)
            return jax.tree.map(leaf, params, *grads_list)

        #: ``(params, [grads per micro]) -> params``: mean-of-grads SGD,
        #: accumulated in float32 — what every combine runs.
        self.sgd_update = jax.jit(sgd_update)
        # (version, device copy) of the last param version an op read.
        self._dev_params: tuple[int, object] | None = None
        self._dev_lock = threading.Lock()
        self.registry = OpRegistry(parent=ensure_builtin_ops())
        self.registry.register(OpSpec(
            JAXGRAD, self._grad_parts,
            cost_fn=lambda t: 1.0,          # uniform, indivisible
            split_fn=lambda t: [t]))

    # ---------------------------------------------------------------- setup
    def setup(self, ts) -> None:
        with self._dev_lock:
            self._dev_params = None
        if ts.try_read(("params", ANY)) is None:
            params = M.init_params(self.cfg, jax.random.PRNGKey(self.seed))
            ts.put(("params", 0), jax.device_get(params))

    def _device_params(self, version: int, host_params):
        """The device copy of param ``version``, uploaded once per version
        for all handler threads (a version is immutable once committed).
        The span holds the wait for the lock and, for a new version, the
        upload's dispatch; the grad's compute span waits for the rest."""
        with span("acan.jax_sgd.grad.params_upload",
                  version=version) as sp, self._dev_lock:
            if self._dev_params is None or self._dev_params[0] != version:
                self._dev_params = None          # free the old copy first
                self._dev_params = (version, jax.device_put(host_params))
                sp.set_metadata(uploaded=1, bytes=nbytes(host_params))
            else:
                sp.set_metadata(uploaded=0)
            return self._dev_params[1]

    # ---------------------------------------------------------- stage graph
    def n_rounds(self) -> int:
        return self.steps

    def stage_names(self, rnd: int) -> list[str]:
        return ["grad"]

    def stage_deps(self, rnd: int) -> dict[str, list]:
        # The true dependency is a pure chain: the grad op reads
        # ("params", step), which only exists once the previous round's
        # combine committed it — there is nothing for a frontier
        # scheduler to overlap (synchronous SGD), and declaring the edge
        # keeps that explicit rather than an accident of the default.
        return {"grad": [("grad", -1)]}

    def stage_tasks(self, ts, rnd: int, stage: str) -> list[TaskDesc]:
        return [TaskDesc(JAXGRAD, 0, rnd, rnd, 0, 0, m, m + 1)
                for m in range(self.n_micro)]

    # ------------------------------------------------------------------- op
    def _grad_parts(self, ctx: ExecContext, tasks: list[TaskDesc]):
        hit = ctx.ts.try_read(("params", ANY))
        if hit is None:
            raise PreconditionUnmet("params")
        params = None
        items = []
        for t in tasks:
            with self._crash_lock:
                crash = self._crash_rng.random() < self.handler_crash_prob
                if crash:
                    self.crashes += 1
            if crash:
                # Emulated crash while holding the task: the group is
                # discarded with nothing written, and the Manager's
                # timeout re-issues it (paper §5.1).
                raise PreconditionUnmet("injected handler crash")
            micro = t.out_lo
            with span("acan.jax_sgd.grad", step=t.step, micro=micro):
                if params is None:
                    params = self._device_params(hit[0][1], hit[1])
                batch = self.pipe.batch_at(t.step * self.n_micro + micro)
                # The batch is host data: the call uploads it.
                with span("acan.jax_sgd.grad.compute", step=t.step,
                          micro=micro, bytes=nbytes(batch)):
                    out = jax.block_until_ready(self.grad_fn(params, batch))
                with span("acan.jax_sgd.grad.fetch", step=t.step,
                          micro=micro) as sp:
                    loss, grads = jax.device_get(out)
                    sp.set_metadata(bytes=nbytes((loss, grads)))
            items.append((("gpart", t.step, micro), (float(loss), grads)))
        return items

    # -------------------------------------------------------------- combine
    def combine(self, ts, rnd: int, stage: str, mgr) -> None:
        with span("acan.jax_sgd.combine", step=rnd):
            if not mgr.window.can_commit(0, rnd):
                return                   # already committed before a crash
            with span("acan.jax_sgd.combine.gather", step=rnd):
                hit = ts.try_read(("params", rnd))
                parts = [] if hit is None else [
                    ts.try_read(("gpart", rnd, m))
                    for m in range(self.n_micro)]
            if hit is None or any(p is None for p in parts):
                return                   # stage incomplete (stopped early)
            parts = [p[1] for p in parts]
            mean_loss = float(np.mean([p[0] for p in parts]))
            with span("acan.jax_sgd.combine.upload", step=rnd) as sp:
                host = (hit[1], [p[1] for p in parts])
                params, grads = jax.block_until_ready(jax.device_put(host))
                sp.set_metadata(bytes=nbytes(host))
            with span("acan.jax_sgd.combine.update", step=rnd):
                new = jax.block_until_ready(self.sgd_update(params, grads))
            del params, grads
            with span("acan.jax_sgd.combine.fetch", step=rnd) as sp:
                new_params = jax.device_get(new)
                sp.set_metadata(bytes=nbytes(new_params))
            del new
            with span("acan.jax_sgd.combine.commit", step=rnd):
                record_loss(ts, rnd, mean_loss, mgr.cfg.history_limit)
                if mgr.window.commit(0, rnd):    # §5.4 exactly-once
                    ts.put(("params", rnd + 1), new_params)
                    ts.delete(("params", rnd))

    # -------------------------------------------------------------- cleanup
    def finish_round(self, ts, rnd: int) -> None:
        ts.delete(("gpart", rnd, ANY))
        ts.delete(("done", ANY, ANY, rnd, ANY, ANY, ANY, ANY, ANY))

    # ------------------------------------------------------------- protocol
    def key_schemas(self) -> tuple[KeySchema, ...]:
        return KEY_SCHEMAS

    def stage_effects(self, rnd: int) -> dict[str, tuple[StageEffect, ...]]:
        # The grad op reads ("params", ANY) — any committed version — so
        # the read is declared unpinned and conservatively aliases every
        # params version; the combine's commit pins the versions it
        # writes/deletes. With the ("grad", -1) chain edge the WW on
        # params between consecutive rounds is always ordered.
        return {
            "grad": (
                reads("params"),
                writes("gpart", step=rnd), reads("gpart", step=rnd),
                writes("params", step=rnd + 1),
                deletes("params", step=rnd),
            ),
            FINISH_STAGE: (deletes("gpart", step=rnd),),
        }
