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

The op closes over the jitted grad function and the data pipeline, so it
registers in a **program-private** registry chained to the global one —
two concurrent programs never collide.

Everything the program puts into the space is host (numpy) data: the
space may live in a server process that must never touch the device
(unpickling a ``jax.Array`` would ``device_put`` there). Only the
process running the op and the combine moves arrays to the device: each
handler uploads a param version once (``_device_params``), and the
combine averages and applies the update with one jitted call.

The op and the combine run in one process (a program with ops of its
own cannot run on a process fleet), so the combine takes its operands
from the device copies the ops left there: the gradients the ops kept
(``_keep_grads``) and the param version they read. The space still gets
every gradient and every committed version as host data, and a copy
missing on the device (after a Manager revival, say) goes up from it.

Each move between host and device is a span of its own (``acan.jax_sgd.*``,
:mod:`repro.core.trace`) that carries the ``bytes`` it moved.

TS data-plane keys: ``("params", step)`` (current param tree),
``("gpart", step, micro)`` ((loss, grad tree as the slices of
:func:`slice_leaves`) per microbatch) — scoped to the ``jax_sgd``
namespace when co-resident with other programs on a
multi-tenant cloud (the op's ``ctx.ts`` is then that tenant's
:class:`~repro.core.space.ScopedSpace`, so a handler fleet can serve
JAX training next to the numpy programs on one space).
"""

from __future__ import annotations

import functools
import threading
from concurrent.futures import ThreadPoolExecutor

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


#: Largest slice in which a gradient leaf crosses to the host. On a TPU
#: v5e host, copies of whole leaves (up to 157 MB for smollm-360m) into
#: fresh host buffers, three gradient trees at a time, used up host
#: memory at ~2 GB/s once more than ~1.5 trees a second crossed; in
#: slices of 16 MB the same traffic held host memory flat and a tree's
#: copy took 0.38 s, not 0.47 s (PERF.md).
FETCH_SLICE_BYTES = 16 << 20


#: Most threads the program makes device calls from. The TPU runtime
#: keeps ~0.45 GB of host memory for each thread that ever moved data to
#: or from the device (a fresh thread per grad call, three at a time,
#: used up host memory at ~1 GB/s; PERF.md), and the fault plane
#: replaces every handler and Manager thread it kills. So the program
#: makes its device calls from threads of its own that outlive them.
DEVICE_THREADS = 8


def slice_leaves(tree, limit: int) -> list[list]:
    """Each leaf of ``tree`` as a list of slices along its first axis of
    at most ``limit`` bytes (at least one row); a leaf within it, or a
    scalar, as ``[leaf]``. The cuts follow from the shapes, so this runs
    under ``jit``."""
    out = []
    for x in jax.tree.leaves(tree):
        size = x.size * x.dtype.itemsize
        if x.ndim == 0 or size <= limit:
            out.append([x])
            continue
        rows = max(1, limit * x.shape[0] // size)
        out.append([x[i:i + rows] for i in range(0, x.shape[0], rows)])
    return out


def join_leaves(treedef, slices: list[list]):
    """Inverse of :func:`slice_leaves`: the tree of ``treedef`` whose
    leaves are ``slices`` joined along their first axis."""
    return jax.tree.unflatten(treedef, [
        s[0] if len(s) == 1 else jnp.concatenate(s) for s in slices])


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
              description="(loss, grad tree in slices) per microbatch"),
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
        # A gradient crosses to the host, and back to a combine that has
        # no device copy of it, as the slices of slice_leaves (see
        # FETCH_SLICE_BYTES).
        self._slice = jax.jit(
            functools.partial(slice_leaves, limit=FETCH_SLICE_BYTES))
        self._join = jax.jit(join_leaves, static_argnums=0)
        self._treedef = jax.tree.structure(M.abstract_params(cfg))
        self._pool = ThreadPoolExecutor(DEVICE_THREADS,
                                        thread_name_prefix="jax_sgd-device")

        def sgd_update(params, grads_list):
            def leaf(p, *gs):
                g = sum(x.astype(jnp.float32) for x in gs) / len(gs)
                return (p.astype(jnp.float32) - lr * g).astype(p.dtype)
            return jax.tree.map(leaf, params, *grads_list)

        #: ``(params, [grads per micro]) -> params``: mean-of-grads SGD,
        #: accumulated in float32 — what every combine runs.
        self.sgd_update = jax.jit(sgd_update)
        # (version, device copy) of the last param version an op read or
        # a combine committed.
        self._dev_params: tuple[int, object] | None = None
        # {(step, micro): (version, device gradient)} that the ops kept
        # for the combine, all of param version _grads_version.
        self._dev_grads: dict[tuple[int, int], tuple[int, object]] = {}
        self._grads_version = 0
        self._dev_lock = threading.Lock()
        self.registry = OpRegistry(parent=ensure_builtin_ops())
        self.registry.register(OpSpec(
            JAXGRAD, self._grad_parts,
            cost_fn=lambda t: 1.0,          # uniform, indivisible
            split_fn=lambda t: [t]))

    # ---------------------------------------------------------------- setup
    def setup(self, ts) -> None:
        fresh = ts.try_read(("params", ANY)) is None
        with self._dev_lock:
            self._dev_params = None
            if fresh:            # a new job: its versions count from 0
                self._dev_grads, self._grads_version = {}, 0
        if fresh:
            params = M.init_params(self.cfg, jax.random.PRNGKey(self.seed))
            ts.put(("params", 0), jax.device_get(params))

    def _on_device(self, fn, *args):
        """``fn(*args)`` on one of the program's device threads (see
        :data:`DEVICE_THREADS`); the caller waits for it."""
        return self._pool.submit(fn, *args).result()

    def _device_params(self, version: int, host_params):
        """The device copy of param ``version``, uploaded once per version
        for all handler threads (a version is immutable once committed).
        The span holds the wait for the lock and, for a new version, the
        upload's dispatch; the grad's compute span waits for the rest."""
        with span("acan.jax_sgd.grad.params_upload",
                  version=version) as sp, self._dev_lock:
            if self._dev_params is None or self._dev_params[0] != version:
                self._dev_params = None          # free the old copy first
                self._dev_params = (version, self._on_device(
                    jax.device_put, host_params))
                sp.set_metadata(uploaded=1, bytes=nbytes(host_params))
            else:
                sp.set_metadata(uploaded=0)
            return self._dev_params[1]

    def _keep_grads(self, step: int, micro: int, version: int,
                    grads) -> None:
        """Keep the device gradient of ``(step, micro)``, computed on param
        ``version``, for the combine. Only one on its own step's version
        can be combined, and only those of the newest version are kept
        (at most ``n_micro`` trees): a newer one drops them, an older one
        (a straggler whose step has committed) is not kept. A duplicate
        run is bitwise the same, so the first stays."""
        with self._dev_lock:
            if version != step or version < self._grads_version:
                return
            self._drop_grads(version)
            self._dev_grads.setdefault((step, micro), (version, grads))

    def _drop_grads(self, version: int) -> None:
        """Drop the kept gradients of versions before ``version``, and
        keep none of them from now on. The caller holds ``_dev_lock``."""
        if version > self._grads_version:
            self._dev_grads, self._grads_version = {}, version

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
                    out = self._on_device(lambda: jax.block_until_ready(
                        self.grad_fn(params, batch)))
                with span("acan.jax_sgd.grad.fetch", step=t.step,
                          micro=micro) as sp:
                    loss, grads = self._on_device(lambda: jax.device_get(
                        (out[0], self._slice(out[1]))))
                    sp.set_metadata(bytes=nbytes((loss, grads)))
                self._keep_grads(t.step, micro, hit[0][1], out[1])
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
                params, grads = self._resident(rnd)
                reused = sum(x is not None for x in [params, *grads])
                (params, grads), moved = self._on_device(
                    self._upload, params, grads, hit[1],
                    [p[1] for p in parts])
                sp.set_metadata(reused=reused, bytes=moved)
            with span("acan.jax_sgd.combine.update", step=rnd):
                new = self._on_device(lambda: jax.block_until_ready(
                    self.sgd_update(params, grads)))
            del params, grads
            with span("acan.jax_sgd.combine.fetch", step=rnd) as sp:
                new_params = self._on_device(jax.device_get, new)
                sp.set_metadata(bytes=nbytes(new_params))
            with span("acan.jax_sgd.combine.commit", step=rnd):
                record_loss(ts, rnd, mean_loss, mgr.cfg.history_limit)
                if mgr.window.commit(0, rnd):    # §5.4 exactly-once
                    # The next round's ops read the new version here.
                    with self._dev_lock:
                        self._dev_params = (rnd + 1, new)
                        self._drop_grads(rnd + 1)
                    ts.put(("params", rnd + 1), new_params)
                    ts.delete(("params", rnd))

    def _resident(self, rnd: int):
        """The combine's operands of step ``rnd`` that are on the device
        already, None for each that is not: the params if the ops last
        read version ``rnd``, and each micro's gradient if an op kept it
        computed on that version (a straggler of step ``rnd`` may have
        read the next)."""
        with self._dev_lock:
            params = self._dev_params
            grads = [self._dev_grads.get((rnd, m))
                     for m in range(self.n_micro)]
        return (params[1] if params is not None and params[0] == rnd
                else None,
                [g[1] if g is not None and g[0] == rnd else None
                 for g in grads])

    def _upload(self, params, grads, host_params, host_grads):
        """``(params, grads)`` on the device, each None uploaded from its
        host copy, and the bytes that took. A gradient goes up as its
        slices, joined as they land, one gradient at a time, so no more
        than one sliced copy is on the device."""
        moved = 0
        if params is None:
            params = jax.device_put(host_params)
            moved += nbytes(host_params)
        grads = list(grads)
        for m, g in enumerate(grads):
            if g is None:
                grads[m] = self._join(self._treedef,
                                      jax.device_put(host_grads[m]))
                moved += nbytes(host_grads[m])
        return jax.block_until_ready((params, grads)), moved

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
