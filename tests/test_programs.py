"""The unified WorkloadProgram API (PR 3): the op registry, program-
agnostic scheduling, and the acceptance criteria — the paper MLP, the
JAX-SGD port, and the non-regular MoE routing program all train through
the *same* Manager/Handler plane, and the MoE program survives
manager+handler crashes with revival."""

import numpy as np
import pytest

from repro.core import (ACANCloud, CloudConfig, FaultPlan, GLOBAL_OPS,
                        LayerSpec, MLPProgram, MoERoutingProgram, OpRegistry,
                        OpSpec, TaskDesc, TupleSpace, UnknownOp)
from repro.core.manager import Manager, ManagerConfig


# ------------------------------------------------------------ op registry
def test_registry_parent_chain_and_shadowing():
    child = OpRegistry(parent=GLOBAL_OPS)
    # parent ops are visible through the chain
    assert child.resolve("forward") is GLOBAL_OPS.resolve("forward")
    # a child registration shadows without touching the parent
    spec = OpSpec("forward", lambda ctx, ts: [], lambda t: 42.0)
    child.register(spec)
    assert child.resolve("forward") is spec
    assert GLOBAL_OPS.resolve("forward") is not spec
    # duplicate registration in the same registry is rejected
    with pytest.raises(ValueError):
        child.register(spec)
    with pytest.raises(UnknownOp):
        child.resolve("definitely-not-registered")


def test_partition_respects_custom_cost_and_split():
    reg = OpRegistry(parent=GLOBAL_OPS)
    reg.register(OpSpec("atomic", lambda ctx, ts: [],
                        cost_fn=lambda t: 1e9, split_fn=lambda t: [t]))
    t = TaskDesc("atomic", 0, 0, 0)
    assert reg.partition(t, 256.0) == [t]    # indivisible stays whole


# ----------------------------------------------- programs on the one plane
def _moe_cfg(**kw):
    base = dict(n_handlers=3, task_cap=256.0, pouch_size=64,
                time_scale=1e-6, initial_timeout=0.1,
                fault_plan=FaultPlan(interval=1e9), wall_limit=120.0)
    base.update(kw)
    return CloudConfig(**base)


def test_moe_program_trains_decreasing_loss():
    prog = MoERoutingProgram(steps=12, seed=0)
    res = ACANCloud(_moe_cfg(), program=prog).run()
    losses = [l for _, l in res.loss_history]
    assert len(losses) == 12
    assert np.mean(losses[-4:]) < np.mean(losses[:4])
    assert res.ledger_ok
    assert res.manager_revivals == 0


def test_moe_program_survives_manager_and_handler_crashes():
    """Acceptance: the non-regular program completes under an exp3-style
    plan (Manager AND all Handlers crash each interval with p=1.0) via
    daemon revival, and still learns."""
    prog = MoERoutingProgram(steps=12, seed=0)
    res = ACANCloud(_moe_cfg(
        fault_plan=FaultPlan(interval=0.1, speed_levels=(1.0, 5.0, 10.0),
                             p_speed_change=1.0, p_handler_crash=1.0,
                             p_manager_crash=1.0, seed=1)),
        program=prog).run()
    losses = [l for _, l in res.loss_history]
    assert len(losses) == 12              # completed despite the crashes
    assert np.mean(losses[-4:]) < np.mean(losses[:4])
    assert res.manager_revivals >= 1
    assert res.handler_revivals >= 1
    assert res.ledger_ok


def test_moe_task_sizes_are_irregular():
    """The expert stage's task costs are data-dependent: after routing, a
    hot expert's prototype task must cost more than a cold expert's —
    the non-regular regime the GSS timeout has to absorb."""
    prog = MoERoutingProgram(steps=2, seed=0)
    expert_tasks = prog.probe_expert_tasks()
    costs = [GLOBAL_OPS.cost(t) for t in expert_tasks]
    assert len(costs) >= 2
    assert len(set(costs)) > 1, costs     # irregular — not uniform
    # every routed slot appears exactly once across the expert tasks
    total_slots = sum(t.n for t in expert_tasks)
    assert total_slots == prog.B * prog.k


def test_moe_dispatch_is_revival_deterministic():
    """stage_tasks is a pure function of TS state: a 'revived' Manager
    (fresh program call on the same TS) derives identical expert tasks."""
    prog = MoERoutingProgram(steps=2, seed=3)
    ts = TupleSpace()
    prog.setup(ts)
    mgr = Manager(ts=ts, program=prog, cfg=ManagerConfig(task_cap=1e9))
    from repro.core.executor import TaskExecutor
    TaskExecutor(ts).execute_batch(prog.stage_tasks(ts, 0, "route"))
    prog.combine(ts, 0, "route", mgr)
    first = prog.expert_stage_tasks(ts, 0)
    prog2 = MoERoutingProgram(steps=2, seed=3)     # the revived instance
    prog2.combine(ts, 0, "route", mgr)             # idempotent re-run
    assert prog2.expert_stage_tasks(ts, 0) == first


def test_mlp_program_equals_legacy_cloud_path():
    """CloudConfig without an explicit program builds the MLP program —
    and an explicitly-passed MLPProgram is bit-identical to it."""
    base = dict(layers=[LayerSpec(16, 16), LayerSpec(16, 1)], n_handlers=3,
                epochs=1, n_samples=6, task_cap=32.0, pouch_size=64,
                lr=0.05, time_scale=1e-6, initial_timeout=0.1,
                fault_plan=FaultPlan(interval=1e9), seed=0, wall_limit=60.0)
    res_default = ACANCloud(CloudConfig(**base)).run()
    cfg = CloudConfig(**base)
    res_explicit = ACANCloud(cfg, program=MLPProgram(
        cfg.layers, epochs=1, n_samples=6, seed=0)).run()
    ld = [l for _, l in res_default.loss_history]
    le = [l for _, l in res_explicit.loss_history]
    np.testing.assert_allclose(ld, le, rtol=1e-6, atol=1e-8)


def test_moe_route_combine_resumes_after_partial_crash():
    """Crash-recovery contract: the route combine's idempotency guard is
    its LAST-written tuple (expert 0's dispatch), so a Manager that died
    mid-combine leaves the guard unset and the revived combine redoes
    everything instead of wedging stage_tasks('expert')."""
    from repro.core.executor import TaskExecutor
    prog = MoERoutingProgram(steps=1, seed=0)
    ts = TupleSpace()
    prog.setup(ts)
    TaskExecutor(ts).execute_batch(prog.stage_tasks(ts, 0, "route"))
    prog._combine_route(ts, 0)
    # Simulate a crash mid-combine: the guard tuple is missing, the rest
    # of the dispatch lists landed.
    ts.delete(("disp", 0, 0))
    prog._combine_route(ts, 0)          # the revived Manager's re-run
    for e in range(prog.E):
        assert ts.try_read(("disp", 0, e)) is not None
    assert len(prog.expert_stage_tasks(ts, 0)) >= 1


def test_mlp_backward_combine_resumes_after_partial_crash():
    """Same contract for the MLP backward combine: the guard is dy (the
    last-written tuple), so a crash between the gW and gB/dy puts does
    not make the revived Manager skip the rest of the combine."""
    layers = [LayerSpec(8, 8), LayerSpec(8, 1)]
    prog = MLPProgram(layers, epochs=1, n_samples=1, seed=0)
    rng = np.random.default_rng(5)
    ts = TupleSpace()
    l, d = 1, 0
    ts.put(("gw", l, d, 0, 1, 0, 8), rng.standard_normal((1, 8)).astype(np.float32))
    ts.put(("gb", l, d, 0, 1), rng.standard_normal(1).astype(np.float32))
    ts.put(("bpart", l, d, 0, 8, 0, 1), rng.standard_normal(8).astype(np.float32))
    ts.put(("act", 0, d), rng.standard_normal(8).astype(np.float32))
    prog._combine_backward(ts, l, d, layers[l])
    full_gB = ts.try_read(("gB", l, d))[1]
    # Simulate a crash after the gW put but before gB/dy landed.
    ts.delete(("gB", l, d))
    ts.delete(("dy", 0, d))
    prog._combine_backward(ts, l, d, layers[l])   # revived re-run
    np.testing.assert_array_equal(ts.try_read(("gB", l, d))[1], full_gB)
    assert ts.try_read(("dy", 0, d)) is not None


def test_reissued_counts_only_straggler_republications():
    """A stage wider than pouch_size publishes its later pouches of
    first-time tasks — those must NOT count as re-issues (only a task
    published a second time after a timeout does)."""
    import threading
    from repro.core.handler import Handler, SpeedBox
    ts = TupleSpace()
    prog = MLPProgram([LayerSpec(16, 16), LayerSpec(16, 1)], epochs=1,
                      n_samples=2, seed=0)
    # task_cap 16 -> fwd_0 partitions into 16 tasks; pouch_size 4 forces
    # four first-time pouches per such stage.
    mgr = Manager(ts=ts, program=prog,
                  cfg=ManagerConfig(task_cap=16.0, pouch_size=4,
                                    initial_timeout=10.0))
    stop = threading.Event()
    h = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=16.0,
                lr=0.01, time_scale=1e-9, stop_event=stop)
    th = threading.Thread(target=h.run, daemon=True)
    th.start()
    mgr.run()
    stop.set()
    th.join(timeout=2.0)
    assert ts.try_read(("mstate", "finished")) is not None
    # A genuine GSS timeout under load may re-issue its pending tasks; a
    # first-time pouch counted as a re-issue would exceed them.
    assert mgr.reissued <= mgr.timed_out_tasks, (mgr.reissued,
                                                 mgr.timed_out_tasks)


@pytest.mark.parametrize("straggler_s", [0.1, 0.6, 5.0])
def test_gss_timeout_learns_nothing_from_a_met_reissue(monkeypatch,
                                                        straggler_s):
    """A pouch that re-published a task and is then met leaves the
    timeout where the deadline put it, whether the straggler lands
    moments after its re-issue (which, learnt, halved the timeout and
    set off a cascade of re-issues) or its copy runs the task from the
    start (which, learnt from the first issue, ratcheted the timeout up
    after every handler kill). First-time pouches adapt it as always."""
    import types

    from repro.core import manager as manager_mod
    from repro.core.manager import _StageRun
    from repro.core.tasks import content_key
    clock = [100.0]
    monkeypatch.setattr(manager_mod, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0], time=lambda: clock[0]))
    ts = TupleSpace()
    prog = MLPProgram([LayerSpec(4, 1)], epochs=1, n_samples=1, seed=0)
    mgr = Manager(ts=ts, program=prog,
                  cfg=ManagerConfig(initial_timeout=1.0))
    tasks = [TaskDesc("jaxgrad", 0, 0, 0, 0, 0, m, m + 1) for m in range(2)]
    run = _StageRun(rnd=0, name="grad", order=0, tasks=tasks,
                    done_pat=mgr._stage_done_pattern(tasks))

    def done(t):
        ts.put(("done",) + content_key(t), "h")

    mgr._start_pouch(run)                   # both tasks, first issue
    clock[0] += 1.0
    done(tasks[0])
    mgr._finish_pouch(run, barrier_met=False)   # deadline, one pending
    assert mgr.controller.timeout == pytest.approx(1.3)   # x (1 + 0.6/2)
    mgr._start_pouch(run)                   # the straggler re-issued
    assert mgr.reissued == 1 and run.pouch == [tasks[1]]
    clock[0] += straggler_s
    done(tasks[1])
    mgr._finish_pouch(run, barrier_met=True)
    assert mgr.controller.timeout == pytest.approx(1.3)

    # A first-time pouch is timed from its own issue: EMA toward 1.3x.
    later = [TaskDesc("jaxgrad", 0, 1, 1, 0, 0, 0, 1)]
    run = _StageRun(rnd=1, name="grad", order=0, tasks=later,
                    done_pat=mgr._stage_done_pattern(later))
    mgr._start_pouch(run)
    clock[0] += 0.5
    done(later[0])
    mgr._finish_pouch(run, barrier_met=True)
    assert mgr.controller.timeout == pytest.approx(0.5 * 1.3 + 0.65 * 0.5)


def test_moe_respects_history_limit():
    prog = MoERoutingProgram(steps=10, seed=0)
    res = ACANCloud(_moe_cfg(history_limit=4), program=prog).run()
    steps = [s for s, _ in res.loss_history]
    assert steps == list(range(6, 10))    # trimmed to the newest 4


@pytest.mark.parametrize("limit", [64, 1 << 20], ids=["sliced", "whole"])
def test_gradient_slices_rejoin_exactly(limit):
    """A gradient leaf crosses to the host in slices of at most ``limit``
    bytes along its first axis (one row at least), and the combine's join
    gives back the tree bit for bit."""
    import jax
    import jax.numpy as jnp

    from repro.programs import jax_sgd
    tree = {"w": jnp.arange(35, dtype=jnp.float32).reshape(7, 5),
            "loss": jnp.float32(3.0),
            "stack": {"u": jnp.arange(24, dtype=jnp.bfloat16).reshape(3, 2, 4)},
            "wide": jnp.ones((2, 100), jnp.float32)}
    slices = jax.device_get(jax.jit(jax_sgd.slice_leaves,
                                    static_argnums=1)(tree, limit))
    for x, parts in zip(jax.tree.leaves(tree), slices):
        row = x.nbytes // x.shape[0] if x.ndim else x.nbytes
        assert all(p.nbytes <= max(limit, row) for p in parts)
        assert (len(parts) > 1) == (x.nbytes > limit and x.ndim > 0
                                    and x.shape[0] > 1)
    back = jax.jit(jax_sgd.join_leaves, static_argnums=0)(
        jax.tree.structure(tree), slices)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_calls_stay_on_the_programs_threads():
    """Under kills of the Manager and every handler, each grad call and
    each update runs on one of the program's own device threads, never on
    the handler or Manager threads the fault plane replaces."""
    import threading

    from repro.configs import get_config
    from repro.programs import jax_sgd
    prog = jax_sgd.JAXSGDProgram(get_config("smollm_360m", reduced=True),
                                 steps=6, n_micro=2, micro_batch=2, seq=16,
                                 seed=3)
    seen = []
    for name in ("grad_fn", "sgd_update"):
        fn = getattr(prog, name)

        def spy(*a, _fn=fn):
            seen.append(threading.current_thread().name)
            return _fn(*a)
        setattr(prog, name, spy)
    res = ACANCloud(CloudConfig(
        n_handlers=2, handler_batch=1, wall_limit=120.0, initial_timeout=0.002,
        fault_plan=FaultPlan(interval=0.2, p_manager_crash=1.0,
                             p_handler_crash=1.0, seed=5)),
        program=prog).run()
    assert res.finished and res.manager_revivals >= 1
    assert seen and all(n.startswith("jax_sgd-device") for n in seen)
    assert len(set(seen)) <= jax_sgd.DEVICE_THREADS
