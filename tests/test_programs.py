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


def test_acan_step_runner_trains_and_survives_crashes():
    """JAX-SGD through ACANCloud, with the program's own handler crashes
    (a quarter of the grad tasks are dropped mid-task): it learns, and
    commits every param version exactly once."""
    from repro.configs import get_config
    from repro.core.space import ANY
    from repro.programs.jax_sgd import JAXSGDProgram
    prog = JAXSGDProgram(get_config("smollm_360m", reduced=True), steps=6,
                         n_micro=3, micro_batch=2, seq=32, lr=0.05,
                         handler_crash_prob=0.25, seed=0)
    # A dropped task waits out the GSS deadline before its re-issue; 5 s
    # covers the first pouch's compile, and the controller adapts after.
    cloud = ACANCloud(CloudConfig(
        n_handlers=3, handler_batch=1, initial_timeout=5.0,
        fault_plan=FaultPlan(interval=1e9), wall_limit=120.0), program=prog)
    res = cloud.run()
    losses = [l for _, l in sorted(res.loss_history)]
    assert res.finished
    assert len(losses) == 6
    assert cloud.ts.keys(("params", ANY)) == [("params", 6)]  # exactly-once
    assert losses[-1] < losses[0]           # it actually learns
    assert all(np.isfinite(l) for l in losses)
    # with 25% crash probability over >= 18 tasks we expect some re-issues
    assert prog.crashes + res.reissues >= 1


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


# ------------------------------------------- JAX-SGD's resident operands
N_MICRO = 2


def _sgd(steps: int = 3):
    from repro.configs import get_config
    from repro.programs.jax_sgd import JAXSGDProgram
    return JAXSGDProgram(get_config("smollm_360m", reduced=True),
                         steps=steps, n_micro=N_MICRO, micro_batch=2,
                         seq=16, seed=3)


class _Span(dict):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set_metadata(self, **ids):
        self.update(ids)


def _spy_spans(monkeypatch) -> list:
    """Stand in for the program's spans: each one's name and ids, those
    set on it as it ran included, whether a profiler runs or not."""
    from repro.programs import jax_sgd
    got = []

    def span(name, **ids):
        got.append((name, s := _Span(ids)))
        return s
    monkeypatch.setattr(jax_sgd, "span", span)
    return got


def _ids(spans, name) -> list:
    return [s for n, s in spans if n == name]


def _run_grads(prog, ts, rnd: int) -> None:
    """Run round ``rnd``'s grad tasks by hand and publish their parts."""
    from repro.core.executor import ExecContext
    ctx = ExecContext(ts)
    for t in prog.stage_tasks(ts, rnd, "grad"):
        for key, val in prog._grad_parts(ctx, [t]):
            ts.put(key, val)


def _mgr():
    import types

    from repro.core.conflict import CommitWindow
    return types.SimpleNamespace(window=CommitWindow(), cfg=ManagerConfig())


def _assert_trees_equal(a, b) -> None:
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and np.array_equal(x, y)


def _sgd_cloud(prog, **kw):
    cfg = dict(n_handlers=2, handler_batch=1, wall_limit=120.0)
    cfg.update(kw)
    cloud = ACANCloud(CloudConfig(**cfg), program=prog)
    res = cloud.run()
    assert res.finished
    return res, cloud.ts


def test_resident_operands_update_bitwise_as_the_fallback(monkeypatch):
    """N steps whose combines take the ops' device copies end on the same
    params, bit for bit, and the same losses as N steps whose combines
    find no device copy (the store read as empty) and upload every
    operand from the space; the store never holds more than n_micro
    gradient trees."""
    spans = _spy_spans(monkeypatch)
    steps = 3
    resident = _sgd(steps)
    real_keep, most = resident._keep_grads, [0]

    def keep(*a):
        real_keep(*a)
        most[0] = max(most[0], len(resident._dev_grads))
    resident._keep_grads = keep
    res_a, ts_a = _sgd_cloud(resident)
    reused_a = [s["reused"] for s in _ids(spans, "acan.jax_sgd.combine.upload")]

    spans.clear()
    fallback = _sgd(steps)
    fallback._resident = lambda rnd: (None, [None] * N_MICRO)
    res_b, ts_b = _sgd_cloud(fallback)
    ups = _ids(spans, "acan.jax_sgd.combine.upload")

    assert 0 < most[0] <= N_MICRO
    assert sum(reused_a) > 0
    assert ups and all(s["reused"] == 0 for s in ups)
    from repro.programs.jax_sgd import nbytes
    tree = nbytes(ts_b.try_read(("params", steps))[1])
    assert all(s["bytes"] == (N_MICRO + 1) * tree for s in ups)
    assert res_a.loss_history == res_b.loss_history
    assert len(res_a.loss_history) == steps
    _assert_trees_equal(ts_a.try_read(("params", steps))[1],
                        ts_b.try_read(("params", steps))[1])


def test_gradient_of_another_version_is_never_combined(monkeypatch):
    """A kept gradient of step ``rnd`` computed on another param version
    (a straggler that read the next one) is refused when kept and, were
    it in the store, skipped by the combine: that micro goes up from the
    space, ``reused`` counts the rest, and the update is the fallback's."""
    import jax
    import jax.numpy as jnp

    from repro.programs.jax_sgd import nbytes
    spans = _spy_spans(monkeypatch)
    prog = _sgd()
    ts = TupleSpace()
    prog.setup(ts)
    _run_grads(prog, ts, 0)
    # The same inputs for a fallback combine on a space of its own.
    twin = TupleSpace()
    for key in [("params", 0)] + [("gpart", 0, m) for m in range(N_MICRO)]:
        twin.put(key, ts.try_read(key)[1])

    version, grads = prog._dev_grads[(0, 1)]
    assert version == 0
    prog._keep_grads(0, 1, 1, grads)            # a straggler on version 1
    assert prog._dev_grads[(0, 1)][1] is grads
    # Plant a wrong-version gradient, zeros, for micro 1.
    prog._dev_grads[(0, 1)] = (1, jax.tree.map(jnp.zeros_like, grads))
    prog.combine(ts, 0, "grad", _mgr())
    (up,) = _ids(spans, "acan.jax_sgd.combine.upload")
    tree = nbytes(ts.try_read(("params", 1))[1])
    assert up["reused"] == N_MICRO             # params and micro 0
    assert up["bytes"] == tree                 # micro 1 from the space

    prog._dev_params = None                    # nothing resident
    prog.combine(twin, 0, "grad", _mgr())
    (_, up) = _ids(spans, "acan.jax_sgd.combine.upload")
    assert up["reused"] == 0 and up["bytes"] == (N_MICRO + 1) * tree
    _assert_trees_equal(ts.try_read(("params", 1))[1],
                        twin.try_read(("params", 1))[1])


def test_commit_drops_older_gradients_and_keeps_the_new_params(monkeypatch):
    """The store holds at most n_micro trees, the first of duplicate runs;
    a committing combine drops every gradient of an older version and
    leaves the new version on the device, so the next round's ops upload
    no params and its combine uploads nothing."""
    spans = _spy_spans(monkeypatch)
    prog = _sgd()
    ts = TupleSpace()
    prog.setup(ts)
    _run_grads(prog, ts, 0)
    assert sorted(prog._dev_grads) == [(0, m) for m in range(N_MICRO)]
    first = prog._dev_grads[(0, 0)][1]
    from repro.core.executor import ExecContext
    (t0,) = [t for t in prog.stage_tasks(ts, 0, "grad") if t.out_lo == 0]
    prog._grad_parts(ExecContext(ts), [t0])    # a straggler's duplicate
    assert len(prog._dev_grads) == N_MICRO
    assert prog._dev_grads[(0, 0)][1] is first

    mgr = _mgr()
    prog.combine(ts, 0, "grad", mgr)
    assert mgr.window.committed_step == {0: 0}
    assert prog._dev_grads == {} and prog._grads_version == 1
    assert prog._dev_params[0] == 1
    _assert_trees_equal(prog._dev_params[1], ts.try_read(("params", 1))[1])
    prog._keep_grads(0, 0, 0, first)           # lands after its commit
    assert prog._dev_grads == {}

    spans.clear()
    _run_grads(prog, ts, 1)
    ups = _ids(spans, "acan.jax_sgd.grad.params_upload")
    assert len(ups) == N_MICRO
    assert all(s["version"] == 1 and s["uploaded"] == 0 for s in ups)
    assert sorted(prog._dev_grads) == [(1, m) for m in range(N_MICRO)]
    assert all(v == 1 for v, _ in prog._dev_grads.values())
    prog.combine(ts, 1, "grad", mgr)
    (up,) = _ids(spans, "acan.jax_sgd.combine.upload")
    assert up["reused"] == N_MICRO + 1 and up["bytes"] == 0
    assert prog._dev_grads == {} and prog._dev_params[0] == 2


def _jax_sgd_crash_sites() -> list:
    """The crash points of the JAX-SGD job: each TS mutation site of the
    program (crashed after the op lands), and the combine's loss record,
    crashed before it lands — after the new params' fetch, before the
    commit — in the second step."""
    import sys
    from pathlib import Path
    repo = str(Path(__file__).resolve().parent.parent)
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.crash_lint import site_registry
    sites = {s.site_id: s for s in site_registry()}
    out = [(sites["manager:program.record_loss:put[losshist]#0"],
            "before", 2)]
    out += [(s, "after", 1) for s in sites.values()
            if s.path.endswith("programs/jax_sgd.py")]
    return out


@pytest.fixture(scope="module")
def crash_free():
    res, ts = _sgd_cloud(_sgd(), ts_backend="crashpoint+sharded",
                         fault_plan=FaultPlan(interval=1e9))
    return res.loss_history, ts.try_read(("params", 3))[1]


@pytest.mark.parametrize("site,when,nth", _jax_sgd_crash_sites(),
                         ids=lambda v: getattr(v, "site_id", str(v)))
def test_manager_crash_in_the_combine_commits_once(monkeypatch, crash_free,
                                                   site, when, nth):
    """A Manager crash at each of the JAX-SGD job's crash points, the one
    between the combine's fetch and its commit included, is revived and
    commits every version once: the same losses and, bit for bit, the
    same final params as the crash-free run, and only that version left
    in the space. Crashed before its commit, the combine's re-run takes
    the kept gradients and uploads only the params, which the revived
    Manager no longer holds on the device."""
    from repro.core.space import ANY, CrashSpec, find_crashpoint
    spans = _spy_spans(monkeypatch)
    prog = _sgd()
    cloud = ACANCloud(CloudConfig(
        n_handlers=2, handler_batch=1, wall_limit=120.0,
        ts_backend="crashpoint+sharded", fault_plan=FaultPlan(interval=1e9)),
        program=prog)
    cp = find_crashpoint(cloud.ts.backend)
    cp.arm(CrashSpec(site_id=site.site_id, role=site.role, path=site.path,
                     line=site.line, end_line=site.end_line, nth=nth,
                     when=when))
    res = cloud.run()
    assert res.finished
    assert cp.firings, "the armed site was never reached"
    assert res.manager_revivals >= 1
    losses, params = crash_free
    assert res.loss_history == losses
    assert cloud.ts.keys(("params", ANY)) == [("params", 3)]
    _assert_trees_equal(cloud.ts.try_read(("params", 3))[1], params)
    if when == "before":                       # step 1's combine, twice
        ups = [s for s in _ids(spans, "acan.jax_sgd.combine.upload")
               if s["step"] == 1]
        assert [s["reused"] for s in ups] == [N_MICRO + 1, N_MICRO]
