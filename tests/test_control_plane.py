"""The event-driven control plane (PR 2) on the program-agnostic
scheduler (PR 3): blocking pouch barriers with crash/resume semantics,
batched vectorized task execution, the Handler "store" livelock guard,
and TS garbage caps."""

import threading
import time

import numpy as np
import pytest

from repro.core import (ACANCloud, CloudConfig, FaultPlan, LayerSpec,
                        MLPProgram, TupleSpace, make_teacher_data, partition,
                        prototype_tasks)
from repro.core.executor import PreconditionUnmet, TaskExecutor
from repro.core.handler import Handler, SpeedBox
from repro.core.manager import Manager, ManagerConfig, ManagerCrash
from repro.core.tasks import TaskDesc
from repro.core.space import ANY


# ------------------------------------------------- barrier crash/resume
def test_manager_crash_inside_blocking_barrier_resumes_from_cursor():
    """Crash the Manager while it is parked INSIDE a blocking pouch
    barrier (no handlers -> the barrier cannot complete; GSS timeout 30 s
    -> without the sliced wait the crash would fire only after 30 s),
    then revive from TS state alone and finish the job exactly once."""
    ts = TupleSpace(backend="sharded")
    layers = [LayerSpec(8, 8), LayerSpec(8, 1)]
    n_samples = 4
    X, Y = make_teacher_data(layers, n_samples, 0)
    for i in range(n_samples):
        ts.put(("x", i), X[i])
        ts.put(("label", i), Y[i])
    program = MLPProgram(layers, epochs=1, n_samples=n_samples, seed=0)
    cfg = ManagerConfig(task_cap=16.0, pouch_size=50, initial_timeout=30.0)
    mgr = Manager(ts=ts, program=program, cfg=cfg)
    outcome = []

    def body():
        try:
            mgr.run()
        except ManagerCrash:
            outcome.append("crash")

    th = threading.Thread(target=body, daemon=True)
    th.start()
    time.sleep(0.3)
    assert th.is_alive()                      # parked in the barrier
    assert ts.count(("task", ANY)) > 0        # with its pouch issued
    t0 = time.monotonic()
    mgr.crash_event.set()
    th.join(timeout=2.0)
    crash_latency = time.monotonic() - t0
    assert not th.is_alive() and outcome == ["crash"]
    assert crash_latency < 1.0                # not the 30 s GSS deadline
    cursor = ts.try_read(("mstate", "cursor"))
    assert cursor is not None
    assert (cursor[1]["round"], cursor[1]["stage_idx"]) == (0, 0)

    # Revival: a fresh Manager + one handler resume from the cursor and
    # the done marks already in TS; every sample completes exactly once.
    stop = threading.Event()
    mgr2 = Manager(ts=ts, program=program, cfg=cfg, stop_event=stop)
    handler = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=16.0,
                      lr=0.05, time_scale=1e-6, stop_event=stop)
    threads = [threading.Thread(target=mgr2.run, daemon=True),
               threading.Thread(target=handler.run, daemon=True)]
    for t in threads:
        t.start()
    ts.read(("mstate", "finished"), timeout=60.0)
    stop.set()
    steps = sorted(k[1] for k in ts.keys(("losshist", ANY)))
    assert steps == list(range(n_samples))


# --------------------------------------------------- store livelock guard
def test_store_livelock_all_handlers_under_capacity():
    """Regression: a too-big task re-put under the same key could be
    re-taken immediately by the same handler — with every handler
    under-capacity the seed loop degenerated into a hot take/store spin.
    Tagged re-puts + one-backoff-cycle self-skip keep the task circulating
    at backoff cadence while small tasks drain normally."""
    ts = TupleSpace(backend="sharded")
    ts.put(("pre", 0, 0), np.zeros(8, dtype=np.float32))
    big = TaskDesc("forward", 0, 0, 0, 0, 32, 0, 32)          # cost 1024
    ts.put(("task", "big"), big.to_wire())
    n_small = 8
    for j in range(n_small):                                  # cost 1 each
        t = TaskDesc("activation", 0, 0, 0, 0, 0, j, j + 1)
        ts.put(("task", f"s{j}"), t.to_wire())
    stop = threading.Event()
    handlers = [Handler(ts=ts, name=f"h{i}", speed=SpeedBox(1.0),
                        capacity=16.0, time_scale=1e-9,
                        store_backoff=0.02, stop_event=stop)
                for i in range(2)]
    threads = [threading.Thread(target=h.run, daemon=True) for h in handlers]
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=2.0)
    assert sum(h.tasks_done for h in handlers) == n_small
    assert ts.count(("task", ANY)) == 1       # the big task still circulates
    # Bounded by the backoff cadence (~0.5 s / 0.02 s per handler, plus
    # slack) — the untagged seed loop spun ~1000 stores/s here.
    assert sum(h.tasks_stored for h in handlers) < 150


def test_unknown_op_is_stored_not_fatal():
    """A task whose op is not in this handler's registry is a capability
    miss: the handler stores it back (for a specialised peer) instead of
    dying — a heterogeneous fleet keeps draining what it understands."""
    ts = TupleSpace()
    ts.put(("task", "alien"), TaskDesc("warpdrive", 0, 0, 0).to_wire())
    ts.put(("pre", 0, 0), np.zeros(4, dtype=np.float32))
    ts.put(("task", "ok"), TaskDesc("activation", 0, 0, 0, 0, 0, 0, 4).to_wire())
    stop = threading.Event()
    h = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=256.0,
                time_scale=1e-9, stop_event=stop)
    th = threading.Thread(target=h.run, daemon=True)
    th.start()
    time.sleep(0.3)
    stop.set()
    th.join(timeout=2.0)
    assert h.tasks_done == 1
    assert h.tasks_stored >= 1
    assert ts.count(("task", ANY)) == 1       # the alien task circulates


# --------------------------------------------------- TS garbage bounds
def test_history_caps_and_per_sample_loss_cleanup():
    cfg = CloudConfig(layers=[LayerSpec(16, 16), LayerSpec(16, 1)],
                      n_handlers=2, epochs=1, n_samples=10, task_cap=32.0,
                      pouch_size=64, lr=0.05, time_scale=1e-6,
                      initial_timeout=0.1, fault_plan=FaultPlan(interval=1e9),
                      seed=0, wall_limit=60.0, history_limit=6)
    cloud = ACANCloud(cfg)
    cloud.run()
    ts = cloud.ts
    # per-sample loss tuples are deleted by the program's finish_round
    assert ts.count(("loss", ANY, ANY)) == 0
    # history tuples are capped at history_limit, keeping the newest
    assert ts.count(("thist", ANY, ANY)) <= 6
    steps = sorted(k[1] for k in ts.keys(("losshist", ANY)))
    assert steps == list(range(4, 10))


# --------------------------------------------------- batched execution
def _seeded_space(layers):
    """A TS holding every input any stage of sample 0 could need."""
    rng = np.random.default_rng(7)
    ts = TupleSpace()
    for l, spec in enumerate(layers):
        ts.put(("w", l), rng.standard_normal(
            (spec.n_out, spec.n_in)).astype(np.float32))
        ts.put(("b", l), rng.standard_normal(spec.n_out).astype(np.float32))
        ts.put(("pre", l, 0), rng.standard_normal(
            spec.n_out).astype(np.float32))
        ts.put(("act", l, 0), rng.standard_normal(
            spec.n_out).astype(np.float32))
        ts.put(("dy", l, 0), rng.standard_normal(
            spec.n_out).astype(np.float32))
        ts.put(("gW", l, 0), rng.standard_normal(
            (spec.n_out, spec.n_in)).astype(np.float32))
        ts.put(("gB", l, 0), rng.standard_normal(
            spec.n_out).astype(np.float32))
    ts.put(("x", 0), rng.standard_normal(layers[0].n_in).astype(np.float32))
    ts.put(("label", 0), rng.standard_normal(
        layers[-1].n_out).astype(np.float32))
    return ts


def test_execute_batch_matches_sequential_for_every_stage():
    """Vectorized group execution must write the same tuples as per-task
    execution for every MLP op (forward/activation/loss/backward/
    update), including non-uniform edge-tile shapes."""
    layers = [LayerSpec(16, 16), LayerSpec(16, 1)]
    for protos in prototype_tasks(layers, 0, 0).values():
        tasks = [t for p in protos for t in partition(p, 32.0)]
        ts_seq, ts_batch = _seeded_space(layers), _seeded_space(layers)
        for t in tasks:
            TaskExecutor(ts_seq, lr=0.05).execute(t)
        TaskExecutor(ts_batch, lr=0.05).execute_batch(tasks)
        snap_seq, snap_batch = ts_seq.snapshot(), ts_batch.snapshot()
        assert snap_seq.keys() == snap_batch.keys()
        for k in snap_seq:
            np.testing.assert_allclose(snap_seq[k], snap_batch[k],
                                       rtol=1e-6, atol=1e-7, err_msg=str(k))


def test_execute_batch_heterogeneous_splits_into_groups():
    layers = [LayerSpec(8, 8), LayerSpec(8, 1)]
    ts = _seeded_space(layers)
    mixed = [TaskDesc("forward", 0, 0, 0, 0, 8, 0, 8),
             TaskDesc("activation", 0, 0, 0, 0, 0, 0, 8)]
    TaskExecutor(ts, lr=0.05).execute_batch(mixed)
    assert ts.count(("fpart", 0, 0, 0, 8, 0, 8)) == 1
    assert ts.count(("actpart", 0, 0, 0, 8)) == 1


def test_execute_batch_unmet_precondition_writes_nothing():
    """A group whose inputs are missing is discarded atomically — no
    partial writes land in TS."""
    ts = TupleSpace()
    tasks = partition(TaskDesc("forward", 0, 0, 0, 0, 16, 0, 16), 32.0)
    with pytest.raises(PreconditionUnmet):
        TaskExecutor(ts).execute_batch(tasks)
    assert ts.count(("fpart", ANY, ANY, ANY, ANY, ANY, ANY)) == 0
