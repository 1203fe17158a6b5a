"""The pipelined stage-DAG control plane (PR 5): frontier scheduling
over `stage_deps`, chain-DAG ≡ sequential equivalence, crash-mid-
frontier recovery from the persisted frontier, MoE
per-expert overlap under an exp3-style fault plan, and the satellite
fixes (PouchController revival clamp, HandlerTenant capacity caps)."""

import threading
import time

import numpy as np
import pytest

from repro.core import (ACANCloud, CloudConfig, FaultPlan, LayerSpec,
                        MLPProgram, MoERoutingProgram, PouchController,
                        TupleSpace)
from repro.core.handler import Handler, HandlerTenant, SpeedBox
from repro.core.manager import Manager, ManagerConfig
from repro.core.program import WorkloadProgram
from repro.core.space import ANY, ScopedSpace
from repro.core.tasks import TaskDesc
from repro.programs.mlp import ACTIVATION, stage_dag


# ----------------------------------------------------------- DAG contract
def test_default_stage_deps_is_a_chain():
    prog = MLPProgram([LayerSpec(4, 4), LayerSpec(4, 1)], epochs=1,
                      n_samples=2)
    chain = WorkloadProgram.stage_deps(prog, 0)     # the default impl
    names = prog.stage_names(0)
    assert chain[names[0]] == []
    for prev, cur in zip(names, names[1:]):
        assert chain[cur] == [prev]


def test_mlp_stage_dag_declares_cross_round_update_edges():
    dag = stage_dag(2)
    assert ("upd_0", -1) in dag["fwd_0"]            # prev round's commit
    assert ("upd_1", -1) in dag["fwd_1"]
    assert "act_0" in dag["fwd_1"]
    assert dag["upd_1"] == ["bwd_1"]
    # the update sweep is independent of the next sample's forward: no
    # edge from any fwd/act stage into upd_l
    assert all(not d[0].startswith(("fwd", "act"))
               for d in dag["upd_0"] if isinstance(d, tuple))


def test_unknown_dep_name_fails_loudly():
    class Broken(MLPProgram):
        def stage_deps(self, rnd):
            return {"fwd_0": ["definitely_not_a_stage"]}

    prog = Broken([LayerSpec(4, 4)], epochs=1, n_samples=1)
    mgr = Manager(ts=TupleSpace(), program=prog)
    with pytest.raises(ValueError, match="not a stage"):
        mgr.run()


def test_dependency_cycle_is_a_deadlock_error():
    class Cyclic(MLPProgram):
        def stage_names(self, rnd):
            return ["fwd_0", "upd_0"]

        def stage_deps(self, rnd):
            return {"fwd_0": ["upd_0"], "upd_0": ["fwd_0"]}

    prog = Cyclic([LayerSpec(4, 4)], epochs=1, n_samples=1)
    mgr = Manager(ts=TupleSpace(), program=prog)
    with pytest.raises(RuntimeError, match="deadlock"):
        mgr.run()


# ------------------------------------------- chain ≡ sequential (§6.1 MLP)
@pytest.mark.parametrize("backend", ["local", "sharded"])
def test_pipelined_mlp_trajectory_bit_identical_to_sequential(backend):
    """Acceptance: with max_inflight_stages=1 the frontier scheduler IS
    the sequential scheduler, and because the MLP DAG pins every true
    dependency (including the cross-round upd->fwd edges), a wide
    frontier produces the *bit-identical* §6.1 trajectory too."""
    base = dict(layers=[LayerSpec(16, 16), LayerSpec(16, 1)], n_handlers=3,
                epochs=1, n_samples=6, task_cap=32.0, pouch_size=64,
                lr=0.05, time_scale=1e-6, initial_timeout=0.1,
                fault_plan=FaultPlan(interval=1e9), seed=0, wall_limit=60.0,
                ts_backend=backend)
    res_seq = ACANCloud(CloudConfig(**base, max_inflight_stages=1)).run()
    res_pipe = ACANCloud(CloudConfig(**base, max_inflight_stages=6)).run()
    ls = [l for _, l in res_seq.loss_history]
    lp = [l for _, l in res_pipe.loss_history]
    assert len(ls) == len(lp) == 6
    np.testing.assert_array_equal(np.array(ls), np.array(lp))
    assert res_seq.ledger_ok and res_pipe.ledger_ok


# --------------------------------------------- crash-mid-frontier recovery
class DiamondProgram(WorkloadProgram):
    """a -> (b1 | b2) -> c over two rounds. ``a`` and ``c`` are zero-task
    combine barriers; ``b1``/``b2`` are independent task stages (distinct
    layers -> distinct done patterns). Combine calls and window commits
    are journaled on the (shared) program instance, so a test can assert
    exactly-once semantics across a crash/revival pair."""

    name = "diamond"

    def __init__(self, rounds: int = 2, width: int = 8) -> None:
        self.rounds = rounds
        self.width = width
        self.combines: list[tuple[int, str]] = []
        self.commits: list[int] = []

    def setup(self, ts) -> None:
        for rnd in range(self.rounds):
            for layer in (1, 2):
                if ts.try_read(("pre", layer, rnd)) is None:
                    ts.put(("pre", layer, rnd),
                           np.linspace(-1, 1, self.width).astype(np.float32))

    def n_rounds(self) -> int:
        return self.rounds

    def stage_names(self, rnd):
        return ["a", "b1", "b2", "c"]

    def stage_deps(self, rnd):
        return {"b1": ["a"], "b2": ["a"], "c": ["b1", "b2"]}

    def stage_tasks(self, ts, rnd, stage):
        if stage in ("a", "c"):
            return []
        layer = 1 if stage == "b1" else 2
        return [TaskDesc(ACTIVATION, layer, rnd, rnd, 0, 0, 0, self.width)]

    def combine(self, ts, rnd, stage, mgr) -> None:
        self.combines.append((rnd, stage))
        if stage == "c" and mgr.window.can_commit(0, rnd) \
                and mgr.window.commit(0, rnd):
            self.commits.append(rnd)

    def finish_round(self, ts, rnd) -> None:
        ts.delete(("actpart", ANY, rnd, ANY, ANY))
        ts.delete(("done", ANY, ANY, rnd, ANY, ANY, ANY, ANY, ANY))


def test_crash_with_two_stages_in_flight_resumes_from_frontier():
    """Acceptance: a Manager crashed with >= 2 stages in flight resumes
    from the persisted frontier — the completed stage is NOT redone, the
    in-flight stages are, and every combine/commit happens exactly once."""
    ts = TupleSpace(backend="sharded")
    prog = DiamondProgram(rounds=2)
    cfg = ManagerConfig(task_cap=64.0, initial_timeout=30.0,
                        max_inflight_stages=2)
    mgr = Manager(ts=ts, program=prog, cfg=cfg)
    outcome = []

    def body():
        try:
            mgr.run()
        except Exception as exc:                    # ManagerCrash
            outcome.append(type(exc).__name__)

    th = threading.Thread(target=body, daemon=True)
    th.start()
    deadline = time.monotonic() + 5.0
    while len(mgr._inflight) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(mgr._inflight) == 2                  # b1 AND b2 in flight
    mgr.crash_event.set()
    th.join(timeout=2.0)
    assert not th.is_alive() and outcome == ["ManagerCrash"]
    # 'a' combined once, b1/b2 not combined, frontier persisted with 'a'
    assert prog.combines == [(0, "a")]
    frontier = ts.try_read(("mstate", "frontier"))
    assert frontier is not None
    assert frontier[1]["base"] == 0
    assert [0, "a"] in frontier[1]["completed"]

    # Revival: fresh Manager + a handler finish the job from TS state.
    stop = threading.Event()
    mgr2 = Manager(ts=ts, program=prog, cfg=cfg, stop_event=stop)
    handler = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=64.0,
                      time_scale=1e-9, stop_event=stop)
    threads = [threading.Thread(target=mgr2.run, daemon=True),
               threading.Thread(target=handler.run, daemon=True)]
    for t in threads:
        t.start()
    ts.read(("mstate", "finished"), timeout=30.0)
    stop.set()
    # exactly-once: no (round, stage) combined twice — in particular the
    # frontier-completed 'a' of round 0 was not re-run by the revival —
    # and the §5.4 window committed each round exactly once.
    assert sorted(prog.combines) == sorted(
        (r, s) for r in range(2) for s in ("a", "b1", "b2", "c"))
    assert prog.commits == [0, 1]


# ---------------------------------------- MoE per-expert overlap + faults
def test_moe_per_expert_overlap_under_exp3_plan():
    """The non-regular program with per-expert stages completes under an
    exp3-style p=1.0 plan while the frontier keeps several expert stages
    in flight, with the same exactly-once expert commits."""
    prog = MoERoutingProgram(steps=10, seed=0)
    cfg = CloudConfig(n_handlers=3, task_cap=256.0, pouch_size=64,
                      time_scale=2e-5, initial_timeout=0.1,
                      fault_plan=FaultPlan(
                          interval=0.1, speed_levels=(1.0, 5.0, 10.0),
                          p_speed_change=1.0, p_handler_crash=1.0,
                          p_manager_crash=1.0, seed=1),
                      wall_limit=120.0, max_inflight_stages=4)
    res = ACANCloud(cfg, program=prog).run()
    losses = [l for _, l in res.loss_history]
    assert len(losses) == 10                        # completed all rounds
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    assert res.manager_revivals >= 1
    assert res.handler_revivals >= 1
    assert res.ledger_ok


def test_moe_pipelined_trajectory_matches_sequential():
    base = dict(n_handlers=4, task_cap=128.0, pouch_size=64,
                time_scale=1e-6, initial_timeout=0.1,
                fault_plan=FaultPlan(interval=1e9), wall_limit=60.0)
    seq = ACANCloud(CloudConfig(**base, max_inflight_stages=1),
                    program=MoERoutingProgram(steps=6, seed=0)).run()
    pipe = ACANCloud(CloudConfig(**base, max_inflight_stages=8),
                     program=MoERoutingProgram(steps=6, seed=0)).run()
    ls = [l for _, l in seq.loss_history]
    lp = [l for _, l in pipe.loss_history]
    assert len(ls) == len(lp) == 6
    np.testing.assert_array_equal(np.array(ls), np.array(lp))


# ------------------------------------------------ frontier bookkeeping
def test_finished_run_leaves_empty_frontier_at_n_rounds():
    prog = MLPProgram([LayerSpec(8, 8), LayerSpec(8, 1)], epochs=1,
                      n_samples=3, seed=0)
    cloud = ACANCloud(CloudConfig(
        layers=prog.layers, n_handlers=2, epochs=1, n_samples=3,
        task_cap=32.0, pouch_size=64, lr=0.05, time_scale=1e-6,
        initial_timeout=0.1, fault_plan=FaultPlan(interval=1e9), seed=0,
        wall_limit=60.0, max_inflight_stages=3))
    cloud.run()
    frontier = cloud.spaces[0].try_read(("mstate", "frontier"))[1]
    assert frontier["base"] == 3 and frontier["completed"] == []
    # The swept cursor (PR 9) trails base by at most the rounds finished
    # after the last checkpoint; a revived Manager re-sweeps the gap.
    assert 1 <= frontier["swept"] <= 2
    cursor = cloud.spaces[0].try_read(("mstate", "cursor"))[1]
    assert (cursor["round"], cursor["stage_idx"]) == (3, 0)


# ------------------------------------- PouchController revival (bugfix)
def test_pouch_controller_revive_clamps_and_forgives_one_shortfall():
    pc = PouchController(pouch=100, min_pouch=8)
    for _ in range(12):                             # crash-heavy collapse
        pc.update(False, 1.0)
    assert pc.pouch == pc.min_pouch
    pc.revive(100)
    assert pc.pouch == 100                          # clamped back up
    assert pc.update(False, 1.0) == 100             # first shortfall: grace
    assert pc.update(False, 1.0) < 100              # real load signal again
    # a legitimately GROWN pouch survives revival untouched
    pc2 = PouchController(pouch=300)
    pc2.revive(100)
    assert pc2.pouch == 300


def test_manager_revival_restores_adaptive_pouch():
    """A revived Manager must not inherit a crash-collapsed pouch: the
    persisted size is clamped back to the configured starting point on
    load (the crash-induced barrier timeout was fault, not load)."""
    ts = TupleSpace()
    ts.put(("mstate", "cursor"), {"round": 0, "stage_idx": 0,
                                  "timeout": 0.2, "pouch": 8, "window": {}})
    prog = MLPProgram([LayerSpec(4, 4)], epochs=1, n_samples=1)
    mgr = Manager(ts=ts, program=prog,
                  cfg=ManagerConfig(pouch_size=64, adaptive_pouch=True))
    mgr._load_frontier()
    assert mgr.pouch_ctl.pouch == 64
    assert mgr.pouch_ctl.shrink_grace == 1
    # without adaptive_pouch the persisted value is used verbatim
    mgr2 = Manager(ts=ts, program=prog, cfg=ManagerConfig(pouch_size=64))
    mgr2._load_frontier()
    assert mgr2.pouch_ctl.pouch == 8


# ----------------------------------------- HandlerTenant capacity caps
def test_handler_tenant_max_tasks_caps_per_batch_drain():
    """A namespace capped at max_tasks=1 keeps at most one of that
    tenant's tasks per drained batch — the excess is stored back (tagged)
    for the rest of the fleet — yet everything still completes because
    stored tasks circulate at backoff cadence."""
    ts = TupleSpace(backend="sharded")
    sa, sb = ScopedSpace(ts, "a"), ScopedSpace(ts, "b")
    for space in (sa, sb):
        space.put(("pre", 0, 0), np.zeros(4, dtype=np.float32))
    n_a, n_b = 6, 2
    for j in range(n_a):
        sa.put(("task", f"a{j}"),
               TaskDesc(ACTIVATION, 0, 0, 0, 0, 0, j, j + 1).to_wire())
    for j in range(n_b):
        sb.put(("task", f"b{j}"),
               TaskDesc(ACTIVATION, 0, 0, 0, 0, 0, j, j + 1).to_wire())
    stop = threading.Event()
    h = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=256.0,
                time_scale=1e-9, batch_size=16, store_backoff=0.01,
                stop_event=stop,
                tenants={"a": HandlerTenant(sa, max_tasks=1),
                         "b": HandlerTenant(sb)})
    th = threading.Thread(target=h.run, daemon=True)
    th.start()
    deadline = time.monotonic() + 10.0
    while (sa.count(("done", ANY, ANY, ANY, ANY, ANY, ANY, ANY, ANY)) < n_a
           or sb.count(("done", ANY, ANY, ANY, ANY, ANY, ANY, ANY, ANY))
           < n_b) and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    th.join(timeout=2.0)
    assert sa.count(("done", ANY, ANY, ANY, ANY, ANY, ANY, ANY, ANY)) == n_a
    assert sb.count(("done", ANY, ANY, ANY, ANY, ANY, ANY, ANY, ANY)) == n_b
    # the cap actually bit: capped stores happened, across several drains
    assert h.tasks_capped >= n_a - 1
    assert h.batches_taken > 1
