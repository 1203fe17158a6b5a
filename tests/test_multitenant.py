"""Multi-tenant tuple space (PR 4): namespace-scoped spaces, the shared
handler fleet, per-tenant Manager recovery, and the two ride-along
bugfixes (loss-history None-deref, TimeoutController history growth).

The headline acceptance test runs the paper MLP and the non-regular MoE
program *co-resident on one physical space* under an exp3-style fault
plan: both must complete with correct per-program results, the MLP §6.1
trajectory must stay bit-identical to single-tenant mode, and the
instrumented delete counters must show zero deletes capable of crossing
a namespace.
"""

import threading

import numpy as np
import pytest

from repro.core import (ACANCloud, ANY, CloudConfig, FaultPlan, LayerSpec,
                        Manager, ManagerConfig, MLPProgram, MoERoutingProgram,
                        MultiCloudResult, ScopedSpace, TimeoutController,
                        TSTimeout, TupleSpace)
from repro.core.handler import Handler, HandlerTenant, SpeedBox
from repro.core.space import (DEFAULT_NAMESPACE, NsSubject, as_scoped,
                              key_namespace, scope_key, scope_pattern,
                              task_take_pattern, unscope_key)

BACKEND_SPECS = ["local", "sharded:4"]


@pytest.fixture(params=BACKEND_SPECS)
def ts(request):
    return TupleSpace(backend=request.param)


# ----------------------------------------------------------- scoping layer
def test_scope_key_roundtrip_and_namespace():
    k = ("task", "e1t1")
    sk = scope_key("mlp", k)
    assert isinstance(sk[0], NsSubject)
    assert sk[0].namespace == "mlp" and sk[0].subject == "task"
    assert unscope_key(sk) == k
    assert key_namespace(sk) == "mlp"
    # default namespace is a passthrough
    assert scope_key(DEFAULT_NAMESPACE, k) is k
    assert key_namespace(k) == DEFAULT_NAMESPACE


def test_scoped_views_are_isolated(ts):
    a = ScopedSpace(ts, "a")
    b = ScopedSpace(ts, "b")
    a.put(("task", "t1"), "wa")
    b.put(("task", "t1"), "wb")
    ts.put(("task", "t1"), "raw")
    # same unscoped key, three distinct tuples
    assert a.try_read(("task", ANY))[1] == "wa"
    assert b.try_read(("task", ANY))[1] == "wb"
    assert ts.try_read(("task", "t1"))[1] == "raw"
    assert a.count(("task", ANY)) == 1
    # returned keys are unscoped
    assert a.keys(("task", ANY)) == [("task", "t1")]
    # THE bug class: one tenant's global sweep cannot touch the others
    assert b.delete(("task", ANY)) == 1
    assert a.count(("task", ANY)) == 1
    assert ts.try_read(("task", "t1")) is not None
    # predicate subjects stay namespace-pinned
    assert a.count((lambda s: s == "task", ANY)) == 1
    # take is namespaced and returns unscoped keys
    k, v = a.take_batch(("task", ANY), 8)[0]
    assert (k, v) == (("task", "t1"), "wa")
    assert a.count(("task", ANY)) == 0


def test_plain_tuple_subject_cannot_alias_scoped_subject(ts):
    """NsSubject equality is strict: a raw key whose subject is the
    plain tuple ("mlp", "task") must not overwrite, match, or delete
    tenant mlp's scoped task bucket (which would corrupt the tenant
    while the delete audit attributes it to a fixed subject)."""
    assert NsSubject("mlp", "task") != ("mlp", "task")
    assert ("mlp", "task") != NsSubject("mlp", "task")
    assert NsSubject("mlp", "task") == NsSubject("mlp", "task")
    mlp = ScopedSpace(ts, "mlp")
    mlp.put(("task", "t1"), "scoped")
    ts.put((("mlp", "task"), "t1"), "raw")          # same-looking raw key
    assert mlp.try_read(("task", "t1"))[1] == "scoped"   # not overwritten
    assert ts.delete(((("mlp", "task")), ANY)) == 1      # removes raw only
    assert mlp.count(("task", ANY)) == 1


def test_scope_helpers_edge_cases(ts):
    from repro.core.space import match, scope_pattern
    # non-tuple / empty keys pass through untouched for the backend's
    # canonical validate_key error, never wrapped
    assert scope_key("a", "not-a-tuple") == "not-a-tuple"
    assert scope_key("a", ()) == ()
    assert scope_pattern("a", ()) == ()
    assert unscope_key(()) == ()
    assert key_namespace(()) == DEFAULT_NAMESPACE
    # a callable (predicate) subject stays namespace-pinned AND keeps the
    # inner predicate's verdict
    a = ScopedSpace(ts, "a")
    a.put(("task", "t1"), "wa")
    ScopedSpace(ts, "b").put(("task", "t1"), "wb")
    hit = scope_pattern("a", (lambda s: s == "task", ANY))
    miss = scope_pattern("a", (lambda s: s == "done", ANY))
    assert match(hit, scope_key("a", ("task", "t1")))
    assert not match(hit, scope_key("b", ("task", "t1")))
    assert not match(hit, ("task", "t1"))       # raw key: other tenant
    assert not match(miss, scope_key("a", ("task", "t1")))
    assert a.count((lambda s: s == "task", ANY)) == 1
    assert a.count((lambda s: s == "done", ANY)) == 0
    # ANY subject in a scoped view widens within the namespace only
    assert a.count((ANY, ANY)) == 1
    assert a.take_batch((ANY, ANY), 8)[0] == (("task", "t1"), "wa")


def test_scoped_try_get_and_put_many_roundtrip(ts):
    a, b = ScopedSpace(ts, "a"), ScopedSpace(ts, "b")
    a.put_many([(("act", i), i * 10) for i in range(3)])
    assert b.try_get(("act", ANY)) is None
    k, v = a.try_get(("act", 1))
    assert (k, v) == (("act", 1), 10)            # returned key unscoped
    assert a.count(("act", ANY)) == 2            # try_get was destructive
    assert ts.count(("act", ANY)) == 0           # raw view sees nothing


def test_scoped_mstate_cursors_do_not_collide(ts):
    a, b = ScopedSpace(ts, "a"), ScopedSpace(ts, "b")
    a.put(("mstate", "cursor"), {"round": 3})
    b.put(("mstate", "cursor"), {"round": 7})
    assert a.try_read(("mstate", "cursor"))[1]["round"] == 3
    assert b.try_read(("mstate", "cursor"))[1]["round"] == 7
    a.delete(("mstate", "cursor"))
    assert b.try_read(("mstate", "cursor"))[1]["round"] == 7


def test_scoped_wait_count_and_snapshot(ts):
    a, b = ScopedSpace(ts, "a"), ScopedSpace(ts, "b")
    for i in range(3):
        a.put(("done", i), "h")
    b.put(("done", 99), "h")
    assert a.wait_count(("done", ANY), 3, timeout=1.0) == 3
    with pytest.raises(TSTimeout):
        a.wait_count(("done", ANY), 4, timeout=0.05)
    assert set(a.snapshot()) == {("done", 0), ("done", 1), ("done", 2)}
    assert set(b.snapshot()) == {("done", 99)}


def test_scoping_is_flat_not_nested(ts):
    a = ScopedSpace(ts, "a")
    rescoped = ScopedSpace(a, "b")          # re-scopes from the root
    rescoped.put(("x", 1), "v")
    assert ScopedSpace(ts, "b").try_read(("x", 1))[1] == "v"
    assert a.try_read(("x", ANY)) is None
    assert a.scoped("b").try_read(("x", 1))[1] == "v"
    assert as_scoped(ts, "") is ts


def test_task_take_pattern_spans_namespaces(ts):
    from repro.core.space import match
    pat = task_take_pattern()
    assert match(pat, ("task", "t1"))
    assert match(pat, scope_key("mlp", ("task", "t1")))
    assert not match(pat, ("done", "t1"))
    sel = task_take_pattern({"mlp"})
    assert match(sel, scope_key("mlp", ("task", "t1")))
    assert not match(sel, scope_key("moe", ("task", "t1")))
    assert not match(sel, ("task", "t1"))   # default ns not selected
    # end-to-end: the fleet pattern drains across namespaces FIFO
    ScopedSpace(ts, "a").put(("task", "t1"), "wa")
    ScopedSpace(ts, "b").put(("task", "t1"), "wb")
    batch = ts.take_batch(task_take_pattern(), 8, timeout=0.5)
    assert [v for _, v in batch] == ["wa", "wb"]
    assert {key_namespace(k) for k, _ in batch} == {"a", "b"}


# --------------------------------------------------- manager epoch in tids
def test_manager_epoch_persists_and_prefixes_tids(ts):
    prog = MLPProgram([LayerSpec(4, 4), LayerSpec(4, 1)], epochs=1,
                      n_samples=1, seed=0)
    space = ScopedSpace(ts, "mlp")
    stop = threading.Event()
    h = Handler(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=64.0,
                time_scale=1e-9, stop_event=stop,
                tenants={"mlp": HandlerTenant(space, prog.registry)})
    th = threading.Thread(target=h.run, daemon=True)
    th.start()
    Manager(ts=space, program=prog,
            cfg=ManagerConfig(task_cap=64.0, initial_timeout=5.0)).run()
    assert space.try_read(("mstate", "epoch"))[1] == 1
    # a "revived" Manager on the same space draws the next epoch, so its
    # fresh task_seq can never re-mint a predecessor's tid
    space2 = ScopedSpace(ts, "mlp")
    prog2 = MLPProgram([LayerSpec(4, 4), LayerSpec(4, 1)], epochs=1,
                       n_samples=1, seed=0)
    m2 = Manager(ts=space2, program=prog2,
                 cfg=ManagerConfig(task_cap=64.0, initial_timeout=5.0))
    m2._bump_epoch()
    assert m2.epoch == 2
    m2._issue(prog2.stage_tasks(space2, 0, "fwd_0"))
    tids = [k[1] for k in space2.keys(("task", ANY))]
    assert tids and all(t.startswith("e2t") for t in tids)
    stop.set()
    th.join(timeout=2.0)


# ------------------------------------------- co-residency, the shared fleet
def _base(**kw):
    base = dict(layers=[LayerSpec(16, 16), LayerSpec(16, 1)], n_handlers=3,
                epochs=1, n_samples=6, task_cap=32.0, pouch_size=64,
                lr=0.05, time_scale=1e-6, initial_timeout=0.1,
                fault_plan=FaultPlan(interval=1e9), seed=0, wall_limit=120.0)
    base.update(kw)
    return CloudConfig(**base)


def _programs(cfg, moe_steps=8):
    return [MLPProgram(cfg.layers, epochs=cfg.epochs,
                       n_samples=cfg.n_samples, seed=cfg.seed),
            MoERoutingProgram(steps=moe_steps, seed=0)]


@pytest.mark.parametrize("backend", BACKEND_SPECS)
def test_two_programs_one_space_shared_fleet(backend):
    """MLP + MoE co-resident: both complete, per-program results are
    independent, and the MLP trajectory is bit-identical to the
    single-tenant run of the same config."""
    single = ACANCloud(_base(ts_backend=backend)).run()
    ref = [l for _, l in single.loss_history]

    cfg = _base(ts_backend=f"instrumented:{backend}")
    cloud = ACANCloud(cfg, programs=_programs(cfg))
    multi = cloud.run()
    assert isinstance(multi, MultiCloudResult)
    assert set(multi.per_program) == {"mlp", "moe_routing"}
    mlp_losses = [l for _, l in multi.per_program["mlp"].loss_history]
    moe_losses = [l for _, l in multi.per_program["moe_routing"].loss_history]
    assert mlp_losses == ref                      # bit-identical
    assert len(moe_losses) == 8
    assert np.mean(moe_losses[-3:]) < np.mean(moe_losses[:3])
    assert multi.ledger_ok
    # zero deletes capable of crossing a namespace: no widened-subject
    # deletes, and nothing was ever removed under an unscoped task subject
    dm = cloud.ts.backend.delete_metrics()
    assert cloud.ts.stats()["instr_widened_deletes"] == 0
    assert dm.get("task", {"removed": 0})["removed"] == 0
    # each tenant's own sweeps did run, scoped to its namespace
    assert NsSubject("mlp", "task") in dm
    assert NsSubject("moe_routing", "task") in dm


def test_cotenants_complete_under_exp3_fault_plan():
    """Acceptance: co-resident MLP + MoE under an exp3-style plan (every
    Manager and all Handlers crash each interval with p=1.0, speeds
    re-drawn 1:5:10) — both programs complete via revival, the MLP
    trajectory still matches single-tenant bit-for-bit, and no delete
    could cross a namespace."""
    plan = FaultPlan(interval=0.1, speed_levels=(1.0, 5.0, 10.0),
                     p_speed_change=1.0, p_handler_crash=1.0,
                     p_manager_crash=1.0, seed=1)
    single = ACANCloud(_base()).run()
    ref = [l for _, l in single.loss_history]

    cfg = _base(ts_backend="instrumented:local", fault_plan=plan,
                time_scale=2e-5)
    cloud = ACANCloud(cfg, programs=_programs(cfg))
    multi = cloud.run()
    mlp = multi.per_program["mlp"]
    moe = multi.per_program["moe_routing"]
    assert [l for _, l in mlp.loss_history] == ref
    assert len(moe.loss_history) == 8             # completed despite crashes
    assert multi.manager_revivals >= 1
    assert multi.handler_revivals >= 1
    assert mlp.manager_revivals + moe.manager_revivals == multi.manager_revivals
    assert cloud.ts.stats()["instr_widened_deletes"] == 0
    assert cloud.ts.backend.delete_metrics().get(
        "task", {"removed": 0})["removed"] == 0
    assert multi.ledger_ok


def test_independent_cursor_recovery_per_tenant(ts):
    """Crashing ONE tenant's Manager mid-run leaves the other tenant's
    cursor/epoch untouched; the revived Manager resumes from its own
    namespace and both complete."""
    progs = {
        "a": MLPProgram([LayerSpec(8, 8), LayerSpec(8, 1)], epochs=1,
                        n_samples=4, seed=0),
        "b": MLPProgram([LayerSpec(8, 8), LayerSpec(8, 1)], epochs=1,
                        n_samples=4, seed=1),
    }
    spaces = {ns: ScopedSpace(ts, ns) for ns in progs}
    stop = threading.Event()
    crash_a = threading.Event()
    handlers = []
    for i in range(2):
        h = Handler(ts=ts, name=f"h{i}", speed=SpeedBox(1.0), capacity=64.0,
                    time_scale=1e-6, stop_event=stop,
                    tenants={ns: HandlerTenant(spaces[ns], p.registry)
                             for ns, p in progs.items()})
        th = threading.Thread(target=h.run, daemon=True)
        th.start()
        handlers.append(th)

    def run_mgr(ns, crash_event):
        mgr = Manager(ts=spaces[ns], program=progs[ns],
                      cfg=ManagerConfig(task_cap=64.0, initial_timeout=0.2),
                      crash_event=crash_event, stop_event=stop)
        try:
            mgr.run()
        except Exception:
            pass

    crash_a.set()                                 # A dies on its first check
    ta = threading.Thread(target=run_mgr, args=("a", crash_a), daemon=True)
    tb = threading.Thread(target=run_mgr, args=("b", threading.Event()),
                          daemon=True)
    ta.start(); tb.start()
    ta.join(timeout=30.0)
    assert not ta.is_alive()                      # A crashed
    # B's namespace must be unaffected by A's death; revive A and finish.
    ta2 = threading.Thread(target=run_mgr, args=("a", threading.Event()),
                           daemon=True)
    ta2.start()
    ta2.join(timeout=60.0); tb.join(timeout=60.0)
    assert spaces["a"].try_read(("mstate", "finished")) is not None
    assert spaces["b"].try_read(("mstate", "finished")) is not None
    # per-tenant epochs: A ran twice, B once
    assert spaces["a"].try_read(("mstate", "epoch"))[1] == 2
    assert spaces["b"].try_read(("mstate", "epoch"))[1] == 1
    # trajectories are the tenants' own (different seeds -> different data)
    la = [v for _, v in sorted(
        (k[1], spaces["a"].try_read(k)[1])
        for k in spaces["a"].keys(("losshist", ANY)))]
    lb = [v for _, v in sorted(
        (k[1], spaces["b"].try_read(k)[1])
        for k in spaces["b"].keys(("losshist", ANY)))]
    assert len(la) == 4 and len(lb) == 4 and la != lb
    stop.set()
    for th in handlers:
        th.join(timeout=2.0)


# ------------------------------------------------------ satellite bugfixes
def test_collect_survives_vanishing_history_tuple():
    """Regression (cloud.py loss-history None-deref): a losshist tuple
    listed by keys() can be trimmed before try_read — collection must
    skip it, not crash on None[1]."""
    cfg = _base()
    cloud = ACANCloud(cfg, programs=[MLPProgram(
        cfg.layers, epochs=1, n_samples=4, seed=0)])
    res = cloud.run()
    space = cloud.spaces[0]

    class Vanishing:
        """Space view whose try_read loses each losshist key once."""

        def __init__(self, inner):
            self._inner = inner
            self._dropped = set()

        def keys(self, pattern):
            return self._inner.keys(pattern)

        def try_read(self, pattern):
            if (pattern[0] in ("losshist", "thist")
                    and pattern not in self._dropped):
                self._dropped.add(pattern)
                return None
            return self._inner.try_read(pattern)

    class Daemon:
        manager_revivals_by = [0]
        handler_revivals = 0
        speed_changes = 0

    cloud.spaces[0] = Vanishing(space)
    try:
        res2 = cloud._collect(0, Daemon(), 0.0)
    finally:
        cloud.spaces[0] = space
    # every try_read returned None exactly once -> empty histories, no crash
    assert res2.loss_history == [] and res2.timeout_history == []
    assert len(res.per_program["mlp"].loss_history) == 4


def test_timeout_controller_history_is_capped():
    """Regression (gss.py unbounded growth): history must not exceed
    history_limit, and the Manager wires ManagerConfig.history_limit in."""
    tc = TimeoutController(history_limit=5)
    for i in range(50):
        tc.update(True, 0.01, 1.0)
    assert len(tc.history) == 5
    tc0 = TimeoutController(history_limit=0)      # 0 = unbounded
    for _ in range(20):
        tc0.update(False, 0.01, 0.5)
    assert len(tc0.history) == 20
    mgr = Manager(ts=TupleSpace(), program=MLPProgram(
        [LayerSpec(4, 4)], epochs=1, n_samples=1),
        cfg=ManagerConfig(history_limit=7))
    assert mgr.controller.history_limit == 7


def test_adaptive_pouch_grows_and_shrinks_and_persists():
    from repro.core import PouchController
    pc = PouchController(pouch=100)
    assert pc.update(True, 1.0) > 100             # full+done -> grow
    assert PouchController(pouch=100).update(False, 1.0) < 100
    # Manager wiring: adaptive runs complete and checkpoint the pouch size
    cfg = _base(adaptive_pouch=True, pouch_size=8)
    cloud = ACANCloud(cfg, program=MLPProgram(
        cfg.layers, epochs=1, n_samples=4, seed=0))
    res = cloud.run()
    assert len(res.loss_history) == 4
    cursor = cloud.spaces[0].try_read(("mstate", "cursor"))[1]
    assert cursor["pouch"] >= 1                   # persisted for revival


def test_per_tenant_fault_plans_crash_only_the_planned_tenant():
    """CloudConfig.fault_plans: tenant-scoped crash plans ride the same
    daemon — only the MoE tenant's Manager is crashed (on its own
    seed/interval), the MLP tenant runs fault-free and stays
    bit-identical to the single-tenant reference, and the firing stats
    are accounted per tenant."""
    single = ACANCloud(_base()).run()
    ref = [l for _, l in single.loss_history]

    cfg = _base(
        time_scale=2e-5,
        fault_plan=FaultPlan(interval=1e9),       # shared plan: inert
        fault_plans={"moe_routing": FaultPlan(interval=0.1,
                                              p_manager_crash=1.0, seed=2)})
    cloud = ACANCloud(cfg, programs=_programs(cfg))
    multi = cloud.run()
    mlp = multi.per_program["mlp"]
    moe = multi.per_program["moe_routing"]
    assert [l for _, l in mlp.loss_history] == ref
    assert len(moe.loss_history) == 8             # completed via revivals
    assert mlp.manager_revivals == 0              # never crashed
    assert moe.manager_revivals >= 1
    assert multi.handler_revivals == 0            # fleet untouched


def test_per_tenant_config_keys_must_name_real_namespaces():
    """A typo'd (or single-program-mode) fault_plans/tenant_caps key must
    fail loudly at construction, not be silently inert."""
    cfg = _base(fault_plans={"mlp": FaultPlan(p_manager_crash=1.0)})
    with pytest.raises(ValueError, match="unknown namespaces"):
        ACANCloud(cfg)                            # single-program: ns ""
    cfg2 = _base(tenant_caps={"moe-routing": 2})  # typo for moe_routing
    with pytest.raises(ValueError, match="moe-routing"):
        ACANCloud(cfg2, programs=_programs(cfg2))
    # correctly-keyed maps construct fine
    cfg3 = _base(tenant_caps={"moe_routing": 2})
    ACANCloud(cfg3, programs=_programs(cfg3))


def test_zero_tenant_cap_is_rejected():
    cfg = _base(tenant_caps={"moe_routing": 0})
    with pytest.raises(ValueError, match="livelock"):
        ACANCloud(cfg, programs=_programs(cfg))
