"""core/faults.py in isolation: FaultPlan probability firing,
MonitorDaemon.power() accounting with dead/revived handler threads, and
the revival counters — previously covered only indirectly through
end-to-end cloud runs."""

import threading

import numpy as np

from repro.core.faults import FaultPlan, MonitorDaemon
from repro.core.handler import SpeedBox


def _daemon(plan: FaultPlan, n_handlers: int = 2, is_finished=lambda: False,
            make_manager=None, make_handler=None) -> MonitorDaemon:
    return MonitorDaemon(
        plan=plan,
        manager_crash=threading.Event(),
        handler_crashes=[threading.Event() for _ in range(n_handlers)],
        speed_boxes=[SpeedBox(1.0) for _ in range(n_handlers)],
        make_manager_thread=make_manager or (lambda: _live_thread()),
        make_handler_thread=make_handler or (lambda i: _live_thread()),
        is_finished=is_finished,
    )


def _live_thread(started: bool = True) -> threading.Thread:
    """A thread that stays alive until its (daemon-thread) event fires at
    interpreter exit — stands in for a healthy Manager/Handler."""
    th = threading.Thread(target=threading.Event().wait, daemon=True)
    if started:
        th.start()
    return th


def _dead_thread() -> threading.Thread:
    th = threading.Thread(target=lambda: None, daemon=True)
    th.start()
    th.join()
    return th


# ------------------------------------------------------------ fault firing
def test_fire_faults_probability_one_sets_every_event():
    d = _daemon(FaultPlan(p_speed_change=1.0, p_handler_crash=1.0,
                          p_manager_crash=1.0, seed=0))
    d._fire_faults()
    assert d.manager_crash.is_set()
    assert all(ev.is_set() for ev in d.handler_crashes)
    assert d.speed_changes == 1
    assert all(box.get() in (1.0, 5.0, 10.0) for box in d.speed_boxes)


def test_fire_faults_probability_zero_never_fires():
    d = _daemon(FaultPlan(p_speed_change=0.0, p_handler_crash=0.0,
                          p_manager_crash=0.0, seed=0))
    for _ in range(50):
        d._fire_faults()
    assert not d.manager_crash.is_set()
    assert not any(ev.is_set() for ev in d.handler_crashes)
    assert d.speed_changes == 0


def test_fire_faults_intermediate_probability_statistics():
    """p=0.5 with a seeded rng: the manager-crash draw must land well
    inside (and not at either edge of) the binomial range."""
    fired = 0
    for trial in range(200):
        d = _daemon(FaultPlan(p_manager_crash=0.5, seed=trial))
        d._fire_faults()
        fired += d.manager_crash.is_set()
    assert 60 < fired < 140, fired


def test_speed_levels_are_drawn_from_plan():
    d = _daemon(FaultPlan(p_speed_change=1.0, speed_levels=(2.0, 9.0),
                          seed=3), n_handlers=4)
    seen = set()
    for _ in range(30):
        d._fire_faults()
        seen |= {box.get() for box in d.speed_boxes}
    assert seen == {2.0, 9.0}


# ------------------------------------------------------- power accounting
def test_power_sums_speeds_of_live_handlers_only():
    d = _daemon(FaultPlan(), n_handlers=3)
    d.speed_boxes[0].set(1.0)
    d.speed_boxes[1].set(5.0)
    d.speed_boxes[2].set(10.0)
    live0, live2 = _live_thread(), _live_thread()
    d.attach(_live_thread(), [live0, _dead_thread(), live2])
    assert d.power() == 11.0            # the dead 5.0-handler is excluded
    assert d.manager_alive()


def test_power_is_zero_before_attach():
    d = _daemon(FaultPlan(), n_handlers=2)
    assert d.power() == 0.0
    assert not d.manager_alive()


# ------------------------------------------------------- revival counters
def test_revive_replaces_dead_threads_and_counts():
    revived = []
    d = _daemon(FaultPlan(),
                n_handlers=2,
                make_handler=lambda i: (revived.append(i), _live_thread())[1])
    d.attach(_live_thread(), [_dead_thread(), _live_thread()])
    d._revive()
    assert d.handler_revivals == 1
    assert d.manager_revivals == 0      # manager was alive
    assert revived == [0]
    assert all(th.is_alive() for th in d._hthreads)
    d._revive()                         # everything alive now: no-op
    assert d.handler_revivals == 1


def test_dead_manager_is_revived_unless_finished():
    d = _daemon(FaultPlan(), is_finished=lambda: False)
    d.attach(_dead_thread(), [_live_thread(), _live_thread()])
    d._revive()
    assert d.manager_revivals == 1
    assert d.manager_alive()

    # A Manager that is dead BECAUSE the job finished must not be revived.
    d2 = _daemon(FaultPlan(), is_finished=lambda: True)
    d2.attach(_dead_thread(), [_live_thread(), _live_thread()])
    d2._revive()
    assert d2.manager_revivals == 0


# ------------------------------------------------------ multi-manager mode
def test_multi_manager_fire_sets_every_crash_event():
    events = [threading.Event() for _ in range(3)]
    d = MonitorDaemon(
        plan=FaultPlan(p_manager_crash=1.0, seed=0),
        manager_crashes=events,
        handler_crashes=[threading.Event()],
        speed_boxes=[SpeedBox(1.0)],
        make_manager_threads=lambda i: _live_thread(),
        make_handler_thread=lambda i: _live_thread(),
    )
    d._fire_faults()
    assert all(ev.is_set() for ev in events)
    # the singular alias points at manager 0's event
    assert d.manager_crash is events[0]


def test_multi_manager_revival_is_per_tenant():
    made = []
    fin = [False, True]                  # tenant 1 finished, tenant 0 crashed
    d = MonitorDaemon(
        plan=FaultPlan(),
        manager_crashes=[threading.Event(), threading.Event()],
        handler_crashes=[threading.Event()],
        speed_boxes=[SpeedBox(1.0)],
        make_manager_threads=lambda i: (made.append(i), _live_thread())[1],
        make_handler_thread=lambda i: _live_thread(),
        is_manager_finished=lambda i: fin[i],
    )
    d.attach([_dead_thread(), _dead_thread()], [_live_thread()])
    assert not d.manager_alive()
    d._revive()
    assert made == [0]                   # only the unfinished tenant revives
    assert d.manager_revivals == 1
    assert d.manager_revivals_by == [1, 0]
    assert d.manager_alive(0)
    assert not d.manager_alive(1)


def test_daemon_run_fires_on_interval_and_stops():
    """End-to-end daemon loop: with a tiny interval the plan fires at
    least once, revival keeps the fleet populated, and stop_event exits
    the loop promptly."""
    d = _daemon(FaultPlan(interval=0.03, p_speed_change=1.0, seed=1),
                n_handlers=2)
    d.attach(_live_thread(), [_dead_thread(), _live_thread()])
    th = threading.Thread(target=d.run, daemon=True)
    th.start()
    deadline = threading.Event()
    deadline.wait(0.3)
    d.stop_event.set()
    th.join(timeout=2.0)
    assert not th.is_alive()
    assert d.speed_changes >= 2
    assert d.handler_revivals >= 1
    # Both handlers live (one revived), each at a speed of the plan.
    assert np.isfinite(d.power())
    assert {b.get() for b in d.speed_boxes} <= set(d.plan.speed_levels)
    assert d.power() == sum(b.get() for b in d.speed_boxes)


# ------------------------------------------------- per-tenant fault plans
def _tenant_daemon(shared: FaultPlan, plans: dict, n: int = 2,
                   namespaces=("a", "b")) -> MonitorDaemon:
    return MonitorDaemon(
        plan=shared,
        plans=plans,
        namespaces=list(namespaces),
        manager_crashes=[threading.Event() for _ in range(n)],
        handler_crashes=[threading.Event()],
        speed_boxes=[SpeedBox(1.0)],
        make_manager_threads=lambda i: _live_thread(),
        make_handler_thread=lambda i: _live_thread(),
    )


def test_tenant_plan_exempts_manager_from_shared_crash_draw():
    """A tenant with its own plan is crashed only by its own plan: the
    shared p=1.0 draw fires every *other* Manager, and the tenant's own
    p=0.0 plan never fires it."""
    d = _tenant_daemon(FaultPlan(p_manager_crash=1.0, seed=0),
                       {"a": FaultPlan(p_manager_crash=0.0, seed=9)})
    d._fire_faults()
    assert not d.manager_crashes[0].is_set()        # tenant a: own plan
    assert d.manager_crashes[1].is_set()            # tenant b: shared plan
    assert d.manager_crash_firings_by == [0, 1]
    d._fire_tenant_faults(0)                        # a's own p=0.0 draw
    assert not d.manager_crashes[0].is_set()
    assert d.manager_crash_firings_by == [0, 1]


def test_tenant_plan_fires_independently_with_own_seed():
    d = _tenant_daemon(FaultPlan(p_manager_crash=0.0, seed=0),
                       {"a": FaultPlan(p_manager_crash=1.0, seed=7)})
    d._fire_faults()                                # shared plan: nothing
    assert not any(ev.is_set() for ev in d.manager_crashes)
    d._fire_tenant_faults(0)
    assert d.manager_crashes[0].is_set()
    assert not d.manager_crashes[1].is_set()
    assert d.manager_crash_firings_by == [1, 0]
    # tenants without their own plan have no tenant stream at all
    d._fire_tenant_faults(1)
    assert not d.manager_crashes[1].is_set()


def test_tenant_plan_seed_gives_independent_stream():
    """Two tenants with identical p=0.5 plans but different seeds must
    draw independently — same-seed tenants fire in lockstep."""
    fired = {"same": 0, "diff": 0}
    for trial in range(100):
        d_same = _tenant_daemon(
            FaultPlan(), {"a": FaultPlan(p_manager_crash=0.5, seed=trial),
                          "b": FaultPlan(p_manager_crash=0.5, seed=trial)})
        d_same._fire_tenant_faults(0)
        d_same._fire_tenant_faults(1)
        fired["same"] += (d_same.manager_crashes[0].is_set()
                          == d_same.manager_crashes[1].is_set())
        d_diff = _tenant_daemon(
            FaultPlan(), {"a": FaultPlan(p_manager_crash=0.5, seed=trial),
                          "b": FaultPlan(p_manager_crash=0.5,
                                         seed=trial + 5000)})
        d_diff._fire_tenant_faults(0)
        d_diff._fire_tenant_faults(1)
        fired["diff"] += (d_diff.manager_crashes[0].is_set()
                          == d_diff.manager_crashes[1].is_set())
    assert fired["same"] == 100                     # lockstep
    assert 25 < fired["diff"] < 75                  # independent draws


def test_daemon_run_fires_tenant_plans_on_their_own_interval():
    """End-to-end loop: tenant a's 30 ms p=1.0 plan fires repeatedly
    while the shared plan (astronomical interval) never does — so only
    tenant a's Manager accumulates crash firings."""
    d = _tenant_daemon(FaultPlan(interval=1e9, p_manager_crash=1.0, seed=0),
                       {"a": FaultPlan(interval=0.03, p_manager_crash=1.0,
                                       seed=3)})
    d.attach([_live_thread(), _live_thread()], [_live_thread()])
    th = threading.Thread(target=d.run, daemon=True)
    th.start()
    threading.Event().wait(0.3)
    d.stop_event.set()
    th.join(timeout=2.0)
    assert not th.is_alive()
    assert d.manager_crash_firings_by[0] >= 2
    assert d.manager_crash_firings_by[1] == 0
    assert d.manager_crashes[0].is_set()
    assert not d.manager_crashes[1].is_set()
