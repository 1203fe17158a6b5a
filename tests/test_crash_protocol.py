"""PR 9 crash-recovery protocol units: the ``_unstore_if_stale``
compensation on store re-put paths, the Manager's persisted ``swept``
cursor and post-checkpoint re-sweep, and deterministic end-to-end pins
for the crash windows PR 9 closed (the capability-miss store re-put and
the delete-free commit path).
"""

import sys
import threading
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from repro.core.executor import TaskExecutor  # noqa: E402
from repro.core.handler import Handler, SpeedBox, _TenantRT  # noqa: E402
from repro.core.manager import Manager, ManagerConfig  # noqa: E402
from repro.core.program import ensure_builtin_ops  # noqa: E402
from repro.core.space import (CrashPointFired, CrashSpec,  # noqa: E402
                              TupleSpace, find_crashpoint)
from repro.core.tasks import TaskDesc  # noqa: E402
from repro.programs.mlp import LayerSpec, MLPProgram  # noqa: E402


def _handler(ts, **kw):
    base = dict(ts=ts, name="h0", speed=SpeedBox(1.0), capacity=16.0)
    base.update(kw)
    return Handler(**base)


def _rt(ts):
    reg = ensure_builtin_ops()
    return _TenantRT(ts, reg, TaskExecutor(ts, lr=0.02, registry=reg))


def _task(step):
    return TaskDesc(op="fwd", layer=0, data_id=0, step=step,
                    in_lo=0, in_hi=4, out_lo=0, out_hi=4)


# ------------------------------------------------- _unstore_if_stale units
def test_unstore_removes_stale_identity_matched_reput():
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 5, "completed": []})
    h, rt = _handler(ts), _rt(ts)
    value = ("wire", "h0")
    ts.put(("task", "t1"), value)
    h._unstore_if_stale(("task", "t1"), value, _task(step=1), rt)
    assert ts.try_read(("task", "t1")) is None
    assert h.tasks_fenced == 1


def test_unstore_keeps_live_round_reput():
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 2, "completed": []})
    h, rt = _handler(ts), _rt(ts)
    value = ("wire", "h0")
    ts.put(("task", "t1"), value)
    h._unstore_if_stale(("task", "t1"), value, _task(step=2), rt)
    assert ts.try_read(("task", "t1")) is not None
    assert h.tasks_fenced == 0


def test_unstore_token_guard_spares_fresh_reissue():
    """A Manager re-issue under the same tid is a bare (untagged) wire
    string — the stale handler's tokened compensation must not delete
    it. Ownership is decided by VALUE (the ``(wire, name, nonce)``
    token), not object identity, which never matches over a
    RemoteBackend (every read-back is a fresh unpickled copy)."""
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 5, "completed": []})
    h, rt = _handler(ts), _rt(ts)
    ours = h._store_value("wire")
    ts.put(("task", "t1"), "wire")   # fresh re-issue: untagged
    h._unstore_if_stale(("task", "t1"), ours, _task(step=1), rt)
    assert ts.try_read(("task", "t1"))[1] == "wire"
    assert h.tasks_fenced == 0


def test_unstore_token_guard_spares_other_incarnations_reput():
    """Same handler NAME, different incarnation (a daemon-revived
    worker): the nonce differs, so the old incarnation's compensation
    leaves the new incarnation's re-put alone."""
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 5, "completed": []})
    h, rt = _handler(ts), _rt(ts)
    ours = h._store_value("wire")
    theirs = _handler(ts)._store_value("wire")   # fresh salt, same name
    assert ours != theirs
    ts.put(("task", "t1"), theirs)
    h._unstore_if_stale(("task", "t1"), ours, _task(step=1), rt)
    assert ts.try_read(("task", "t1"))[1] == theirs
    assert h.tasks_fenced == 0


def test_unstore_token_matches_across_serialization():
    """The PR 10 process-fleet case the old identity guard silently
    broke on: the read-back is a pickle round-trip of our own re-put —
    a different object with the same token — and MUST still be
    compensated, or stale tasks leak past shutdown in the process
    fleet."""
    import pickle
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 5, "completed": []})
    h, rt = _handler(ts), _rt(ts)
    ours = h._store_value("wire")
    copy = pickle.loads(pickle.dumps(ours))
    assert copy == ours and copy is not ours
    ts.put(("task", "t1"), copy)
    h._unstore_if_stale(("task", "t1"), ours, _task(step=1), rt)
    assert ts.try_read(("task", "t1")) is None
    assert h.tasks_fenced == 1


def test_unstore_finished_flag_fences_every_step():
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "finished"), True)
    h, rt = _handler(ts), _rt(ts)
    value = ("wire", "h0")
    ts.put(("task", "t1"), value)
    h._unstore_if_stale(("task", "t1"), value, _task(step=10 ** 9), rt)
    assert ts.try_read(("task", "t1")) is None


def test_unstore_noop_without_rt_or_task():
    ts = TupleSpace(backend="sharded")
    h = _handler(ts)
    value = ("wire", "h0")
    ts.put(("task", "t1"), value)
    h._unstore_if_stale(("task", "t1"), value, None, _rt(ts))
    h._unstore_if_stale(("task", "t1"), value, _task(step=0), None)
    assert ts.try_read(("task", "t1")) is not None


# ------------------------------------------------------- _undo_stale units
def test_undo_stale_deletes_own_writes_across_serialization():
    """Orphan-partial compensation over the wire: the read-back of our
    result write is an unpickled ndarray copy — content-equal, not
    identical — and must still be undone (the process-fleet leak the
    identity guard caused)."""
    import pickle

    import numpy as np
    ts = TupleSpace(backend="sharded")
    h, rt = _handler(ts), _rt(ts)
    ours = np.arange(6.0)
    stored = pickle.loads(pickle.dumps(ours))
    ts.put(("fpart", 0, 1, 0, 4), stored)
    h._undo_stale(rt, [_task(step=1)], [(("fpart", 0, 1, 0, 4), ours)])
    assert ts.try_read(("fpart", 0, 1, 0, 4)) is None
    assert h.tasks_fenced == 1


def test_undo_stale_spares_later_rounds_rewrite():
    """A later round legitimately re-wrote the same step-less key with
    DIFFERENT content (new weights → new partials): not ours, stays."""
    import numpy as np
    ts = TupleSpace(backend="sharded")
    h, rt = _handler(ts), _rt(ts)
    ours = np.arange(6.0)
    theirs = np.arange(6.0) + 1.0
    ts.put(("fpart", 0, 1, 0, 4), theirs)
    h._undo_stale(rt, [_task(step=1)], [(("fpart", 0, 1, 0, 4), ours)])
    hit = ts.try_read(("fpart", 0, 1, 0, 4))
    assert hit is not None and hit[1][0] == 1.0


# ----------------------------------------------- frontier ``swept`` cursor
def _manager(ts):
    prog = MLPProgram(layers=[LayerSpec(4, 1)], epochs=1, n_samples=2)
    return Manager(ts=ts, program=prog, cfg=ManagerConfig(),
                   stop_event=threading.Event())


def test_load_frontier_reads_swept_cursor():
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "cursor"), {"round": 3, "stage_idx": 0,
                                  "timeout": 0.25, "pouch": 10,
                                  "window": {}})
    ts.put(("mstate", "frontier"), {"base": 3, "swept": 1, "completed": []})
    m = _manager(ts)
    m._load_frontier()
    assert m._base == 3 and m._swept == 1


def test_load_frontier_legacy_checkpoint_reads_fully_swept():
    """Pre-PR-9 checkpoints carry no ``swept`` — under the old protocol
    cleanup ran before the checkpoint, so everything below base IS
    swept; the revived Manager must not re-sweep (deletes are
    idempotent, but the re-sweep would be wasted work every revival)."""
    ts = TupleSpace(backend="sharded")
    ts.put(("mstate", "frontier"), {"base": 3, "completed": []})
    m = _manager(ts)
    m._load_frontier()
    assert m._base == 3 and m._swept == 2


def test_load_frontier_absent_means_fresh_start():
    ts = TupleSpace(backend="sharded")
    m = _manager(ts)
    m._load_frontier()
    assert m._base == 0 and m._swept == -1


def test_checkpoint_persists_swept():
    ts = TupleSpace(backend="sharded")
    m = _manager(ts)
    m._base, m._swept = 4, 2
    m._checkpoint()
    fr = ts.try_read(("mstate", "frontier"))[1]
    assert fr["base"] == 4 and fr["swept"] == 2


# ------------------------------------------------ deterministic e2e pins
#: The event loop's capability-miss store re-put (an unserved namespace,
#: an unknown op or a too-big task goes back to the space, tagged).
CAPABILITY_MISS_SITE = "handler:handler.Handler._run_event:put[?]#2"


def test_event_store_reput_crash_leaves_task_recoverable():
    """The handler's capability-miss store re-put. Crash *after* the put
    (before the compensation ran): the task tuple is back in TS, so a
    revived handler simply re-takes it — nothing is lost and nothing
    leaks."""
    from tools.crash_lint import site_registry
    (site,) = [s for s in site_registry()
               if s.site_id == CAPABILITY_MISS_SITE]
    ts = TupleSpace(backend="crashpoint+sharded")
    cp = find_crashpoint(ts.backend)
    cp.arm(CrashSpec(site_id=site.site_id, role="handler", path=site.path,
                     line=site.line, end_line=site.end_line))
    # An op no registry knows: a capability miss, so the event loop takes
    # the task and stores it straight back — traversing the armed site.
    ts.put(("task", "t1"), TaskDesc(op="exotic", layer=0, data_id=0,
                                    step=0).to_wire())
    stop = threading.Event()
    h = _handler(ts, stop_event=stop)
    died = []

    def body():
        try:
            h.run()
        except CrashPointFired:
            died.append(True)

    th = threading.Thread(target=body, daemon=True)
    th.start()
    th.join(timeout=10.0)
    stop.set()
    assert died == [True], "armed store site never fired"
    assert len(cp.firings) == 1
    assert cp.firings[0]["site"] == site.site_id
    # when="after": the re-put landed before the crash — the task tuple
    # survives, tagged by the storing handler, for the next incarnation.
    hit = ts.try_read(("task", "t1"))
    assert hit is not None
    wire, stored_by, _nonce = hit[1]
    assert TaskDesc.from_wire(wire).op == "exotic" and stored_by == "h0"


def test_commit_and_finish_round_sites_recover_via_sweep():
    """End-to-end pins for the satellite-6 fixes: crashing right after
    the weight commit re-put and mid ``finish_round`` cleanup must
    recover to a bit-identical run (post-checkpoint re-sweep + plain
    re-puts instead of delete+put absence windows)."""
    from tools.crash_sweep import sweep, sweep_sites
    want = {
        "manager:mlp.MLPProgram._commit_update:put[w]#0",
        "manager:mlp.MLPProgram.finish_round:delete[done]#0",
    }
    sites = [s for s in sweep_sites() if s.site_id in want]
    assert {s.site_id for s in sites} == want
    results = sweep(sites, backends=("crashpoint+checked+sharded",),
                    verbose=False)
    for r in results:
        assert r.reached, r.site_id
        assert r.ok, (r.site_id, r.failures)
