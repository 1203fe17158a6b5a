"""How ``ACANCloud.run`` ends: injected crashes are revived, a real error
in an op or a combine fails the run within seconds, and a run stopped by
its wall limit says so."""

import time

import pytest

from repro.configs import get_config
from repro.core import ACANCloud, CloudConfig
from repro.core.cloud import CloudRunError
from repro.core.handler import HandlerCrash
from repro.programs.jax_sgd import JAXSGDProgram


def _program(**kw):
    base = dict(steps=2, n_micro=2, micro_batch=2, seq=16, seed=0)
    base.update(kw)
    return JAXSGDProgram(get_config("smollm_360m", reduced=True), **base)


def _cloud(prog, **kw):
    base = dict(n_handlers=2, handler_batch=1, wall_limit=60.0)
    base.update(kw)
    return ACANCloud(CloudConfig(**base), program=prog)


def _boom(msg):
    def fn(*_a, **_k):
        raise ValueError(msg)
    return fn


@pytest.mark.parametrize("site", ["op", "combine"])
def test_real_error_fails_run_promptly(site):
    prog = _program()
    boom = _boom(f"boom in {site}")
    if site == "op":
        prog.grad_fn = boom
    else:
        prog.sgd_update = boom
    t0 = time.monotonic()
    with pytest.raises(CloudRunError) as ei:
        _cloud(prog).run()
    assert time.monotonic() - t0 < 15.0          # not at the 60 s limit
    assert isinstance(ei.value.__cause__, ValueError)
    assert f"boom in {site}" in str(ei.value)


def test_injected_handler_crash_still_revives():
    prog = _program()
    real = prog.grad_fn
    calls = []

    def crash_once(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            raise HandlerCrash("injected")
        return real(*a, **k)
    prog.grad_fn = crash_once
    res = _cloud(prog).run()
    assert res.finished
    assert res.handler_revivals >= 1
    assert [s for s, _ in res.loss_history] == [0, 1]


def test_wall_limit_stop_is_unfinished():
    # Every task "crashes" before finishing: the job can never complete.
    prog = _program(handler_crash_prob=1.0)
    res = _cloud(prog, wall_limit=1.0).run()
    assert not res.finished
    assert res.loss_history == []
    assert res.reissues >= 1


def test_finished_run_reports_reissues():
    prog = _program(steps=3, handler_crash_prob=0.3)
    res = _cloud(prog).run()
    assert res.finished
    assert len(res.loss_history) == 3
    assert prog.crashes >= 1 and res.reissues >= 1
