"""The program's spans (``repro.core.trace``): a tiny JAX-SGD cloud run
under ``jax.profiler`` writes every ``acan.`` span with its ids, each
child inside its parent; the ``bytes`` on the transfer spans are exactly
those of the trees moved; the GSS-timeout instants count the Manager's
timed-out tasks; and a run with no profiler session writes nothing and
trains the same."""

from __future__ import annotations

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import ACANCloud, CloudConfig, FaultPlan
from repro.models import model as M
from repro.programs.jax_sgd import JAXSGDProgram, nbytes

SPANS = {    # name -> its ids
    "acan.jax_sgd.grad": {"step", "micro"},
    "acan.jax_sgd.grad.params_upload": {"version", "uploaded"},
    "acan.jax_sgd.grad.compute": {"step", "micro", "bytes"},
    "acan.jax_sgd.grad.fetch": {"step", "micro", "bytes"},
    "acan.jax_sgd.combine": {"step"},
    "acan.jax_sgd.combine.gather": {"step"},
    "acan.jax_sgd.combine.upload": {"step", "reused", "bytes"},
    "acan.jax_sgd.combine.update": {"step"},
    "acan.jax_sgd.combine.fetch": {"step", "bytes"},
    "acan.jax_sgd.combine.commit": {"step"},
    "acan.manager.recover": {"epoch"},
    "acan.manager.gss_timeout": {"rnd", "epoch", "pending", "issued"},
    "acan.fault.fire": {"manager", "handlers"},
    "acan.fault.revive": {"role", "index"},
    "acan.model.attention": {"impl", "seq", "heads"},
}
N_MICRO = 2


def _program(steps: int) -> JAXSGDProgram:
    return JAXSGDProgram(get_config("smollm_360m", reduced=True),
                         steps=steps, n_micro=N_MICRO, micro_batch=2,
                         seq=16, seed=3)


def _run(prog, plan: FaultPlan | None = None, **kw):
    cfg = CloudConfig(n_handlers=2, handler_batch=1, wall_limit=120.0,
                      fault_plan=plan or FaultPlan(), **kw)
    res = ACANCloud(cfg, program=prog).run()
    assert res.finished
    return res


def _events(log_dir: str) -> list[dict]:
    """The ``acan.`` host events of the trace under ``log_dir``: name,
    start, end (ns), ids, and the host line (thread) they lie on."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend({"name": e.name, "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns,
                        "ids": dict(e.stats), "line": (plane.name, i)}
                       for e in line.events if e.name.startswith("acan."))
    return out


def _traced(tmp, prog, plan=None, **kw):
    log_dir = str(tmp / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        res = _run(prog, plan, **kw)
    finally:
        jax.profiler.stop_trace()
    return res, _events(log_dir)


@pytest.fixture(scope="module")
def faulty(tmp_path_factory):
    """Kills of the Manager and both handlers every 0.2 s, and a GSS
    timeout far under a gradient call."""
    prog = _program(steps=6)
    res, events = _traced(
        tmp_path_factory.mktemp("faulty"), prog,
        FaultPlan(interval=0.2, p_manager_crash=1.0, p_handler_crash=1.0,
                  seed=5), initial_timeout=0.002)
    return prog, res, events


@pytest.fixture(scope="module")
def clean(tmp_path_factory):
    prog = _program(steps=3)
    res, events = _traced(tmp_path_factory.mktemp("clean"), prog)
    return prog, res, events


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(child, parents) -> bool:
    return any(p["line"] == child["line"] and p["start"] <= child["start"]
               and child["end"] <= p["end"] for p in parents)


def test_every_span_is_written_with_its_ids(faulty):
    _, _, events = faulty
    for name, ids in SPANS.items():
        got = _named(events, name)
        assert got, name
        for e in got:
            assert set(e["ids"]) == ids or (
                # A params upload carries the bytes it moved.
                name == "acan.jax_sgd.grad.params_upload"
                and set(e["ids"]) == ids | {"bytes"}
                and e["ids"]["uploaded"] == 1), (name, e["ids"])
    assert {e["name"] for e in events} == set(SPANS)


def test_attention_instant_names_the_path(faulty):
    """Tracing the gradient program records the attention path it took
    (the jnp chunked one on the CPU) with the sequence and head count."""
    prog, _, events = faulty
    got = _named(events, "acan.model.attention")
    a = prog.cfg.period[0].attn
    assert got and all(e["ids"] == {"impl": "chunked", "seq": 16,
                                    "heads": a.n_heads} for e in got)


def test_ssd_instant_names_the_path(tmp_path):
    """Compiling a Mamba-2 gradient program while a profiler runs records
    the SSD path it took with the sequence, chunk, heads and state size."""
    cfg = get_config("mamba2_2_7b", reduced=True)
    m = cfg.period[0].mamba
    fn = jax.jit(jax.value_and_grad(
        lambda p, b: M.train_loss(p, cfg, b)[0]))
    tok = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        fn.lower(M.abstract_params(cfg),
                 {"tokens": tok, "labels": tok}).compile()
    finally:
        jax.profiler.stop_trace()
    got = _named(_events(log_dir), "acan.model.ssd")
    assert got and all(e["ids"] == {
        "impl": "chunked", "seq": 40, "chunk": m.chunk, "heads": m.n_heads,
        "d_state": m.d_state} for e in got)


def test_each_child_lies_inside_its_parent(faulty):
    _, _, events = faulty
    grads = _named(events, "acan.jax_sgd.grad")
    for e in _named(events, "acan.jax_sgd.grad.params_upload"):
        assert _inside(e, grads)
    for child in ("compute", "fetch"):
        for e in _named(events, f"acan.jax_sgd.grad.{child}"):
            assert _inside(e, [g for g in grads if g["ids"] == {
                "step": e["ids"]["step"], "micro": e["ids"]["micro"]}])
    combines = _named(events, "acan.jax_sgd.combine")
    for child in ("gather", "upload", "update", "fetch", "commit"):
        for e in _named(events, f"acan.jax_sgd.combine.{child}"):
            assert _inside(e, [c for c in combines
                               if c["ids"]["step"] == e["ids"]["step"]])


def test_gss_timeout_instants_count_the_timed_out_tasks(faulty):
    _, res, events = faulty
    marks = _named(events, "acan.manager.gss_timeout")
    assert marks
    assert sum(e["ids"]["pending"] for e in marks) == res.timed_out_tasks
    assert all(0 < e["ids"]["pending"] <= e["ids"]["issued"] for e in marks)
    assert all(e["end"] - e["start"] < 1e6 for e in marks)   # under 1 ms
    assert res.reissues <= res.timed_out_tasks


def test_recover_and_fault_spans_follow_the_kills(faulty):
    _, res, events = faulty
    assert res.manager_revivals >= 1
    recover = _named(events, "acan.manager.recover")
    # One per Manager incarnation, in epoch order.
    assert sorted(e["ids"]["epoch"] for e in recover) == list(
        range(1, res.manager_revivals + 2))
    revive = _named(events, "acan.fault.revive")
    assert sum(e["ids"]["role"] == "manager" for e in revive) \
        == res.manager_revivals
    assert sum(e["ids"]["role"] == "handler" for e in revive) \
        == res.handler_revivals
    fires = _named(events, "acan.fault.fire")
    assert fires and all(e["ids"] == {"manager": 1, "handlers": 1}
                         for e in fires)


def test_span_bytes_under_kills_are_whole_trees(faulty):
    """Each transfer span carries the bytes of the trees it moved, a
    revived handler's param upload and a re-issued gradient too; the
    combine's upload, those of the operands it found no device copy of
    (a revived Manager holds no params on the device)."""
    prog, res, events = faulty
    t = _tree_bytes(prog)
    want = {"acan.jax_sgd.grad.params_upload": t,
            "acan.jax_sgd.grad.compute": nbytes(prog.pipe.batch_at(0)),
            "acan.jax_sgd.grad.fetch": t + 4,            # float32 loss
            "acan.jax_sgd.combine.fetch": t}
    for name, size in want.items():
        got = [e["ids"]["bytes"] for e in _named(events, name)
               if "bytes" in e["ids"]]
        assert got and set(got) == {size}, name
    ups = _named(events, "acan.jax_sgd.combine.upload")
    assert ups and all(0 <= e["ids"]["reused"] <= 1 + N_MICRO
                       and e["ids"]["bytes"]
                       == (1 + N_MICRO - e["ids"]["reused"]) * t
                       for e in ups)
    uploads = _named(events, "acan.jax_sgd.grad.params_upload")
    assert sum(e["ids"]["uploaded"] for e in uploads) >= len(
        {e["ids"]["version"] for e in uploads if e["ids"]["uploaded"]})


def _tree_bytes(prog) -> int:
    shapes = jax.eval_shape(
        lambda: M.init_params(prog.cfg, jax.random.PRNGKey(0)))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def test_span_bytes_count_the_trees_exactly(clean):
    prog, res, events = clean
    t = _tree_bytes(prog)                      # one param or grad tree
    batch = nbytes(prog.pipe.batch_at(0))      # one micro-batch's tokens
    steps = prog.steps
    calls = len(_named(events, "acan.jax_sgd.grad"))
    assert calls >= steps * N_MICRO
    uploads = sum(e["ids"]["uploaded"] for e in
                  _named(events, "acan.jax_sgd.grad.params_upload"))
    assert uploads >= 1
    combines = _named(events, "acan.jax_sgd.combine.upload")
    assert len(combines) == steps
    # The ops' device copies are the combine's operands: it uploads only
    # those it found none of, and the next round's ops read the version
    # it committed on the device.
    assert any(e["ids"]["reused"] == 1 + N_MICRO for e in combines)
    missed = sum(1 + N_MICRO - e["ids"]["reused"] for e in combines)
    # Up: the param versions the ops found no device copy of, each
    # batch, and each combine's missing operands. Down: each gradient
    # with its float32 loss, each combine's new params.
    up = uploads * t + calls * batch + missed * t
    down = calls * (t + 4) + steps * t
    assert sum(e["ids"].get("bytes", 0) for e in events) == up + down
    assert res.timed_out_tasks == sum(
        e["ids"]["pending"] for e in
        _named(events, "acan.manager.gss_timeout"))


def test_no_profiler_session_writes_nothing_and_trains_the_same(
        clean, tmp_path, monkeypatch):
    _, traced, _ = clean
    monkeypatch.chdir(tmp_path)
    prog = _program(steps=3)
    plain = _run(prog)
    assert os.listdir(tmp_path) == []
    assert plain.loss_history == traced.loss_history
    assert len(plain.loss_history) == 3


def test_spans_need_no_jax_in_a_process_without_it():
    """Handler workers and the tuple-space server never import JAX; the
    control plane's spans there are no-ops that do not import it."""
    code = ("import sys\n"
            "import repro.core\n"
            "from repro.core.trace import instant, span\n"
            "with span('acan.x', step=1) as s:\n"
            "    s.set_metadata(bytes=2)\n"
            "instant('acan.y', rnd=0)\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")]
        + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
