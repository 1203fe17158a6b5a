"""The readers of the program's own spans: ``spans.program_spans`` on a
recorded CPU profile, ``spans.idle_gaps`` and ``devtrace.summarize`` on a
trace with and without ``acan.`` spans, and the six readers in traced
rehearsals of tiny cells, with the spans and without them (as on a
program that writes none)."""

import functools
import time
import types

import jax
import pytest

import tiny

import devtrace
import harness
import spans
from repro.core import trace

MS = 1e6   # ns
READERS = {"host_device_gb_per_step", "gss_timeouts_per_step",
           "combine_upload_s", "grad_fetch_s",
           "host_device_gb_per_step.faults", "resume_s.faults"}


def test_program_spans_are_the_acan_spans_inside_the_window(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    trace.instant("acan.before")
    trace.instant("bench.window_open")
    with trace.span("acan.jax_sgd.grad.fetch", step=3, micro=1) as sp:
        sp.set_metadata(bytes=64)
    trace.instant("acan.manager.gss_timeout", rnd=3, epoch=1, pending=2,
                  issued=4)
    trace.instant("bench.window_close")
    trace.instant("acan.after")
    jax.profiler.stop_trace()
    got = spans.program_spans(str(tmp_path))
    assert [(s.name, s.ids) for s in got] == [
        ("acan.jax_sgd.grad.fetch", {"step": 3, "micro": 1, "bytes": 64}),
        ("acan.manager.gss_timeout",
         {"rnd": 3, "epoch": 1, "pending": 2, "issued": 4})]
    assert got[0].start_ns <= got[0].end_ns <= got[1].start_ns
    assert spans.program_spans(str(tmp_path / "none")) == ()


def test_resume_ends_at_the_revived_managers_first_gradient(
        tmp_path, monkeypatch, capsys):
    """A gradient of the dead Manager's pouch that a handler finishes
    after the kill is not the resume; a kill with no recovery after it in
    the traced part is left out and logged."""
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    jax.profiler.start_trace(spans.trace_dir())
    trace.instant("bench.window_open")
    trace.instant("acan.fault.fire", manager=1, handlers=1)
    with trace.span("acan.jax_sgd.grad", step=4, micro=0):   # old pouch
        time.sleep(0.01)
    with trace.span("acan.manager.recover", epoch=2):
        time.sleep(0.01)
    with trace.span("acan.jax_sgd.grad", step=4, micro=1):
        time.sleep(0.01)
    trace.instant("acan.fault.fire", manager=1, handlers=1)
    trace.instant("bench.window_close")
    jax.profiler.stop_trace()
    got = spans.program_spans()
    fire = spans.named(got, "acan.fault.fire")[0]
    grad = spans.named(got, "acan.jax_sgd.grad")[1]
    run = types.SimpleNamespace(
        median=functools.partial(harness.Run.median, None))
    capsys.readouterr()
    assert harness.load_reader("resume_s.faults")(run) == pytest.approx(
        (grad.end_ns - fire.start_ns) * 1e-9)
    assert "1 of 2 Manager kills" in capsys.readouterr().err


def recorded(program: bool) -> dict:
    """Window 0..100 ms; device ops 10..40 and 60..70. Host: a grad span
    5..45 and a combine span 40..75 from the benchmark; with ``program``
    the program's grad.params_upload 5..10 and combine.upload 42..58 on
    another thread."""
    host = {"python": [
        ("bench.window_open", 0.0, 0.01 * MS),
        ("bench.grad", 5 * MS, 40 * MS),
        ("bench.combine", 40 * MS, 35 * MS),
        ("bench.window_close", 100 * MS, 0.01 * MS)]}
    if program:
        host["acan-manager"] = [
            ("acan.jax_sgd.grad.params_upload", 5 * MS, 5 * MS),
            ("acan.jax_sgd.combine", 41 * MS, 30 * MS),
            ("acan.jax_sgd.combine.upload", 42 * MS, 16 * MS)]
    return {
        "/host:CPU": host,
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 10 * MS, 30 * MS),
                        ("fusion.2", 60 * MS, 10 * MS)],
            "XLA Modules": [("jit_loss_fn(1)", 10 * MS, 30 * MS),
                            ("jit_sgd_update(2)", 60 * MS, 10 * MS)]}}


def test_summarize_is_the_same_with_program_spans():
    plain = devtrace.summarize(recorded(program=False))
    assert devtrace.summarize(recorded(program=True)) == plain
    assert [g[0] for g in plain["idle_gaps"]] == [
        "host.none", "bench.combine", "bench.grad"]


def test_idle_gaps_are_named_by_the_shortest_span():
    plain = devtrace.summarize(recorded(program=False))["idle_gaps"]
    assert spans.idle_gaps(recorded(program=False)) == plain
    got = spans.idle_gaps(recorded(program=True))
    assert [g[1] for g in got] == pytest.approx([g[1] for g in plain])
    assert [g[0] for g in got] == [
        "host.none", "acan.jax_sgd.combine.upload",
        "acan.jax_sgd.grad.params_upload"]
    assert spans.idle_gaps({"/host:CPU": {}}) is None


def _rehearse(tmp_path, monkeypatch, name, traffic):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    root = tiny.make_root(str(tmp_path), {name: ("tiny-llama", traffic)})
    out = harness.run_cell(harness.load_cell(name, root), 2**33 + 21, 1.5,
                           True, time.perf_counter(), log=lambda m: None)
    assert out["correct"], out["checks"]
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_readers_in_a_traced_rehearsal(tmp_path, monkeypatch):
    got = _rehearse(tmp_path, monkeypatch, "t.sgd", tiny.TRAFFIC)
    assert READERS - set(got) == {"resume_s.faults"}     # nothing is killed
    assert got["host_device_gb_per_step.faults"] \
        == got["host_device_gb_per_step"]
    # About the n_micro grads down, the combine's params and n_micro grads
    # up and its params down, and one param upload, a step, or more.
    from repro.models import model as M
    mcfg = harness.model_config(tiny.CONFIGS["tiny-llama"])
    tree = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: M.init_params(mcfg, jax.random.PRNGKey(0)))))
    n = tiny.TRAFFIC["n_micro"]
    assert got["host_device_gb_per_step"] * 1e9 >= 0.9 * (3 + 2 * n) * tree
    assert got["gss_timeouts_per_step"] >= 0
    assert got["combine_upload_s"] > 0 and got["grad_fetch_s"] > 0


def test_resume_read_under_kills(tmp_path, monkeypatch):
    traffic = dict(tiny.TRAFFIC, fault_plan={
        "interval": 0.3, "p_manager_crash": 1.0, "p_handler_crash": 1.0})
    got = _rehearse(tmp_path, monkeypatch, "t.sgd_kills", traffic)
    assert READERS <= set(got)
    assert 0 < got["resume_s.faults"] < 1.5


def test_readers_find_nothing_without_program_spans(tmp_path, monkeypatch):
    """As on a program that writes no ``acan.`` span: the six readers
    return None, and the run and the other readers go on."""
    import repro.core.faults
    import repro.core.manager
    import repro.programs.jax_sgd
    off = lambda *a, **k: trace._OFF           # noqa: E731
    for mod in (repro.core.manager, repro.programs.jax_sgd):
        monkeypatch.setattr(mod, "span", off)
    for mod in (repro.core.manager, repro.core.faults):
        monkeypatch.setattr(mod, "instant", off)
    traffic = dict(tiny.TRAFFIC, fault_plan={
        "interval": 0.3, "p_manager_crash": 1.0, "p_handler_crash": 1.0})
    got = _rehearse(tmp_path, monkeypatch, "t.sgd_kills", traffic)
    assert not READERS & set(got)
    assert {"grad_runs_per_step", "combine_s", "grad_task_s"} <= set(got)
