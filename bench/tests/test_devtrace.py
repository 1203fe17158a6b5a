"""The trace reduction on a small recorded trace."""

import pytest

import tiny  # noqa: F401 — puts bench/lib on the path

import devtrace

MS = 1e6   # ns


def recorded():
    """Window 0..100 ms. Device ops 10..40 (a while holding 10..30 and
    30..40), 60..70;
    a grad program 10..40 and an update program 60..70. Host: a combine
    span 40..60 (inside a longer grad span 35..65) and nothing 70..100."""
    return {
        "/host:CPU": {"python": [
            ("bench.window_open", 0.0, 0.01 * MS),
            ("bench.grad", 35 * MS, 30 * MS),
            ("bench.combine", 40 * MS, 20 * MS),
            ("bench.window_close", 100 * MS, 0.01 * MS),
            ("other", 70 * MS, 20 * MS),
        ]},
        "/device:TPU:0": {
            "XLA Ops": [("while.1", 10 * MS, 30 * MS),        # holds the next two
                        ("fusion.1 = f32[8] fusion(x)", 10 * MS, 20 * MS),
                        ("convolution.2", 30 * MS, 10 * MS),
                        ("fusion.1", 60 * MS, 10 * MS),
                        ("fusion.1", 150 * MS, 10 * MS)],     # after close
            "XLA Modules": [("jit_loss_fn(3)", 10 * MS, 30 * MS),
                            ("jit_sgd_update(4)", 60 * MS, 10 * MS)],
        },
    }


def test_busy_union_and_window():
    s = devtrace.summarize(recorded())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.04)          # 10..40 + 60..70


def test_module_times():
    s = devtrace.summarize(recorded())
    assert s["module_s"] == {"jit_loss_fn": [pytest.approx(0.03)],
                             "jit_sgd_update": [pytest.approx(0.01)]}


def test_top_ops_and_idle_gaps():
    s = devtrace.summarize(recorded())
    assert s["top_ops"] == [                 # self time; the while has none
        ["jit_loss_fn/fusion.1", pytest.approx(0.02)],
        ["jit_loss_fn/convolution.2", pytest.approx(0.01)],
        ["jit_sgd_update/fusion.1", pytest.approx(0.01)]]
    # gaps: 0..10 (no span), 40..60 (combine, the innermost), 70..100
    assert s["idle_gaps"] == [["host.none", pytest.approx(0.03)],
                              ["bench.combine", pytest.approx(0.02)],
                              ["host.none", pytest.approx(0.01)]]


def test_busy_averages_over_devices():
    t = recorded()
    t["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 0.0, 100 * MS)]}
    assert devtrace.summarize(t)["busy_s"] == pytest.approx((0.04 + 0.1) / 2)


def test_partial_overlap_counts_once():
    ops = [(10.0, 30.0, "a"), (20.0, 40.0, "b")]
    assert dict(devtrace.self_times(ops)) == {"a": (10.0, 10.0),
                                              "b": (20.0, 20.0)}


def test_nothing_to_read():
    t = recorded()
    del t["/device:TPU:0"]
    assert devtrace.summarize(t) is None                 # a CPU trace
    t = recorded()
    t["/host:CPU"]["python"] = [e for e in t["/host:CPU"]["python"]
                                if e[0] != "bench.window_close"]
    assert devtrace.summarize(t) is None
