"""The ``mamba2`` family and the ``ssd_s`` reader at reduced size: the
FLOP count against a hand count, the layout against the program's tree,
the reference's SSD against its recurrence, ``ssd_s`` on hand-built
device ops, and a tiny Mamba-2 cell rehearsed through the harness, with
the dropped-state fault the cell's ``correct`` has to catch."""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

import check
import counts
import harness
import reference as R

TINY = {
    "name": "tiny-mamba2", "family": "mamba2",
    "program_config": "mamba2_2_7b", "program_reduced": True,
    "hidden_size": 32, "num_hidden_layers": 2, "vocab_size": 256,
    "d_inner": 64, "d_state": 16, "head_dim": 16, "n_groups": 1,
    "d_conv": 4, "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16"}
#: Limits for the tiny cell, from CPU readings on seeds 1-6: the program
#: read at most loss 5.6e-5, grad 1.24e-2, change 1.49e-2; the float8
#: control at least loss 1.2e-4, grad 4.7e-2, change 2.6e-2; the dropped
#: state at least grad 5.9e-2, change 3.8e-2 (its loss within the
#: program's). The cell's own limits come from the chip at its size.
LIMITS = {"loss": 1e-4, "grad": 2.5e-2, "change": 2e-2}
MS = 1e6   # ns


@pytest.fixture
def cell(tmp_path, monkeypatch):
    """A tiny Mamba-2 cell on the ``sgd`` traffic of the tiny cells; its
    configuration and limits are added here, not in ``tiny.py``."""
    monkeypatch.setitem(tiny.CONFIGS, "tiny-mamba2", TINY)
    monkeypatch.setattr(tiny, "LIMITS", LIMITS)
    root = tiny.make_root(str(tmp_path), {
        "m.sgd": ("tiny-mamba2", tiny.TRAFFIC)})
    return harness.load_cell("m.sgd", root)


def _ssd_s():
    path = os.path.join(tiny.BENCH, "metrics", "ssd_s.py")
    spec = importlib.util.spec_from_file_location("ssd_s_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flops_hand_count():
    """d 32, d_inner 64 (4 heads x 16), one group of 16 states, 2 layers,
    vocab 256."""
    per_layer = (2 * 32 * (2 * 64 + 2 * 16 + 4)   # z, x, B, C, dt
                 + 2 * 64 * 32                     # out
                 + 4 * 4 * 16 * 16)                # SSD recurrence
    fwd = 2 * per_layer + 2 * 32 * 256             # + tied logits
    assert counts.forward_flops_per_token(TINY, 64) == fwd == 53760
    assert counts.forward_flops_per_token(TINY, 4096) == fwd
    assert counts.model_flops_per_token(TINY, 64) == 3 * fwd


def test_published_sizes():
    with open(os.path.join(tiny.BENCH, "configs", "mamba2-2.7b.json")) as f:
        cfg = json.load(f)
    assert counts.param_count(cfg) == 450_472_320
    assert counts.model_flops_per_token(cfg, 2048) == 2_764_062_720


def test_layout_matches_the_program_and_needs_the_conv_bias(monkeypatch):
    """``harness.model_config`` takes the program's tree leaf for leaf;
    a program without the published conv bias is refused."""
    mc = harness.model_config(TINY)
    assert mc.norm_eps == TINY["rms_norm_eps"]
    layout = R.param_layout(TINY)["period"][0]["mamba"]
    assert {"conv_x_bias", "conv_B_bias", "conv_C_bias"} <= set(layout)
    from repro.models import blocks
    specs = blocks.mamba_specs
    monkeypatch.setattr(blocks, "mamba_specs", lambda *a: {
        k: v for k, v in specs(*a).items() if not k.endswith("_bias")})
    with pytest.raises(ValueError, match="does not match"):
        harness.model_config(TINY)


def test_reference_ssd_is_the_recurrence(monkeypatch):
    """The quadratic form, two heads a block over two groups, against
    ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t``."""
    fam = R.family(TINY)
    monkeypatch.setattr(fam, "HEAD_BLOCK", 2)
    T, H, P, G, N = 37, 4, 8, 2, 5
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 2)
    A = -jax.random.uniform(ks[2], (H,), minval=1, maxval=16)
    B = jax.random.normal(ks[3], (T, G, N))
    C = jax.random.normal(ks[4], (T, G, N))
    with jax.default_matmul_precision("highest"):
        got = fam._ssd(False, x, dt, A, B, C)
    h = np.zeros((H, P, N))
    rep = H // G
    for t in range(T):
        Bh = np.repeat(np.asarray(B[t]), rep, 0)
        h = (np.exp(np.asarray(dt[t] * A))[:, None, None] * h
             + np.asarray(dt[t])[:, None, None] * np.asarray(x[t])[:, :, None]
             * Bh[:, None, :])
        want = np.einsum("hpn,hn->hp", h, np.repeat(np.asarray(C[t]), rep, 0))
        np.testing.assert_allclose(np.asarray(got[t]), want, rtol=1e-4,
                                   atol=1e-5)


def test_ssd_s_counts_scoped_self_time():
    """A scoped loop 10..40 ms holds a scoped op 12..20 and an unscoped
    one 20..30: the loop's self time is 12 ms, its scoped op's 8 ms, the
    unscoped ops' nothing; a scoped op 50..55 lies past a window that
    ends at 48 ms."""
    mod = _ssd_s()
    ops = [(10 * MS, 40 * MS, True), (12 * MS, 20 * MS, True),
           (20 * MS, 30 * MS, False), (41 * MS, 45 * MS, False),
           (50 * MS, 55 * MS, True)]
    assert mod.scoped_seconds(ops, 0, 48 * MS) == pytest.approx(0.020)
    assert mod.scoped_seconds(ops, 0, 60 * MS) == pytest.approx(0.025)
    assert mod.scoped_seconds([(o[0], o[1], False) for o in ops],
                              0, 60 * MS) is None


def _message(*fields) -> bytes:
    """A serialized protobuf message of ``(number, value)`` fields: ints
    as varints, bytes and str length-delimited."""
    def varint(n: int) -> bytes:
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += varint(number << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(number << 3 | 2) + varint(len(v)) + v
    return out


def _xspace(path, ops):
    """A profile holding a window 0..100 ms on the host, two executions
    of jit_loss_fn (0..50, 50..90 ms) and one of jit_sgd_update (90..100)
    on /device:TPU:0 with ``ops`` (instruction, start ms, end ms) on its
    XLA Ops line, and the optimized HLO of both modules on
    /host:metadata, in which only jit_loss_fn's ``fusion.2`` and
    ``while.1`` carry the scope (the update reuses the name fusion.2)."""
    def hlo(scoped):
        ins = [_message((1, name), (2, "fusion"), (7, _message(
            (2, f"jit(loss_fn)/{'acan.ssd/' if name in scoped else ''}mul"))))
            for name in ("fusion.1", "fusion.2", "while.1")]
        return _message((1, _message((1, "m"), (3, _message(
            (1, "main"), *[(2, i) for i in ins])))))

    def plane(name, lines=(), metas=(), stat_metas=()):
        return _message((2, name), *[(3, x) for x in lines],
                        *[(4, _message((1, k), (2, v))) for k, v in metas],
                        *[(5, _message((1, k), (2, v))) for k, v in stat_metas])

    def line(name, events):            # events: (metadata id, start, end) ms
        return _message((2, name), (3, 0), *[(4, _message(
            (1, m), (2, int(a * 1e9)), (3, int((b - a) * 1e9))))
            for m, a, b in events])

    names = {n: i + 10 for i, n in enumerate(sorted({o[0] for o in ops}))}
    device = plane(
        "/device:TPU:0",
        [line("XLA Modules", [(1, 0, 50), (1, 50, 90), (2, 90, 100)]),
         line("XLA Ops", [(names[n], a, b) for n, a, b in ops])],
        [(1, _message((1, 1), (2, "jit_loss_fn(1)"))),
         (2, _message((1, 2), (2, "jit_sgd_update(2)")))]
        + [(i, _message((1, i), (2, f"%{n} = f32[2] fusion(%p)")))
           for n, i in names.items()])
    host = plane("/host:CPU", [line("python", [(1, 0, 0), (2, 100, 100)])],
                 [(1, _message((1, 1), (2, "bench.window_open"))),
                  (2, _message((1, 2), (2, "bench.window_close")))])
    stat = lambda proto: (5, _message((1, 7), (6, proto)))  # noqa: E731
    meta = plane("/host:metadata", metas=[
        (1, _message((1, 1), (2, "jit_loss_fn(1)"),
                     stat(hlo({"fusion.2", "while.1"})))),
        (2, _message((1, 2), (2, "jit_sgd_update(2)"), stat(hlo(set()))))],
        stat_metas=[(7, _message((1, 7), (2, "Hlo Proto")))])
    with open(path, "wb") as f:
        f.write(_message((1, device), (1, host), (1, meta)))


def test_ssd_s_reads_a_hand_built_trace(tmp_path, monkeypatch):
    """Per jit_loss_fn execution: the scoped loop 10..40 ms less the two
    ops nested in it (12 ms), the scoped fusion.2 in it (8 ms) and
    another 60..65 (5 ms), over 2 calls; the update's fusion.2 (92..98
    ms) and the unscoped fusion.1 count for nothing."""
    import types
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    os.makedirs(os.path.join(str(tmp_path), "trace"))
    _xspace(os.path.join(str(tmp_path), "trace", "t.xplane.pb"), [
        ("while.1", 10, 40), ("fusion.2", 12, 20), ("fusion.1", 20, 30),
        ("fusion.2", 60, 65), ("fusion.1", 70, 80), ("fusion.2", 92, 98)])
    run = types.SimpleNamespace(trace={"module_s": {
        "jit_loss_fn": [0.05, 0.04], "jit_sgd_update": [0.01]}})
    assert _ssd_s().read(run) == pytest.approx(0.025 / 2)


def test_ssd_s_finds_the_scope_in_a_recorded_profile(tmp_path):
    """The optimized HLO that a real profile keeps on ``/host:metadata``
    (here the CPU's, of a program compiled before the profile started)
    names the scoped instructions."""
    mod = _ssd_s()

    @jax.jit
    def loss_fn(x):
        with jax.named_scope("acan.ssd"):
            y = jnp.sin(x) @ x
        return jnp.cos(y).sum()

    x = jnp.ones((8, 8))
    loss_fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    loss_fn(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    with open(path, "rb") as f:
        protos = mod.module_protos(f.read(), "jit_loss_fn")
    assert protos
    scoped = set().union(*map(mod.scoped_instructions, protos))
    assert scoped and not any("cos" in n for n in scoped)


def test_ssd_s_reads_a_profile_without_scoped_ops(tmp_path, monkeypatch):
    """A profile with the window's markers and no device ops (a CPU run),
    or no profile at all, reads None."""
    import types
    from repro.core import trace
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    run = types.SimpleNamespace(trace={"module_s": {"jit_loss_fn": [0.1]}})
    assert _ssd_s().read(run) is None
    jax.profiler.start_trace(os.path.join(str(tmp_path), "trace"))
    trace.instant("bench.window_open")
    jnp.ones(4).block_until_ready()
    trace.instant("bench.window_close")
    jax.profiler.stop_trace()
    assert _ssd_s().read(run) is None
    assert _ssd_s().read(types.SimpleNamespace(trace=None)) is None


def test_tiny_cell_runs_traced(cell):
    out = harness.run_cell(cell, 2**33 + 7, 1.0, True, time.perf_counter(),
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    got = set(out["metrics"])
    assert {"grad_runs_per_step", "combine_s"} <= got
    # No device trace on the CPU: the device readers find nothing.
    assert not got & {"ssd_s", "mfu", "grad_roofline", "device_idle_share"}


def test_dropped_state_fails_correct(cell, monkeypatch):
    """The cell's comparison catches each chunk starting from a zero
    state (the hand-off between chunks lost) in the program."""
    from repro.models import blocks
    orig = blocks.ssd_chunked

    def dropped(x, dt, A, B, C, D, chunk):
        ys = [orig(x[:, s:s + chunk], dt[:, s:s + chunk], A,
                   B[:, s:s + chunk], C[:, s:s + chunk], D, chunk)[0]
              for s in range(0, x.shape[1], chunk)]
        return jnp.concatenate(ys, axis=1), None

    monkeypatch.setattr(blocks, "ssd_chunked", dropped)
    seed = 5
    rec, losses, params0, _ = harness.drive(cell, seed, 0, None,
                                            time.perf_counter())
    monkeypatch.setattr(blocks, "ssd_chunked", orig)
    nums = check.numbers(cell.traffic["lr"], params0,
                         harness.program_run(rec, losses),
                         harness.reference_run(cell, seed, params0))
    assert any(nums[k] > v for k, v in LIMITS.items()), nums
