"""Task partitioning invariants (paper §5.2) — property-based, now
running through the PR-3 op registry (cost models and split rules are
per-op, looked up by the open op *name* instead of a closed enum).

The partition must (a) respect the size cap, (b) exactly tile the original
task's (input × output) rectangle with disjoint pieces, (c) follow the
4-way / 2-way split rules, (d) round-trip the declarative wire format."""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (GLOBAL_OPS, LayerSpec, TaskDesc, UnknownOp,
                        partition, prototype_tasks)
from repro.programs.mlp import (ACTIVATION, BACKWARD, FORWARD, LOSS, UPDATE,
                                stage_order)

MLP_OPS = [FORWARD, ACTIVATION, LOSS, BACKWARD, UPDATE]

dims = st.sampled_from([1, 2, 4, 8, 16, 32, 64, 128, 256])
caps = st.sampled_from([16.0, 64.0, 256.0, 1024.0])


@given(dims, dims, caps)
@settings(max_examples=200, deadline=None)
def test_forward_partition_tiles_exactly(m, n, cap):
    t = TaskDesc(FORWARD, 0, 0, 0, 0, m, 0, n)
    pieces = partition(t, cap)
    # size cap respected whenever splitting is possible
    for p in pieces:
        assert GLOBAL_OPS.cost(p) <= cap or (p.m <= 1 and p.n <= 1)
    # exact disjoint cover of the m×n rectangle
    cells = set()
    for p in pieces:
        for i in range(p.in_lo, p.in_hi):
            for j in range(p.out_lo, p.out_hi):
                assert (i, j) not in cells, "overlap"
                cells.add((i, j))
    assert len(cells) == m * n


@given(dims, caps)
@settings(max_examples=100, deadline=None)
def test_1d_partition_covers(n, cap):
    t = TaskDesc(ACTIVATION, 0, 0, 0, 0, 0, 0, n)
    pieces = partition(t, cap)
    covered = sorted((p.out_lo, p.out_hi) for p in pieces)
    cur = 0
    for lo, hi in covered:
        assert lo == cur
        cur = hi
    assert cur == n


def test_forward_splits_four_way():
    t = TaskDesc(FORWARD, 0, 0, 0, 0, 8, 0, 8)
    kids = GLOBAL_OPS.split(t)
    assert len(kids) == 4        # paper: "split into FOUR smaller tasks"
    assert {(k.in_lo, k.in_hi, k.out_lo, k.out_hi) for k in kids} == {
        (0, 4, 0, 4), (0, 4, 4, 8), (4, 8, 0, 4), (4, 8, 4, 8)}


def test_update_splits_two_way():
    t = TaskDesc(UPDATE, 0, 0, 0, 0, 8, 0, 8)
    kids = GLOBAL_OPS.split(t)
    assert len(kids) == 2        # "each updating m/2 parameters"


def test_loss_costs_more_per_element():
    loss = TaskDesc(LOSS, 0, 0, 0, 0, 0, 0, 16)
    act = TaskDesc(ACTIVATION, 0, 0, 0, 0, 0, 0, 16)
    # §5.2 "proportionally larger size"
    assert GLOBAL_OPS.cost(loss) > GLOBAL_OPS.cost(act)


def test_unregistered_op_raises():
    t = TaskDesc("nosuchop", 0, 0, 0)
    with pytest.raises(UnknownOp):
        GLOBAL_OPS.cost(t)


@given(st.sampled_from(MLP_OPS), dims, dims)
@settings(max_examples=50, deadline=None)
def test_wire_roundtrip(op, m, n):
    t = TaskDesc(op, 3, 7, 11, 0, m, 0, n, task_id="x1")
    assert TaskDesc.from_wire(t.to_wire()) == t
    assert isinstance(TaskDesc.from_wire(t.to_wire()).op, str)


def test_paper_model_task_census():
    """Paper §6: N=4⁴ model, cap=4⁴ — layer-1 forward must partition into
    256 tasks of 16×16."""
    stages = prototype_tasks([LayerSpec(256, 256), LayerSpec(256, 1)], 0, 0)
    fwd0 = partition(stages["fwd_0"][0], 256.0)
    assert len(fwd0) == 256
    assert all(p.m == 16 and p.n == 16 for p in fwd0)
    fwd1 = partition(stages["fwd_1"][0], 256.0)
    assert len(fwd1) == 1        # 256×1 is exactly at cap


def test_stage_order_dependencies():
    order = stage_order(3)
    assert order.index("fwd_0") < order.index("act_0") < order.index("fwd_1")
    assert order.index("loss") < order.index("bwd_2") < order.index("bwd_0")
    assert order.index("bwd_0") < order.index("upd_0")
