"""Elastic re-meshing — the paper's "reconfigurable" property at the pod
level: devices leave (failure/preemption) or join, the runner rebuilds the
mesh, re-lowers the step, and re-shards live state.

On real multi-host TPU this is driven by slice health callbacks; here the
device pool is explicit so the policy is testable: ``plan_mesh`` picks the
largest usable (data, model) grid from the surviving devices (keeping the
model axis if possible — param layouts survive, only the data axis
shrinks), and ``reshard_tree`` device_puts live arrays onto the new mesh.

Combined with the journal + deterministic pipeline, recovery re-executes
at most the in-flight step — no checkpoint restore on the happy path
(the paper's central claim, validated in tests/test_elastic.py)."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.distributed import sharding as shd


def plan_mesh(devices: list, model_axis: int) -> Mesh:
    """Largest (data, model) mesh from surviving devices. Prefers keeping
    ``model_axis`` intact (same param layout); degrades model axis to the
    largest power-of-two divisor that fits otherwise."""
    n = len(devices)
    model = min(model_axis, n)
    while model > 1 and n // model < 1:
        model //= 2
    data = n // model
    used = devices[: data * model]
    arr = np.array(used).reshape(data, model)
    return Mesh(arr, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def reshard_tree(tree, spec_tree, rules, mesh: Mesh):
    """device_put every leaf onto the new mesh per its logical axes."""
    shardings = shd.tree_shardings(spec_tree, rules, mesh)
    flat_t = jax.tree.leaves(tree)
    flat_s = jax.tree.leaves(shardings,
                             is_leaf=lambda x: hasattr(x, "spec"))
    out = [jax.device_put(t, s) for t, s in zip(flat_t, flat_s)]
    return jax.tree.unflatten(jax.tree.structure(tree), out)


@dataclass
class DevicePool:
    """Testable stand-in for slice health: a mutable set of live devices."""

    devices: list

    def fail(self, idx: list[int]) -> None:
        self.devices = [d for i, d in enumerate(self.devices) if i not in set(idx)]

    def join(self, devs: list) -> None:
        self.devices = self.devices + list(devs)

    def alive(self) -> list:
        return list(self.devices)
