"""Ahead-of-time compiles for one TPU v5e chip, without the chip.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (illegal
block tilings, programs over the device's memory). Each main-path kernel
compiles at the widths of the model it serves (repro.kernels.cases — the
same calls chip_smoke.py runs on the chip) and must lower to a Mosaic
``tpu_custom_call``; the full-width smollm-360m gradient step of the
JAX-SGD program must fit one chip.

The topology is described inside a fixture only — never at import — so
that under pytest-xdist only the worker given this file loads the TPU
library. The persistent compilation cache is off around these compiles:
an entry written for a described chip cannot be read back here."""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import main_path_cases

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, specs):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        specs)


@pytest.mark.parametrize("idx", [0, 1, 2],
                         ids=["tile_matmul", "flash_attention", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, idx):
    case = main_path_cases()[idx]
    compiled = jax.jit(case.fn).lower(*_on(one_chip, case.specs)).compile()
    assert "tpu_custom_call" in compiled.as_text(), case.name
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes < V5E_HBM_BYTES


def test_tile_matmul_block_for_smollm_k(one_chip):
    """K = d_model = 960 has no 128-multiple divisor: the wrapper must
    take the whole K as its block (a 64-block is refused by the chip)."""
    from repro.kernels.tiling import LANE, pick_block
    assert pick_block(960, 512, LANE) == 960
    assert pick_block(2560, 512, LANE) == 512
    case = main_path_cases()[0]
    hlo = jax.jit(case.fn).lower(*_on(one_chip, case.specs)).as_text()
    assert "tpu_custom_call" in hlo


def test_smollm_grad_step_fits_one_chip(one_chip):
    """The JAX-SGD program's own jitted value_and_grad at full width
    (micro-batch 4 x seq 2048, bf16 params) compiles for one v5e chip and
    its arguments, outputs and temporaries fit its HBM."""
    from repro.configs import get_config
    from repro.configs.base import Shape, input_specs
    from repro.models import model as M
    from repro.programs.jax_sgd import JAXSGDProgram
    cfg = get_config("smollm_360m")
    prog = JAXSGDProgram(cfg, steps=1, n_micro=4, micro_batch=4, seq=2048)
    params = _on(one_chip, M.abstract_params(cfg))
    batch = _on(one_chip, input_specs(cfg, Shape("micro", "train", 2048, 4)))
    compiled = prog.grad_fn.lower(params, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert 0.7e9 < ma.argument_size_in_bytes < 0.8e9   # 362 M bf16 params
    assert total < V5E_HBM_BYTES, total
