"""Compile each configuration's gradient and update programs, and the
reference's row gradient, for a described TPU v5e chip, with no chip.

    JAX_PLATFORMS=cpu python bench/aot.py [config ...]

Prints one JSON line per program with ``memory_analysis()``: argument,
output and temporary bytes of one call. Nothing runs, so this gives no
time; it shows what the chip's compiler refuses and what one call holds.
Three handlers can hold gradient calls at once, so a cell's device peak
can reach 3 x (temporaries + outputs) + the params' copy."""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*", default=["smollm-360m"])
    ap.add_argument("--traffic", default="sgd")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(BENCH, "lib")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import reference as R
    from repro.programs.jax_sgd import JAXSGDProgram

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    with open(os.path.join(BENCH, "traffic", args.traffic + ".json")) as f:
        t = json.load(f)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    for name in args.configs:
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            cfg = json.load(f)
        prog = JAXSGDProgram(harness.model_config(cfg), steps=1,
                             n_micro=t["n_micro"], micro_batch=t["micro_batch"],
                             seq=t["seq"], lr=t["lr"])
        params = on_chip(R.layout_shapes(cfg))
        tok = jax.ShapeDtypeStruct((t["micro_batch"], t["seq"]), jnp.int32,
                                   sharding=chip)
        row = jax.ShapeDtypeStruct((t["seq"],), jnp.int32, sharding=chip)
        f32 = on_chip(jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
            R.layout_shapes(cfg)))
        ref = R.Reference(cfg, t["lr"])
        progs = {
            "grad": prog.grad_fn.lower(params,
                                       {"tokens": tok, "labels": tok}),
            "update": prog.sgd_update.lower(params,
                                            [params] * t["n_micro"]),
            "reference_row_grad": ref._vg.lower(f32, row, row),
        }
        for what, lowered in progs.items():
            m = lowered.compile().memory_analysis()
            print(json.dumps({
                "config": name, "program": what,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "peak_bytes": getattr(m, "peak_memory_in_bytes", None)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
