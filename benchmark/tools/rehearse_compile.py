#!/usr/bin/env python3
"""Compile a ``dataset_pass`` cell's forward for a *described* v5e, with
the program's TPU branches steered, and print XLA's memory figures and
the kernels in the compiled program. No chip, no chip time; nothing
runs, so this says what fits and which kernels lower, not how fast.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_compile.py <cell> [batch ...]

The program picks its correlation engine and its kernels by
``jax.default_backend()``, which here says "cpu"; the script makes it
say "tpu" while the program is traced (the steering stays in this
script, not in the program).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark.drivers import dataset_pass

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(argv[1])
    batches = [int(b) for b in argv[2:]] or [cell["traffic"]["batch_size"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import FlowPredictor
    from raft_tpu.models.raft import RAFT
    from raft_tpu.ops.layout import KERNEL_NAMES

    traffic, config = cell["traffic"], cell["config"]
    model = RAFT(RAFTConfig(**config["model"]))
    shapes = weights.variable_shapes(model)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), shapes)
    top, bottom, left, right = dataset_pass.sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    for batch in batches:
        predictor = FlowPredictor(model, shapes, iters=traffic["iters"],
                                  batch_size=batch, **config["predictor"])
        shape = (batch, traffic["height"] + top + bottom,
                 traffic["width"] + left + right, 3)
        images = jax.ShapeDtypeStruct(shape, jnp.float32,
                                      sharding=one_chip)
        t0 = time.time()
        try:
            compiled = predictor._fn(shape, False, "float32").lower(
                shapes, images, images, None).compile()
        except Exception as e:   # what the chip's compiler would raise
            print(json.dumps({"cell": cell["name"], "batch": batch,
                              "error": str(e)[:600]}), flush=True)
            continue
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(json.dumps({
            "cell": cell["name"], "batch": batch,
            "compile_s": round(time.time() - t0, 1),
            "kernels": dataset_pass.census(compiled.as_text(),
                                           KERNEL_NAMES),
            "arguments_bytes": ma.argument_size_in_bytes,
            "outputs_bytes": ma.output_size_in_bytes,
            "temporaries_bytes": ma.temp_size_in_bytes,
            "total_gb": round(total / 1e9, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
