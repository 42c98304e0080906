#!/usr/bin/env python3
"""Compile a token cell's train step for a *described* v5e, with the
program's TPU branches steered, and print XLA's memory figures, the
kernels in the compiled program and, where the cell's driver maps
instructions to model stages, how many each stage has. No chip, no chip
time; nothing runs, so this says what fits and which kernels lower, not
how fast. The cell's driver is found by its kind and the model by the
family's row, so it serves ``ssm_train_steps`` and ``lm_train_steps``
alike (``rehearse_lm_compile.py`` is the older copy for the latter).

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_step_compile.py <cell> [sequences ...]
"""

from __future__ import annotations

import dataclasses
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

    from benchmark import harness
    from benchmark.drivers.dataset_pass import census

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(argv[1])
    driver = harness.load_driver(cell["traffic"]["kind"])
    stage_ops = getattr(driver, "stage_ops", lambda text: {})
    counts = [int(b) for b in argv[2:]] or [cell["traffic"]["sequences"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"

    from raft_tpu.families import family_of
    from raft_tpu.ops.layout import KERNEL_NAMES
    from raft_tpu.parallel import create_train_state, make_train_step

    on_chip = lambda tree: jax.tree.map(              # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    for sequences in counts:
        tcfg, mcfg = driver.configs_of(cell, 0)
        tcfg = dataclasses.replace(tcfg, batch_size=sequences)
        model = family_of(tcfg.model_family).build(mcfg)
        state = jax.eval_shape(
            lambda: create_train_state(jax.random.PRNGKey(0), model, tcfg))
        rows = jax.ShapeDtypeStruct((sequences, tcfg.seq_len), jnp.int32)
        batch = {"tokens": rows, "segment_ids": rows, "positions": rows}
        rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        t0 = time.time()
        try:
            compiled = make_train_step(tcfg).lower(
                on_chip(state), on_chip(batch), on_chip(rng)).compile()
        except Exception as e:   # what the chip's compiler would raise
            print(json.dumps({"cell": cell["name"], "sequences": sequences,
                              "error": str(e)[:1200]}), flush=True)
            continue
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        text = compiled.as_text()
        print(json.dumps({
            "cell": cell["name"], "sequences": sequences,
            "parameters": sum(x.size for x in jax.tree.leaves(state.params)),
            "compile_s": round(time.time() - t0, 1),
            "kernels": census(text, KERNEL_NAMES),
            "stage_instructions": {k: len(v) for k, v in
                                   stage_ops(text).items()},
            "arguments_bytes": ma.argument_size_in_bytes,
            "outputs_bytes": ma.output_size_in_bytes,
            "aliased_bytes": ma.alias_size_in_bytes,
            "temporaries_bytes": ma.temp_size_in_bytes,
            "total_gb": round(total / 1e9, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
