#!/usr/bin/env python3
"""Is the memory that XLA says a ``dataset_pass`` executable needs real,
although ``peak_bytes_in_use`` does not show it? Run one batch, print the
allocator's statistics, then hold ballast arrays of growing size on the
device and run the batch again: if the temporaries are real, the batch
fails (RESOURCE_EXHAUSTED) as soon as ballast + arguments + results +
temporaries pass the device's limit.

    python3 benchmark/tools/memory_probe.py <cell> [ballast GB ...]
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.drivers import dataset_pass

    cell = harness.load_cell(argv[1])
    ballasts = [float(g) for g in argv[2:]] or [1.0, 2.0, 3.0, 4.0]
    device = harness.require_devices(1)[0]
    harness.enable_compile_cache()
    traffic = cell["traffic"]
    predictor, _ = dataset_pass.build(cell, 7)
    pool = dataset_pass.make_pool(7, 2, traffic["height"], traffic["width"])
    top, bottom, left, right = dataset_pass.sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    widths = ((0, 0), (top, bottom), (left, right), (0, 0))
    bs = traffic["batch_size"]
    i1 = np.pad(np.stack([pool[i % 2][0] for i in range(bs)]), widths,
                mode="edge")
    i2 = np.pad(np.stack([pool[i % 2][1] for i in range(bs)]), widths,
                mode="edge")
    predictor.predict_batch(i1, i2)
    kernels, temporaries = dataset_pass.compiled_program(predictor, traffic)
    print(json.dumps({"cell": cell["name"], "kind": device.device_kind,
                      "after_one_batch": device.memory_stats(),
                      "executable_temporaries_bytes": temporaries,
                      "kernels": kernels}), flush=True)
    for gb in ballasts:
        ballast = jnp.zeros((int(gb * 1e9),), jnp.uint8)
        ballast.block_until_ready()
        try:
            predictor.predict_batch(i1, i2)
            outcome = "ran"
        except Exception as e:        # the device's own refusal
            outcome = type(e).__name__ + ": " + str(e)[:200]
        stats = device.memory_stats()
        print(json.dumps({"ballast_gb": gb, "outcome": outcome,
                          "bytes_in_use": stats.get("bytes_in_use"),
                          "peak_bytes_in_use":
                          stats.get("peak_bytes_in_use")}), flush=True)
        del ballast
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
