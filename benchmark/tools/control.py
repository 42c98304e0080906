#!/usr/bin/env python3
"""Read the control of a ``dataset_pass`` cell on the chip, at the
cell's own frame size and iteration count: for each seed, the weights
and the frames as a run makes them, the plain reference over
``check_pairs`` pairs, and beside it the same reference computed with
the configuration's control operand (fp8) in the program's place. Prints
one JSON line for each seed with every pair's gap. The benchmark's own
runs never run this.

    python3 benchmark/tools/control.py <cell> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.drivers import dataset_pass

    cell = harness.load_cell(argv[1])
    devices = jax.devices()
    harness.enable_compile_cache()
    traffic, config = cell["traffic"], cell["config"]
    n = traffic["check_pairs"]
    for seed in (int(s) for s in argv[2:]):
        t0 = time.perf_counter()
        predictor, variables = dataset_pass.build(cell, seed)
        pool = dataset_pass.make_pool(seed, traffic["pool"],
                                      traffic["height"], traffic["width"])
        control = dataset_pass.reference_entry(
            config, traffic, config["control"]["operand"])
        dataset = dataset_pass.SeededPairs(pool, traffic["batch_size"], n)
        rng = np.random.default_rng([seed, 0xBEEF])
        picks = sorted(rng.choice(len(pool), size=min(n, len(pool)),
                                  replace=False).tolist())
        dataset.pool = [pool[i] for i in picks]
        kept = [(0, idx, flow) for idx, _, flow
                in control(predictor, dataset, traffic["pad_mode"])]
        checked = dataset_pass.check_against_reference(
            kept, dataset.pool, variables, config,
            dict(traffic, check_pairs=n), seed)
        rows = checked["rows"]
        print(json.dumps({
            "cell": cell["name"], "seed": seed, "control":
            config["control"]["operand"],
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "epe_px_worst": max(r["epe_px"] for r in rows),
            "epe_px_least": min(r["epe_px"] for r in rows),
            "rows": rows, "seconds": time.perf_counter() - t0}), flush=True)
        del predictor, variables
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
