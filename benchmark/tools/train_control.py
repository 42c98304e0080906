#!/usr/bin/env python3
"""Read the control and the faults of a ``train_steps`` cell on the
chip, at the cell's own size: for each seed the weights and the first
batches as a run makes them, the plain reference through the followed
steps, and in the program's place (a) the same reference with the
configuration's control operand (fp8), (b) the reference fed half of
each batch twice (the other half left out, the mean taken over the
rest), (c) the reference with one leaf moved double at every step. A
step that leaves its state unchanged needs no run: its change reads 1.
One JSON line for each seed and case. The benchmark's own runs never
run this.

    python3 benchmark/tools/train_control.py <cell> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LEAF = ("update", "update_block", "gru", "convq1", "kernel")


def half_rows(batch):
    import numpy as np
    half = batch["image1"].shape[0] // 2
    return {k: np.concatenate([v[:half], v[:half]]) for k, v in batch.items()}


def leaf_double(batch):
    return batch


def _double_after(old, new):
    import jax
    new = jax.tree.map(lambda x: x, new)
    a, b = old, new
    for key in LEAF[:-1]:
        a, b = a[key], b[key]
    b[LEAF[-1]] = a[LEAF[-1]] + 2.0 * (b[LEAF[-1]] - a[LEAF[-1]])
    return new


leaf_double.after = _double_after


def main(argv) -> int:
    import jax

    from benchmark import harness
    from benchmark.drivers import train_steps

    cell = harness.load_cell(argv[1])
    devices = jax.devices()
    harness.enable_compile_cache()
    traffic, config = cell["traffic"], cell["config"]
    for seed in (int(s) for s in argv[2:]):
        _, mcfg = train_steps.configs_of(cell, seed)
        variables = train_steps.seeded_variables(mcfg, seed)
        initial = jax.device_get(variables["params"])
        batches = train_steps.make_batches(
            seed, traffic["pool"], traffic["batch_size"],
            traffic["height"], traffic["width"])[:traffic["followed_steps"]]
        t0 = time.perf_counter()
        theirs = train_steps.follow_reference(variables, batches, traffic,
                                              config)
        reference_s = time.perf_counter() - t0
        cases = {
            "control_" + config["control"]["operand"]:
                dict(operand_name=config["control"]["operand"]),
            "fault_half_rows": dict(fault=half_rows),
            "fault_leaf_moved_double": dict(fault=leaf_double),
        }
        for case, kwargs in cases.items():
            ours = train_steps.follow_reference(variables, batches, traffic,
                                                config, **kwargs)
            numbers = train_steps.compare_steps(initial, ours, theirs)
            print(json.dumps({
                "cell": cell["name"], "seed": seed, "case": case,
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "reference_s": reference_s, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
