#!/usr/bin/env python3
"""Read the upper side of a ``swa_train_steps`` cell's limits on the
chip, at the cell's own size: for each seed the weights and the first
batches as a run makes them, the plain reference through the followed
steps, and in the program's place the same reference with something
wrong with it:

- ``control_<operand>``: the configuration's control operand (fp8) on
  every product the configuration states in bfloat16, backward included;
- ``window_left_out``: the sliding layers attend the whole causal
  document;
- ``positions_on_full``: RoPE applied on the full layers too, which the
  model leaves without positions;
- ``half_the_positions``: every second loss position left out.

Each case goes through the rows a run compares (``compared_followed``
and, where the cell's limits name them, the rows on the norm of the
difference) with the cell's limits, and must read not correct. One JSON
line a case. The benchmark's own runs never run this.

    python3 benchmark/tools/swa_control.py <cell> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DIFFERENCE_ROWS = ("grad_diff_worst_leaf", "change_diff_worst_leaf")


def read_cases(cell: dict, seed: int, only=None):
    """One dict a case: its name, the compared rows beside the cell's
    limits, ``correct``, and the driver's numbers."""
    from benchmark import harness

    driver = harness.load_driver(cell["traffic"]["kind"])
    traffic, config = cell["traffic"], cell["config"]
    limits = cell["cell"]["limits"]
    _, mcfg = driver.configs_of(cell, seed)
    variables = driver.seeded_variables(mcfg, seed)
    batches = driver.make_batches(seed, traffic, mcfg.vocab)[
        :traffic["followed_steps"]]
    t0 = time.perf_counter()
    theirs = driver.follow_reference(variables, batches, traffic, config)
    reference_s = time.perf_counter() - t0
    operand = config["control"]["operand"]
    cases = {"control_" + operand: {"operand_name": operand},
             "window_left_out": {"window": False},
             "positions_on_full": {"positions_on_full": True},
             "half_the_positions": {"keep_every": 2}}
    for name, kwargs in cases.items():
        if only and name not in only:
            continue
        stands_in = driver.follow_reference(variables, batches, traffic,
                                            config, **kwargs)
        numbers = driver.compare_with_difference(variables["params"],
                                                 stands_in, theirs)
        del stands_in
        compared = driver.compared_followed(
            numbers, limits, traffic["followed_steps"])
        for row in DIFFERENCE_ROWS:
            if row in limits:
                compared.add(row, numbers[row], limits[row])
        yield {"cell": cell["name"], "seed": seed, "case": name,
               "correct": compared.correct, "compared": compared.as_dict(),
               "reference_s": reference_s, **numbers}


def main(argv) -> int:
    import jax

    from benchmark import harness

    cell = harness.load_cell(argv[1])
    devices = jax.devices()
    harness.enable_compile_cache()
    caught = True
    for seed in (int(s) for s in argv[2:]):
        for line in read_cases(cell, seed):
            caught &= not line["correct"]
            print(json.dumps(dict(
                line, platform=devices[0].platform,
                kind=devices[0].device_kind, devices=len(devices))),
                flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
