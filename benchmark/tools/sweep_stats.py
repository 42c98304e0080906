#!/usr/bin/env python3
"""How much of the lookup kernel's y-sweep the diagonal rule leaves, on
a pass cell's own seeded flows.

    python3 benchmark/tools/sweep_stats.py --workload <pass cell> --seed <n>

Builds the cell's model with the benchmark's weights, runs it over
``--pairs`` of the seeded frame pairs (padded as the pass pads them) for
``k - 1`` iterations, and hands the coordinates that iteration ``k``
looks up (``grid + flow``) to ``raft_tpu.ops.corr_pallas.sweep_stats``:
the (row, offset) pairs the kernel folds beside those a dense sweep of
the same chunk-aligned band folds and those that can carry a nonzero
weight, and how many diagonals a query tile has live at level 0. A count
from shapes and coordinates, not a time: it reads the same on the CPU
(where the flows differ from the chip's in the last bits of bfloat16)
and on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--pairs", type=int, default=4)
    p.add_argument("--iterations", type=int, nargs="+", default=None,
                   help="lookups to count (default: the first and the last)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.drivers import dataset_pass
    from raft_tpu.ops.corr_pallas import sweep_stats

    cell = harness.load_cell(args.workload)
    traffic = cell["traffic"]
    predictor, variables = dataset_pass.build(cell, args.seed)
    model = predictor.model
    pool = dataset_pass.make_pool(args.seed, args.pairs, traffic["height"],
                                  traffic["width"])
    top, bottom, left, right = dataset_pass.sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    widths = ((0, 0), (top, bottom), (left, right), (0, 0))
    image1, image2 = (np.pad(np.stack(frames), widths, mode="edge")
                      for frames in zip(*pool))
    h, w = image1.shape[1] // 8, image1.shape[2] // 8
    radius, levels = model.config.radius, model.config.corr_levels
    shapes = [(h >> l, w >> l) for l in range(levels)]
    ys, xs = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    grid = np.stack([xs, ys], -1)[None]

    out = {"workload": args.workload, "seed": args.seed, "pairs": args.pairs,
           "device": jax.devices()[0].device_kind, "grid": [h, w],
           "radius": radius, "iterations": {}}
    for k in args.iterations or (1, traffic["iters"]):
        if k == 1:
            flow = np.zeros((args.pairs, h, w, 2), np.float32)
        else:
            flow = np.asarray(jax.jit(
                lambda v, a, b, k=k: model.apply(v, a, b, iters=k - 1,
                                                 test_mode=True)[0])(
                variables, jnp.asarray(image1), jnp.asarray(image2)),
                np.float32)
        stats = sweep_stats(grid + flow, shapes, radius)
        tq = stats["tq"]
        cy = (grid + flow)[..., 1].reshape(args.pairs, -1)
        cy = np.pad(cy, ((0, 0), (0, -cy.shape[1] % tq)),
                    mode="edge").reshape(-1, tq)
        live = (np.ceil(cy.max(1)) - np.floor(cy.min(1)) + 1)
        stats["share"] = stats["diagonal"] / max(stats["dense"], 1)
        stats["live_share"] = stats["live"] / max(stats["dense"], 1)
        stats["flow_px_mean"] = float(np.linalg.norm(flow, axis=-1).mean())
        stats["live_diagonals_level0"] = {
            "median": float(np.median(live)), "p90": float(np.quantile(live, 0.9)),
            "max": float(live.max())}
        out["iterations"][str(k)] = stats
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
