#!/usr/bin/env python
"""The lookup kernel's 2-D query tiles on the chip: equality first, then
times.

Interpret mode and a compile for a described v5e cannot see a slice
that Mosaic then reads wrong (PERF.md section 7, fused step (d)), so the
forward of ``ops/corr_pallas.py`` with the tile ``choose_query_tile``
picks is held, on the chip, to a raster launch of the same lookup: bit
for bit, or the difference reported. With ``--parent DIR`` the raster
launch is the kernel module of another checkout
(``DIR/raft_tpu/ops/corr_pallas.py``, e.g. a ``git archive`` of the
parent commit), else this tree's own raster path. Shapes: Sintel
(RAFT-large and RAFT-small), chairs and KITTI feature grids; flows
smooth, rough (+-3 px of noise a pixel) and wild (+-16 px: tiles whose
columns no window holds read the level a window at a time). On one
chip:

    python3 scripts/corr_kernel_chip_check.py [--parent _parent] [--time]

``--time`` then times the lookup alone at the pass cells' batch of 128
on the Sintel grid (and on chairs' grid, float32, at 32), as the
refinement loop calls it (operands laid out once, outside the
timed call), for the raster tile and 2-D candidates (and the parent's
kernel, laying them out each call, with ``--parent``), each flow,
beside the chooser's count of each tiling's work (``_query_work``) and
``sweep_stats``' product rows a query. ``--tiny`` rehearses the script
on the CPU in interpret mode (it proves nothing about the chip). Prints
one JSON line a case, writes them to ``chiprun_out/corr_check.jsonl``,
and exits 1 if any case differs.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.models.corr import build_feature_pyramid
from raft_tpu.ops import corr_pallas as cp

# (name, H, W, C, radius, operand dtype): feature grids of the cells'
# frames and KITTI's 375 x 1242 (padded to 376 x 1248).
CASES = [("sintel_large", 55, 128, 256, 4, "bfloat16"),
         ("sintel_small", 55, 128, 128, 3, "bfloat16"),
         ("chairs", 46, 62, 256, 4, "float32"),
         ("kitti", 47, 156, 256, 4, "bfloat16")]
TINY = [("tiny", 16, 64, 16, 3, "float32")]     # a tile of 8 x 32 forced
FLOWS = {"smooth": 0.0, "rough": 3.0, "wild": 16.0}
# (name, H, W, C, radius, operand dtype, batch) timed: the pass cells'
# launches, and the chairs cell's float32 one (float32 levels at Sintel
# overrun the launch's scoped VMEM, at the parent too)
TIMED = [("large_bf16", 55, 128, 256, 4, "bfloat16", 128),
         ("small_bf16", 55, 128, 128, 3, "bfloat16", 128),
         ("chairs_f32", 46, 62, 256, 4, "float32", 32)]


def lookup_inputs(h, w, c, dtype, batch, noise, seed):
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    f1 = jnp.asarray(rng.standard_normal((batch, h, w, c)), dt)
    f2 = jnp.asarray(rng.standard_normal((batch, h, w, c)), dt)
    ys, xs = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    phase = rng.uniform(0, 2 * np.pi, (batch, 1, 1))
    flow = np.stack([4 + 3 * np.sin(3 * np.pi * xs / w + phase),
                     -2 + 2 * np.cos(2 * np.pi * ys / h + phase)], -1)
    flow = flow + rng.uniform(-noise, noise, flow.shape) if noise else flow
    coords = np.stack([xs, ys], -1)[None] + flow
    return f1, build_feature_pyramid(f2, 4), jnp.asarray(coords, jnp.float32)


def load_parent(path):
    spec = importlib.util.spec_from_file_location(
        "parent_corr_pallas",
        os.path.join(path, "raft_tpu", "ops", "corr_pallas.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lookup_fn(radius, dtype, tiling, interpret, out_dtype, parent=None):
    if parent is not None:
        return jax.jit(lambda f1, pyr, coords:
                       parent.windowed_correlation_pallas_fused(
                           f1, pyr, coords, radius, mxu_dtype=dtype,
                           interpret=interpret, out_dtype=out_dtype))
    return jax.jit(lambda f1, pyr, coords: cp._fused(
        f1, pyr, coords, radius, True, dtype, interpret, None, True,
        out_dtype, tiling))


def census(coords, pyr, radius, tiling):
    stats = cp.sweep_stats(np.asarray(coords), [p.shape[1:3] for p in pyr],
                           radius, tiling)
    return {"products_a_query": [v["products"] / stats["tiles"]
                                 for v in stats["levels"]],
            "xside_a_query": [v["xside"] / stats["tiles"]
                              for v in stats["levels"]],
            "windowed_share": [v["tiles_windowed"] / stats["tiles"]
                               for v in stats["levels"]]}


def check(cases, batch, interpret, parent, emit):
    bad = 0
    for name, h, w, c, radius, dtype in cases:
        levels = cp._level_geometry([(h >> l, w >> l) for l in range(4)])
        tiling = (cp.choose_query_tile(h, w, levels, radius, c)
                  or cp._tiling(8, 32, levels, radius))
        new = lookup_fn(radius, dtype, tiling, interpret, jnp.float32)
        old = lookup_fn(radius, dtype, None, interpret, jnp.float32, parent)
        for k, (flow, noise) in enumerate(FLOWS.items()):
            f1, pyr, coords = lookup_inputs(h, w, c, dtype, batch, noise, k)
            got = np.asarray(new(f1, pyr, coords))
            want = np.asarray(old(f1, pyr, coords))
            equal = bool(np.array_equal(got, want))
            bad += not equal
            emit({"case": name, "flow": flow, "grid": [h, w],
                  "dtype": dtype, "tile": tiling and [tiling.th, tiling.tw],
                  "windows": tiling and [list(x) for x in tiling.windows],
                  "equal": equal,
                  "differing": int((got != want).sum()),
                  "max_abs_diff": float(np.abs(got - want).max()),
                  "max_abs": float(np.abs(want).max()),
                  **census(coords, pyr, radius, tiling)})
    return bad


def time_ms(fn, args, reps):
    fn(*args).block_until_ready()
    times = []
    for _ in range(reps):
        start = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - start)
    return 1e3 * statistics.median(times), 1e3 * min(times)


def timings(timed, interpret, parent, emit, reps):
    """The lookup alone, as the refinement loop calls it: over operands
    laid out once (``_operands``, outside the timed call); the parent's
    kernel as it is called, laying them out each call."""
    for name, h, w, c, radius, dtype, batch in timed:
        levels = cp._level_geometry([(h >> l, w >> l) for l in range(4)])
        chosen = cp.choose_query_tile(h, w, levels, radius, c)
        candidates = [None] + [cp._tiling(256 // tw, tw, levels, radius)
                               for tw in (16, 32, 64)]
        data = {flow: lookup_inputs(h, w, c, dtype, batch, noise, 7)
                for flow, noise in FLOWS.items()}
        out_dtype = jnp.dtype(dtype)
        if parent is not None:
            fn = lookup_fn(radius, dtype, None, interpret, out_dtype, parent)
            for flow, args in data.items():
                median, least = time_ms(fn, args, reps)
                emit({"timed": name, "flow": flow, "batch": batch,
                      "tile": "parent", "ms_median": median,
                      "ms_min": least})
        fn = jax.jit(lambda ops, coords: cp.windowed_lookup(
            ops, coords, radius, mxu_dtype=dtype, interpret=interpret,
            out_dtype=out_dtype))
        for tiling in candidates:
            build = jax.jit(lambda f1, pyr: cp._operands(f1, pyr, tiling))
            for flow, (f1, pyr, coords) in data.items():
                median, least = time_ms(fn, (build(f1, pyr), coords), reps)
                emit({"timed": name, "flow": flow, "batch": batch,
                      "tile": tiling and [tiling.th, tiling.tw],
                      "chosen": tiling == chosen,
                      "ms_median": median, "ms_min": least,
                      "work_count": cp._query_work(h, w, levels, radius, c,
                                                   True, tiling),
                      **census(coords, pyr, radius,
                               tiling or cp._choose_tile(h * w))})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--parent", default=None)
    p.add_argument("--time", action="store_true")
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--batch", type=int, default=8)
    args = p.parse_args(argv)
    interpret = jax.default_backend() != "tpu"
    if interpret and not args.tiny:
        print("no TPU: run with --tiny to rehearse on the CPU")
        return 2
    parent = load_parent(args.parent) if args.parent else None
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "corr_check.jsonl"), "a")

    def emit(line):
        line = {"device": jax.devices()[0].device_kind, **line}
        print(json.dumps(line), flush=True)
        log.write(json.dumps(line) + "\n")
        log.flush()

    bad = check(TINY if args.tiny else CASES, 1 if args.tiny else args.batch,
                interpret, parent, emit)
    if args.time and not bad:
        timings([case + (1,) for case in TINY] if args.tiny else TIMED,
                interpret, parent, emit, 1 if args.tiny else 8)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
