#!/usr/bin/env python
"""Batch-knee sweep for the round-4 headline engine (run alone on TPU).

The bench headline batch (24) was tuned in round 2 for the
*materialized* engine, whose f32 volume pyramid for 24 pairs fills
~6 GB of HBM. The banded on-demand engine stores no volume
(volume_memory: 0.69 vs 1.07 GB at b4), so its throughput knee may sit
at a larger batch. Sweeps Sintel-resolution test_mode forward over
batch sizes on both engines and prints one JSON line; feeds the
bench.py BATCH decision (recorded in BASELINE.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from raft_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

_res = os.environ.get("RAFT_KNEE_RES", "440,1024").split(",")
if len(_res) != 2:
    raise SystemExit(f"RAFT_KNEE_RES must be 'H,W', got "
                     f"{os.environ['RAFT_KNEE_RES']!r}")
H, W = int(_res[0]), int(_res[1])
ITERS = int(os.environ.get("RAFT_KNEE_ITERS", "12"))
# The round-6 fused GRU kernel changes the per-iteration cost, so the
# knee may move; RAFT_KNEE_GRU pins RAFT_GRU_PALLAS for the whole sweep
# and the payload records which arm produced the numbers.
if os.environ.get("RAFT_KNEE_GRU"):
    os.environ["RAFT_GRU_PALLAS"] = os.environ["RAFT_KNEE_GRU"]
WARMUP, REPS = 2, 6
BATCHES = tuple(int(b) for b in
                os.environ.get("RAFT_KNEE_BATCHES", "24,32,48,64").split(","))


def main():
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    rng = jax.random.PRNGKey(0)
    img1 = jax.random.uniform(rng, (1, H, W, 3), jnp.float32) * 255.0
    base = RAFT(RAFTConfig(iters=ITERS, mixed_precision=True))
    variables = base.init({"params": rng, "dropout": rng}, img1, img1,
                          iters=1)
    out = {"resolution": [H, W], "iters": ITERS, "reps": REPS,
           "gru": os.environ.get("RAFT_GRU_PALLAS") or "auto"}

    for name, alt in (("alternate", True), ("all_pairs", False)):
        model = RAFT(RAFTConfig(iters=ITERS, mixed_precision=True,
                                alternate_corr=alt))

        for batch in BATCHES:
            def arm(batch=batch, model=model, name=name):
                # jit constructed per attempt (not hoisted): after a
                # *runtime* failure the band-retry ladder changes
                # RAFT_CORR_BAND, and a hoisted jit would replay the
                # cached failing executable on every rung instead of
                # re-tracing under the new env (ADVICE r4 low-1;
                # bench.py's alternate_arm does the same).
                fwd = jax.jit(lambda a, b, m=model: (
                    lambda f: (f, jnp.sum(f)))(m.apply(variables, a, b,
                                                       test_mode=True)[1]))
                img = jnp.broadcast_to(img1, (batch, H, W, 3))
                for _ in range(WARMUP):
                    float(fwd(img, img)[1])
                t0 = time.perf_counter()
                for _ in range(REPS):
                    o = fwd(img, img)
                float(o[1])
                rate = REPS * batch / (time.perf_counter() - t0)
                out[f"{name}_b{batch}_pairs_per_sec"] = round(rate, 2)

            arm()               # an OOM or compile error ends the sweep
    print(json.dumps(out))


if __name__ == "__main__":
    main()
