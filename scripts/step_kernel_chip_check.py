#!/usr/bin/env python
"""The fused step kernel against the chained kernels, on the chip.

Interpret mode, the TPU interpreter and a compile for a described v5e
all pass bodies of ``ops/step_pallas.py`` that Mosaic then runs wrong
(PERF.md section 7, PR 36: spans sliced off a sublane tile boundary, kept
rows loaded whole and concatenated, TH 4). Only a run on the chip sees
that, and this is the run: at real feature-map shapes, seeded weights and
inputs, ``h2`` of ``fused_step`` must equal ``motion_encoder`` ->
``sepconv_gru`` bit for bit (the same products in the same order), and
``delta`` the jnp twin's to the compute dtype's rounding. Run it before
anything else is measured after a change to how the kernel slices or
stores its spans:

    chiprun --chips 1 -- python3 scripts/step_kernel_chip_check.py

``--tiny`` rehearses the script on the CPU in interpret mode (a few
seconds; it proves nothing about the chip). Prints one JSON line a case
and exits 1 if any case differs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.ops import gru_pallas, motion_pallas, step_pallas

C, CO, CC = 128, 126, 324

# (H, W, compute dtype, row tile or None for choose_rows): Sintel, chairs
# (an image row padded 62 -> 64), KITTI (156 -> 160), both rungs.
CASES = [(55, 128, "bfloat16", None), (55, 128, "bfloat16", 16),
         (55, 128, "float32", None), (46, 62, "bfloat16", None),
         (46, 62, "bfloat16", 8), (48, 156, "bfloat16", None)]
TINY = [(9, 7, "float32", 8), (9, 16, "bfloat16", 16)]


def seeded_mats(rng):
    def conv(kh, kw, cin, cout):
        scale = (2.0 / (kh * kw * cin)) ** 0.5
        return (jnp.asarray(rng.standard_normal((kh, kw, cin, cout)) * scale,
                            jnp.float32),
                jnp.asarray(rng.standard_normal((cout,)) * 0.1, jnp.float32))
    mm = motion_pallas.pack_weights(
        conv(1, 1, CC, 256), conv(3, 3, 256, 192), conv(7, 7, 2, 128),
        conv(3, 3, 128, 64), conv(3, 3, 256, CO))
    gm = gru_pallas.pack_weights(
        tuple(conv(1, 5, 3 * C, C) for _ in range(3)),
        tuple(conv(5, 1, 3 * C, C) for _ in range(3)), C)
    fm = step_pallas.pack_flow_head(conv(3, 3, C, 256), conv(3, 3, 256, 2))
    return mm, gm, fm


def check(h, w, dtype, th, mats, interpret, batch=2):
    mm, gm, fm = mats
    dt = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(h * w), 4)
    net = jnp.tanh(jax.random.normal(keys[0], (batch, h, w, C))).astype(dt)
    inp = jax.random.normal(keys[1], (batch, h, w, C)).astype(dt)
    corr = jax.random.normal(keys[2], (batch, h, w, CC)).astype(dt)
    flow = (3 * jax.random.normal(keys[3], (batch, h, w, 2))).astype(dt)

    @jax.jit
    def fused(net, inp, corr, flow):
        return step_pallas.fused_step(net, inp, corr, flow, mm, gm, fm,
                                      dtype=dt, interpret=interpret, th=th)

    @jax.jit
    def chained(net, inp, corr, flow):
        mot = motion_pallas.motion_encoder(flow, corr, mm, dtype=dt,
                                           interpret=interpret)
        return gru_pallas.sepconv_gru(net, (inp, mot), gm, dtype=dt,
                                      interpret=interpret)

    @jax.jit
    def twin(net, inp, corr, flow):
        def flat(a):
            return a.reshape(batch, h * w, a.shape[-1])
        gms = tuple(
            tuple(p.astype(dt) for p in m) if isinstance(m, tuple)
            else m.astype(dt)
            for m in gru_pallas.split_x_weights(gm, (C, CO + 2)))
        return step_pallas.reference_step(
            (w, h), flat(net), flat(inp), flat(flow), flat(corr),
            tuple(m.astype(dt) for m in mm), gms,
            tuple(m.astype(dt) for m in fm))[1]

    h2, delta = (np.asarray(o, np.float32)
                 for o in fused(net, inp, corr, flow))
    want_h2 = np.asarray(chained(net, inp, corr, flow), np.float32)
    want_delta = np.asarray(twin(net, inp, corr, flow),
                            np.float32).reshape(delta.shape)
    h2_gap = float(np.abs(h2 - want_h2).max())
    delta_gap = float(np.abs(delta - want_delta).max())
    # 'delta' has no chained kernel to equal: eight bfloat16 ulp of its
    # scale (the twin's float32 products run at XLA's default precision
    # on the chip, so float32 is held no tighter).
    delta_limit = 8 * float(jnp.finfo(jnp.bfloat16).eps) * max(
        1.0, float(np.abs(want_delta).max()))
    return {"h": h, "w": w, "dtype": dtype,
            "th": th or step_pallas.choose_rows(h, w, CC, dt.itemsize,
                                                flow_head=True),
            "h2_max_gap_vs_chained": h2_gap, "delta_max_gap_vs_twin": delta_gap,
            "delta_limit": delta_limit,
            "ok": h2_gap == 0.0 and delta_gap <= delta_limit,
            "platform": jax.devices()[0].platform}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU in interpret mode")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.tiny):
        sys.exit("no TPU: this check means something only on the chip "
                 "(--tiny rehearses the script in interpret mode)")
    mats = seeded_mats(np.random.default_rng(0))
    bad = 0
    for h, w, dtype, th in (TINY if args.tiny else CASES):
        line = check(h, w, dtype, th, mats, interpret=not on_chip)
        print(json.dumps(line), flush=True)
        bad += not line["ok"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
