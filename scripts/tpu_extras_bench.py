#!/usr/bin/env python
"""One-shot TPU measurement sweep for the non-headline benchmarks.

Covers, in independent sections (each guarded so one failure doesn't sink
the rest; results appended per-section to ``TPU_EXTRAS.json``):

* ``sparse_train``  — SparseRAFT train-step timing at the fork's active
  resolution (352x480, ``train_standard.sh:6``), batch swept.
* ``raft_train``    — canonical RAFT train-step timing at the original
  chairs-stage resolution (368x496, ``train_mixed.sh:3``), batch swept.
* ``kitti_eval``    — canonical RAFT eval forward at KITTI resolution
  (1242x375 → padded 1248x384, ``BASELINE.json`` configs[4]) in mixed
  precision, all-pairs vs ``alternate_corr``, with per-program
  compiled-footprint telemetry.
* ``volume_memory`` — compiled HBM footprints (no execution) for the
  two correlation regimes at a volume-dominated point (Sintel, batch 4),
  where the on-demand path's memory advantage is visible.
* ``batch1``        — single-pair latency breakdown (the bench's
  batch-1 gap): batch sweep 1-4. Round-2 result: per-pair cost is flat
  b1→b3 and only falls at b4, i.e. the gap is small-tile MXU/VPU
  utilization, not host latency (see BASELINE.md).
* ``msda_dense``    — one ``DeformableTransformerEncoderLayer`` at dense
  HW-token scale (the gather-bound path flagged in VERDICT r1 #10),
  jnp vs Pallas backends.
* ``encoder_family`` — end-to-end ours_07-lineage forward (SparseRAFT
  with active encoder stacks), MSDA auto-Pallas vs forced gather path.
* ``msda_threshold`` — raw-op backend crossover across the dense-query
  dispatch boundary (query-count sweep, fresh jit per arm).
* ``golden_on_chip`` — golden parity EPEs measured on the chip for the
  all-pairs / banded-alternate / mixed-precision-policy arms (the CPU
  suite only runs the Pallas kernel in interpreter mode).

Run alone on the TPU host (one process per chip):

    python scripts/tpu_extras_bench.py [section ...]

Timing uses a scalar host readback after every measured region.
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

# The one persistent compilation cache every entry point shares.
enable_compile_cache()

OUT_PATH = "TPU_EXTRAS.json"
WARMUP, REPS = 2, 10


def _sync(x) -> float:
    return float(jnp.sum(x) if x.ndim else x)


def _time(fn, *args, reps: int = REPS) -> float:
    """Mean seconds per call; dispatch back-to-back, readback once."""
    for _ in range(WARMUP):
        _sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / reps


def _compile(jitted, *args):
    """One AOT compile used for BOTH timing and footprint, so nothing is
    compiled twice and per-program numbers aren't polluted by the
    process-lifetime ``memory_stats()`` high-water mark."""
    return jitted.lower(*args).compile()


def _hbm_gb(compiled) -> float:
    """Peak-HBM estimate from XLA's own buffer assignment."""
    try:
        ma = compiled.memory_analysis()
        total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                 ma.output_size_in_bytes)
        return round(total / 2 ** 30, 3)
    except Exception:
        return 0.0


def _train_rates(make_model, tcfg_kwargs, H, W, batches) -> dict:
    """Train-step timing sweep shared by the raft_train / sparse_train
    sections: state + jitted step per batch size, timed with the scalar
    readback, peak HBM from runtime telemetry or XLA buffer assignment."""
    from raft_tpu.config import TrainConfig
    from raft_tpu.parallel import create_train_state, make_train_step

    out = {"resolution": [H, W]}
    for batch in batches:
        tcfg = TrainConfig(batch_size=batch, image_size=(H, W),
                           **tcfg_kwargs)
        model = make_model()
        rng = jax.random.PRNGKey(0)
        state = create_train_state(rng, model, tcfg, (H, W))
        step_fn = make_train_step(tcfg, donate=False)
        b = {"image1": jnp.ones((batch, H, W, 3)) * 127.0,
             "image2": jnp.ones((batch, H, W, 3)) * 127.0,
             "flow": jnp.zeros((batch, H, W, 2)),
             "valid": jnp.ones((batch, H, W))}

        # Compile the FULL train step once (lowering a loss-only wrapper
        # would let XLA DCE the backward + optimizer and fake both the
        # timing and the footprint).
        compiled = _compile(step_fn, state, b, rng)

        def step(state_in):
            s2, metrics = compiled(state_in, b, rng)
            return metrics["loss"]

        dt = _time(step, state, reps=5)
        out[f"train_step_ms_b{batch}"] = round(dt * 1e3, 2)
        out[f"train_samples_per_sec_b{batch}"] = round(batch / dt, 2)
        out[f"peak_hbm_gb_b{batch}"] = _hbm_gb(compiled)
    return out


def _alt_train_arm(out: dict, make_alt_model, tcfg_kwargs, H, W,
                   batches) -> None:
    """Banded-kernel training arm shared by sparse_train/raft_train:
    measure _train_rates on the on-demand model, merge under an
    ``alt_`` prefix, in the default band mode; an error surfaces."""
    alt = _train_rates(make_alt_model, tcfg_kwargs, H, W, batches)
    out.update({f"alt_{k}": v for k, v in alt.items()
                if k != "resolution"})


def sparse_train() -> dict:
    """SparseRAFT train-step rates at the fork's active resolution
    (352x480, ``train_standard.sh:6``); the ``alt_`` arms run the
    on-demand correlation path (``OursConfig.alternate_corr`` — deletes
    the volume + avg-pool chain the round-4 b8 profile measured at
    ~17% of the step)."""
    from raft_tpu.config import OursConfig

    def make_model(alternate=False):
        from raft_tpu.models import SparseRAFT
        return SparseRAFT(OursConfig(mixed_precision=True,
                                     alternate_corr=alternate))

    out = _train_rates(
        make_model,
        dict(model_family="sparse", iters=6, sparse_lambda=0.1),
        352, 480, (2, 4, 8))
    _alt_train_arm(out, lambda: make_model(alternate=True),
                   dict(model_family="sparse", iters=6, sparse_lambda=0.1),
                   352, 480, (4, 8))
    return out


def raft_train() -> dict:
    """Canonical RAFT train-step rates at the original chairs-stage
    resolution (368x496, ``train_mixed.sh:3``), mixed precision; the
    ``alt_`` arms train through the on-demand banded kernel (backward
    proven on-chip by the sparse A/B) instead of the materialized
    volume — numerics-identical, f32 accumulation either way."""
    from raft_tpu.config import RAFTConfig

    def make_model(alternate=False):
        from raft_tpu.models.raft import RAFT
        return RAFT(RAFTConfig(iters=12, mixed_precision=True,
                               alternate_corr=alternate))

    out = _train_rates(make_model, dict(iters=12), 368, 496, (4, 8))
    _alt_train_arm(out, lambda: make_model(alternate=True),
                   dict(iters=12), 368, 496, (4, 8))
    return out


def kitti_eval() -> dict:
    """Canonical RAFT at KITTI 1242x375 (padded 1248x384), iters=24,
    mixed precision: all-pairs vs the on-demand Pallas path."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    H, W = 384, 1248            # InputPadder kitti mode output
    out = {"resolution": [H, W], "iters": 24}
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (1, H, W, 3), jnp.float32) * 255.0
    # alternate_corr runs bf16 MXU operands by default under mixed
    # precision (corr_mxu_dtype="auto"); the f32-MXU arm isolates that
    # lever from the banding/fusion redesign.
    for name, alt, mxu in (("all_pairs", False, "auto"),
                           ("alternate_corr", True, "auto"),
                           ("alternate_corr_f32mxu", True, "float32")):
        cfg = RAFTConfig(iters=24, mixed_precision=True,
                         alternate_corr=alt, corr_mxu_dtype=mxu)
        model = RAFT(cfg)
        variables = model.init({"params": rng, "dropout": rng}, img, img,
                               iters=1)

        def run(model=model, variables=variables, name=name):
            def fwd(i1, i2):
                return jnp.sum(model.apply(variables, i1, i2,
                                           test_mode=True)[1])
            compiled = _compile(jax.jit(fwd), img, img)
            dt = _time(compiled, img, img)
            out[f"{name}_ms"] = round(dt * 1e3, 2)
            out[f"{name}_pairs_per_sec"] = round(1.0 / dt, 2)
            out[f"{name}_compiled_hbm_gb"] = _hbm_gb(compiled)

        run()
    return out


def volume_memory() -> dict:
    """Where the on-demand path's memory win actually shows: compiled
    footprints (XLA buffer assignment, no execution) for all-pairs vs
    alternate_corr at a volume-dominated operating point — Sintel
    440x1024, batch 4, the f32 volume pyramid alone is ~1.1 GB."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    H, W, batch = 440, 1024, 4
    out = {"resolution": [H, W], "batch": batch, "iters": 12}
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (batch, H, W, 3), jnp.float32) * 255.0
    for name, alt in (("all_pairs", False), ("alternate_corr", True)):
        cfg = RAFTConfig(iters=12, mixed_precision=True,
                         alternate_corr=alt)
        model = RAFT(cfg)
        variables = model.init({"params": rng, "dropout": rng},
                               img[:1], img[:1], iters=1)

        @jax.jit
        def fwd(i1, i2):
            return jnp.sum(model.apply(variables, i1, i2,
                                       test_mode=True)[1])

        out[f"{name}_compiled_hbm_gb"] = _hbm_gb(_compile(fwd, img, img))
    return out


def loader_train() -> dict:
    """End-to-end train rate WITH the real input pipeline (round 5,
    VERDICT r4 #3): synthetic-but-real-shaped .ppm/.flo files on disk,
    read+decoded+augmented through the actual loader
    (``fetch_dataloader``-equivalent construction) feeding the jitted
    canonical-RAFT train step at the chairs operating point. Compares
    the loader-fed steady state against the synthetic-tensor-fed rate
    of the SAME compiled step, and records the host's core count — the
    capacity model is per-core loader rate x cores vs device rate
    (LOADER_BENCH.json: ~14-18 samples/s/core; a 1-core host is
    loader-bound by construction, a >=4-core pod host is not)."""
    import shutil
    import tempfile

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel import create_train_state, make_train_step
    from scripts.loader_bench import make_dataset, make_fixture

    H, W = 368, 496                      # chairs crop
    batch = 4
    out = {"resolution": [H, W], "batch": batch,
           "cpu_count": os.cpu_count()}
    root = tempfile.mkdtemp(prefix="loader_train_")
    try:
        make_fixture(root)
        ds = 20 * make_dataset(root)
        # the SAME loader-kind/worker resolution training uses
        # (select_loader: process pool on >=4-core hosts, thread
        # prefetcher on small hosts) so this measures the default path
        from raft_tpu.data.datasets import select_loader
        cls, workers = select_loader()
        out["loader_kind"] = cls.__name__
        out["loader_workers"] = workers
        loader = cls(ds, batch_size=batch, shuffle=True,
                     num_workers=workers, prefetch=4)

        tcfg = TrainConfig(batch_size=batch, image_size=(H, W),
                           num_steps=100, iters=12)
        model = RAFT(RAFTConfig(iters=12, mixed_precision=True,
                                alternate_corr=True))
        rng = jax.random.PRNGKey(0)
        state = create_train_state(rng, model, tcfg, (H, W))
        step_fn = make_train_step(tcfg, donate=False)

        it = iter(loader)
        b0 = next(it)
        b0 = {k: jnp.asarray(v) for k, v in b0.items()}
        compiled = _compile(step_fn, state, b0, rng)

        # synthetic-fed reference rate (device-bound ceiling)
        def synth(state_in):
            _, m = compiled(state_in, b0, rng)
            return m["loss"]
        dt = _time(synth, state, reps=5)
        out["synthetic_fed_samples_per_sec"] = round(batch / dt, 2)

        # loader-fed steady state: overlapped (loader prefetches while
        # the device steps), 20 steps after 3 warmup
        n_warm, n_meas = 3, 20
        k = 0
        t0 = None
        cur = state
        while k < n_warm + n_meas:
            try:
                nb = next(it)
            except StopIteration:
                it = iter(loader)
                continue
            nb = {kk: jnp.asarray(v) for kk, v in nb.items()}
            cur, metrics = compiled(cur, nb, rng)
            k += 1
            if k == n_warm:
                float(metrics["loss"])
                t0 = time.perf_counter()
        float(metrics["loss"])
        rate = n_meas * batch / (time.perf_counter() - t0)
        out["loader_fed_samples_per_sec"] = round(rate, 2)
        out["loader_efficiency"] = round(
            rate / out["synthetic_fed_samples_per_sec"], 3)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def batch1() -> dict:
    """The batch-1 latency question (VERDICT r1 #9): is a doubled batch
    free (pipeline slack) or proportional (compute-bound)?"""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    H, W = 440, 1024
    out = {"resolution": [H, W], "iters": 12}
    rng = jax.random.PRNGKey(0)
    cfg = RAFTConfig(iters=12, mixed_precision=True)
    model = RAFT(cfg)
    img1 = jax.random.uniform(rng, (1, H, W, 3), jnp.float32) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img1, img1,
                           iters=1)

    @jax.jit
    def fwd(i1, i2):
        return jnp.sum(model.apply(variables, i1, i2, test_mode=True)[1])

    for batch in (1, 2, 3, 4):
        img = jnp.broadcast_to(img1, (batch, H, W, 3))
        dt = _time(fwd, img, img)
        out[f"ms_b{batch}"] = round(dt * 1e3, 2)
        out[f"pairs_per_sec_b{batch}"] = round(batch / dt, 2)

    # banded-engine arm (round 5, VERDICT r4 #4): does the b2/b3
    # superlinear-cost anomaly reproduce on the on-demand kernel, or is
    # it a materialized-pipeline (volume/lookup layout) artifact?
    amodel = RAFT(RAFTConfig(iters=12, mixed_precision=True,
                             alternate_corr=True))

    def alt_arm(batch):
        afwd = jax.jit(lambda i1, i2: jnp.sum(
            amodel.apply(variables, i1, i2, test_mode=True)[1]))
        img = jnp.broadcast_to(img1, (batch, H, W, 3))
        dt = _time(afwd, img, img)
        out[f"alt_ms_b{batch}"] = round(dt * 1e3, 2)
        out[f"alt_pairs_per_sec_b{batch}"] = round(batch / dt, 2)

    for batch in (1, 2, 3, 4):
        alt_arm(batch)
    return out


def msda_dense() -> dict:
    """DeformableTransformerEncoderLayer at dense HW-token scale
    (sparse-family stride-8 grid of the fork's training res): the
    gather-based jnp core vs the hat-matmul Pallas kernel
    (``raft_tpu/ops/msda_pallas.py``; ``backend`` dispatch)."""
    from raft_tpu.models.deformable import \
        DeformableTransformerEncoderLayer, DeformableTransformerEncoder

    out = {}
    for (h, w) in ((44, 60), (88, 120)):
        d_model = 128
        tokens = h * w
        for backend in ("jnp", "pallas"):
            layer = DeformableTransformerEncoderLayer(
                d_model=d_model, d_ffn=d_model * 4, dropout=0.0,
                activation="gelu", n_levels=1, n_heads=8, n_points=4,
                backend=backend)
            rng = jax.random.PRNGKey(0)
            src = jax.random.normal(rng, (1, tokens, d_model))
            ref = DeformableTransformerEncoder.get_reference_points(
                [(h, w)])
            ref = jnp.broadcast_to(ref, (1, tokens, 1, 2))
            variables = layer.init({"params": rng}, src, None, ref,
                                   [(h, w)])

            @jax.jit
            def fwd(s):
                return jnp.sum(layer.apply(variables, s, None, ref,
                                           [(h, w)]))

            dt = _time(fwd, src)
            out[f"tokens_{tokens}_{backend}_ms"] = round(dt * 1e3, 3)
    return out


def encoder_family() -> dict:
    """End-to-end forward of the ours_07-lineage model (SparseRAFT with
    active deformable encoder stacks — the dense-query regime) at the
    fork's training resolution, with the MSDA auto dispatch (Pallas on
    TPU) vs the gather path forced via the dispatch threshold."""
    from raft_tpu.config import OursConfig
    from raft_tpu.models import SparseRAFT
    from raft_tpu.ops import msda

    # The A/B below is only meaningful where the auto dispatch can pick
    # the kernel — assert rather than silently record jnp-vs-jnp.
    assert jax.default_backend() == "tpu", \
        "encoder_family compares MSDA backends; auto==pallas only on TPU"
    H, W, batch = 352, 480, 4
    out = {"resolution": [H, W], "batch": batch, "encoder_iterations": 2,
           "platform": jax.default_backend()}
    model = SparseRAFT(OursConfig(mixed_precision=True,
                                  encoder_iterations=2))
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (batch, H, W, 3), jnp.float32) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img, img)

    saved = msda._PALLAS_MIN_QUERIES
    hlo_fingerprint = {}
    try:
        for name, threshold in (("auto_pallas", saved),
                                ("jnp", 10 ** 9)):
            msda._PALLAS_MIN_QUERIES = threshold

            # A FRESH jitted callable per arm: jax's tracing cache is
            # keyed on the function object, so re-lowering one shared
            # `fwd` would silently reuse the jaxpr traced under the
            # previous threshold and time the same program twice.
            def arm_fwd(i1, i2):
                return jnp.sum(model.apply(variables, i1, i2,
                                           test_mode=True)[1])

            compiled = _compile(jax.jit(arm_fwd), img, img)
            hlo_fingerprint[name] = hash(compiled.as_text())
            dt = _time(compiled, img, img)
            out[f"{name}_ms"] = round(dt * 1e3, 2)
            out[f"{name}_pairs_per_sec"] = round(batch / dt, 2)
    finally:
        msda._PALLAS_MIN_QUERIES = saved
    assert hlo_fingerprint["auto_pallas"] != hlo_fingerprint["jnp"], \
        "A/B arms compiled to identical programs — dispatch didn't switch"
    out["arms_compiled_distinct"] = True
    return out


def msda_threshold() -> dict:
    """Measure the MSDA backend crossover across the dispatch boundary
    (VERDICT r2 #9: ``_PALLAS_MIN_QUERIES = 512`` was picked, not
    measured — the round-2 crossover data points were 2640/10560 tokens
    only). Raw op timing, fresh jit per arm, dense-regime value map
    (stride-8 grid of the fork's training res, d_model=128, 8 heads)."""
    from raft_tpu.ops import msda
    from raft_tpu.ops.msda import ms_deform_attn

    h, w, m, d, p, L = 44, 60, 8, 16, 4, 1
    s = h * w
    shapes = ((h, w),)
    rng = jax.random.PRNGKey(0)
    value = jax.random.normal(rng, (1, s, m, d), jnp.float32)
    out = {"value_tokens": s, "heads": m, "head_dim": d,
           "current_threshold": msda._PALLAS_MIN_QUERIES}
    for lq in (128, 256, 512, 1024, 2048, s):
        loc = jax.random.uniform(jax.random.PRNGKey(lq),
                                 (1, lq, m, L, p, 2), jnp.float32)
        wts = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(lq + 1),
                              (1, lq, m, L, p)), axis=-1)
        for backend in ("jnp", "pallas"):
            def arm(v, l, a, _b=backend):
                return jnp.sum(ms_deform_attn(v, shapes, l, a, backend=_b))
            compiled = _compile(jax.jit(arm), value, loc, wts)
            dt = _time(compiled, value, loc, wts)
            out[f"lq{lq}_{backend}_us"] = round(dt * 1e6, 1)
    return out


def golden_on_chip() -> dict:
    """Hardware-accuracy validation of the round-3 kernel work: golden
    parity EPEs measured ON the chip (the CPU suite runs the Pallas
    kernel in interpreter mode only). Arms: all-pairs f32 and the banded
    Pallas alternate path (both vs the stored f32 torch outputs — expect
    float-noise, ~3e-6 on CPU), plus the mixed-precision policy arms
    (bf16 encoders/update + bf16 MXU operands + bf16 volume; the parity
    number then reads the whole bf16 compute-policy deviation against
    the f32-recorded golden — ~0.065 px on CPU, where the kernel/volume
    levers are inactive; the on-chip value bounds the full policy).

    Round 5 (VERDICT r4 #1): also records the *aggregate* EPE-vs-GT per
    arm and its drift against the torch-oracle manifest mean — the
    quantity the north star's 0.02 band actually constrains (per-pixel
    parity drift can exceed it while unbiased rounding leaves the
    aggregate untouched). ``*_hi`` arms re-run with
    ``RAFT_CORR_PRECISION=highest`` (3-pass f32-faithful MXU passes on
    the correlation matmuls) to isolate the MXU default-precision
    contribution and price the fix."""
    import json as _json

    from raft_tpu.evaluate import (ASSETS_DIR, load_predictor,
                                   validate_golden)

    weights = os.path.join(ASSETS_DIR, "golden", "weights.npz")
    with open(os.path.join(ASSETS_DIR, "golden", "manifest.json")) as f:
        manifest = _json.load(f)
    manifest_gt = float(sum(p["epe_vs_gt"] for p in manifest["pairs"])
                        / len(manifest["pairs"]))
    # Same-build CPU aggregates (scripts/golden_cpu_reference.py): the
    # matched-policy anchor — |EPE_tpu - EPE_cpu| at the SAME compute
    # policy is the chip-induced drift the 0.02 band constrains (the
    # bf16 policy's own ~+0.028 aggregate shift exists on CPU too).
    with open(os.path.join(ASSETS_DIR, "golden",
                           "cpu_reference.json")) as f:
        cpu_ref = _json.load(f)
    out = {"manifest_gt_epe": manifest_gt}
    for name, kw, precision in (
            ("all_pairs_f32", {}, None),
            ("alternate_f32", dict(alternate_corr=True), None),
            ("policy_mixed", dict(mixed_precision=True), None),
            ("policy_mixed_alt", dict(alternate_corr=True,
                                      mixed_precision=True), None),
            ("all_pairs_f32_hi", {}, "highest"),
            ("alternate_f32_hi", dict(alternate_corr=True), "highest"),
            ("policy_mixed_hi", dict(mixed_precision=True), "highest"),
            ("policy_mixed_alt_hi", dict(alternate_corr=True,
                                         mixed_precision=True),
             "highest")):

        def run(name=name, kw=kw, precision=precision):
            # corr_impl="fixed": each arm measures ITS engine — the
            # round-4 "auto" eval default would re-dispatch the
            # all-pairs arms onto the on-demand kernel on TPU.
            if precision:
                os.environ["RAFT_CORR_PRECISION"] = precision
            try:
                pred = load_predictor(weights, iters=12,
                                      corr_impl="fixed", **kw)
                res = validate_golden(pred)
            finally:
                os.environ.pop("RAFT_CORR_PRECISION", None)
            # raw float: the f32 arms measure float-noise-scale parity
            # that sub-1e-6 rounding would erase
            out[f"{name}_parity_epe"] = res["golden_parity_epe"]
            out[f"{name}_gt_epe"] = res["golden_gt_epe"]
            out[f"{name}_gt_drift"] = abs(res["golden_gt_epe"]
                                          - manifest_gt)
            policy = ("policy_mixed" if kw.get("mixed_precision")
                      else "all_pairs_f32")
            out[f"{name}_gt_drift_vs_cpu"] = abs(
                res["golden_gt_epe"] - cpu_ref[f"{policy}_gt_epe_cpu"])

        run()
    return out


def _warped_pairs(key, n, H, W, max_shift=10):
    """Synthetic *learnable* flow data: ``image2`` is ``image1`` rolled by
    a per-sample integer ``(dy, dx)``; ground-truth flow is the constant
    ``(dx, dy)``. Images are low-frequency random patterns (resized up
    8x) so local structure determines the shift — a model that learns
    nothing stays at the ~shift-magnitude EPE plateau, so the loss trend
    must come from actual optimization."""
    k1, k2 = jax.random.split(key)
    low = jax.random.uniform(k1, (n, H // 8, W // 8, 3))
    imgs = jax.image.resize(low, (n, H, W, 3), "linear") * 255.0
    shifts = jax.random.randint(k2, (n, 2), -max_shift, max_shift + 1)

    def roll_one(img, s):
        return jnp.roll(img, (s[0], s[1]), axis=(0, 1))     # (dy, dx)

    img2 = jax.vmap(roll_one)(imgs, shifts)
    flow = jnp.tile(shifts[:, None, None, ::-1].astype(jnp.float32),
                    (1, H, W, 1))                           # (dx, dy)
    return imgs, img2, flow, jnp.ones((n, H, W), jnp.float32)


def train_convergence() -> dict:
    """Sustained on-chip training: loss must *decrease*, not just step
    fast (VERDICT r3 #3). ~500 steps per family at the chairs-stage /
    active-fork configs (reference ``train_mixed.sh:3`` /
    ``train_standard.sh:6``), fixed seed, batches cycling a small pool
    of synthetic warped pairs (overfit-able by construction). Commits
    the every-10-steps loss curve plus steps/sec."""
    from raft_tpu.config import (OursConfig, RAFTConfig, TrainConfig,
                                 sparse_corr_from_env)
    from raft_tpu.models import SparseRAFT
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel import create_train_state, make_train_step

    steps = int(os.environ.get("RAFT_CONV_STEPS", "500"))
    # RAFT_CONV_ALT=1 runs the raft family through the on-demand banded
    # engine (the round-4 train default on TPU); the sparse family
    # follows its own config default either way.
    raft_alt = os.environ.get("RAFT_CONV_ALT") == "1"
    every, pool, batch = max(1, steps // 50), 16, 4
    out = {"steps": steps, "batch": batch, "seed": 0,
           "raft_engine": "alternate" if raft_alt else "materialized"}
    for family, make_model, (H, W), tkw in (
            ("raft",
             lambda: RAFT(RAFTConfig(iters=12, mixed_precision=True,
                                     alternate_corr=raft_alt)),
             (368, 496), dict(iters=12)),
            ("sparse",
             lambda: SparseRAFT(OursConfig(
                 mixed_precision=True,
                 alternate_corr=sparse_corr_from_env())),
             (352, 480), dict(model_family="sparse", iters=6,
                              sparse_lambda=0.1))):
        tcfg = TrainConfig(batch_size=batch, image_size=(H, W),
                           num_steps=steps, lr=4e-4, **tkw)
        rng = jax.random.PRNGKey(0)
        i1, i2, fl, va = _warped_pairs(jax.random.PRNGKey(7), pool, H, W)
        state = create_train_state(rng, make_model(), tcfg, (H, W))
        step_fn = make_train_step(tcfg)
        losses = []
        t0 = time.perf_counter()
        for s in range(steps):
            lo = (s * batch) % pool
            sel = (lo + jnp.arange(batch)) % pool
            b = {"image1": i1[sel], "image2": i2[sel],
                 "flow": fl[sel], "valid": va[sel]}
            state, metrics = step_fn(state, b, rng)
            if s % every == 0 or s == steps - 1:
                losses.append(round(float(metrics["loss"]), 4))
        wall = time.perf_counter() - t0
        k = max(1, len(losses) // 10)
        head = sum(losses[:k]) / k
        tail = sum(losses[-k:]) / k
        out[family] = {
            "resolution": [H, W],
            f"loss_curve_every{every}": losses,
            "loss_head_mean": round(head, 4),
            "loss_tail_mean": round(tail, 4),
            "decreased": bool(tail < head),
            "steps_per_sec": round(steps / wall, 3)}
    return out


SECTIONS = {"sparse_train": sparse_train, "raft_train": raft_train,
            "kitti_eval": kitti_eval, "volume_memory": volume_memory,
            "batch1": batch1, "msda_dense": msda_dense,
            "encoder_family": encoder_family,
            "msda_threshold": msda_threshold,
            "golden_on_chip": golden_on_chip,
            "loader_train": loader_train,
            "train_convergence": train_convergence}


def main(argv):
    names = argv or list(SECTIONS)
    print("devices:", jax.devices(), flush=True)
    results = {}
    try:
        with open(OUT_PATH) as f:
            results = json.load(f)
    except Exception:
        pass
    for name in names:
        t0 = time.time()
        try:
            results[name] = SECTIONS[name]()
            results[name]["wall_s"] = round(time.time() - t0, 1)
            print(f"{name}: {json.dumps(results[name])}", flush=True)
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{name}: FAILED {e}", flush=True)
        # atomic rewrite: a timeout mid-dump must not leave a truncated
        # artifact where a full committed one stood
        tmp = OUT_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=1)
        os.replace(tmp, OUT_PATH)
    print("wrote", OUT_PATH)


if __name__ == "__main__":
    main(sys.argv[1:])
