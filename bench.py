"""Headline benchmark: Sintel image-pairs/sec/chip @ iters=12.

Runs the flagship canonical RAFT-large forward (test_mode) at Sintel
resolution (436x1024 padded to 440x1024, the ``InputPadder`` pad-to-/8
shape) on the available accelerator and prints ONE JSON line. The
headline value is the eval-default correlation engine (round 4 flip:
the fused on-demand banded kernel — the reference's own sanctioned
``--alternate_corr`` eval mode, ``core/corr.py:64-92`` — wherever it
fits VMEM; identical parameters and golden-parity numerics), with the
materialized-volume arm always published alongside as
``value_all_pairs`` and promoted back to the headline if the banded arm
fails every band-mode rung. ``vs_baseline`` is measured against the
BASELINE.md north-star denominator: the PyTorch reference on 1xV100 at
the same setting, estimated at 10 image-pairs/sec (RAFT paper reports
~10 fps at 1088x436 / 12 iters on a 1080Ti-class GPU; BASELINE.md
records no in-repo number, so the target "≥4x vs V100" is normalized to
this documented estimate).

Throughput is measured at batch=24 (the sweep's knee on v5e-1; the f32
all-pairs volume pyramid for 24 pairs is ~6 GB of the 16 GB HBM): per-chip
eval throughput is the metric, and batching frame pairs is how the
framework evaluates a 1000-frame Sintel pass on TPU; reps are dispatched
back-to-back and synced once via a scalar host readback, so the device
pipeline rate is measured, not the host<->device round-trip latency of a
lone request.

Failure contract: a mode either prints its one JSON line or the process
exits non-zero with the error on stderr. Nothing is caught and turned
into a null artifact; no mode walks to a slower path when a compile
fails. The backend is initialised once, in this process — a chip belongs
to one process, so no child is spawned to probe it — and a run that
finds a CPU without ``JAX_PLATFORMS=cpu`` having asked for one fails
(``_platform``). The compile cache is ``raft_tpu.utils.compile_cache``'s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

METRIC = "sintel_image_pairs_per_sec_per_chip_iters12"
UNIT = "image-pairs/sec"
BASELINE_PAIRS_PER_SEC = 10.0   # PyTorch ref, 1xV100 (see module docstring)


def _env_dim(name: str, default: int) -> int:
    """Operating-point override for explicitly-requested CPU smoke
    artifact captures (round 6: BENCH JSON regenerated on a CPU host at
    a smoke point with honest labels). Any override flips the payload's
    ``smoke_operating_point`` flag so a shrunken run can never be
    mistaken for the TPU trajectory."""
    raw = os.environ.get(name)
    return int(raw) if raw else default


H = _env_dim("RAFT_BENCH_H", 440)     # Sintel 436x1024 after pad-to-/8
W = _env_dim("RAFT_BENCH_W", 1024)
ITERS = _env_dim("RAFT_BENCH_ITERS", 12)
BATCH = _env_dim("RAFT_BENCH_BATCH", 24)
                                # materialized-arm knee (round-2 sweep:
                                # its bf16 volume pyramid OOMs at b64)
# Banded-arm operating point: the on-demand kernel stores no volume, so
# its knee sits far higher. Round-4 sweep: 82.7 @ b24, 90.7 @ b64, 93.7
# @ b128 (b64 chosen, within 3%). Round-5 re-sweep AFTER the transposed
# output store (batch_knee_probe, same day): 94.4 @ b64, 92.8 @ b96,
# **98.7 @ b128** — the tout win compounds with batch, so the headline
# arm moved to b128.
ALT_BATCH = _env_dim("RAFT_BENCH_ALT_BATCH", 128)
WARMUP = 2
REPS = _env_dim("RAFT_BENCH_REPS", 10)
_SMOKE_POINT = any(os.environ.get(k) for k in (
    "RAFT_BENCH_H", "RAFT_BENCH_W", "RAFT_BENCH_ITERS",
    "RAFT_BENCH_BATCH", "RAFT_BENCH_ALT_BATCH", "RAFT_BENCH_REPS"))
# sparse-family secondary metric: the fork's active training resolution
# (reference train_standard.sh:6: 352x480)
SPARSE_H, SPARSE_W, SPARSE_BATCH = 352, 480, 8

def _emit(payload: dict) -> None:
    """Print the mode's one JSON artifact line."""
    print(json.dumps(payload), flush=True)


def _platform() -> str:
    """Initialise the backend in this process and return its platform.
    A CPU is accepted only when ``JAX_PLATFORMS`` asked for one (a local
    smoke run); found unasked, it means the accelerator did not come up,
    and a full-size bench on it would be hours of the wrong number."""
    import jax

    from raft_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform == "cpu" and not os.environ.get(
            "JAX_PLATFORMS", "").startswith("cpu"):
        raise SystemExit(
            "bench.py found no accelerator (JAX fell back to the CPU); "
            "set JAX_PLATFORMS=cpu to ask for a CPU smoke run")
    return platform


def kernel_ab_arm(payload: dict, key: str, arms, measure, platform: str):
    """Shared fused-kernel A/B arm (knee-provenance discipline like the
    banded-vs-all-pairs arms): run ``measure()`` once per arm with that
    arm's trace-time env flags forced, recording each reading as
    ``value_{key}_{label}``. ``arms`` is ``((label, {FLAG: val, ...}),
    ...)`` — each arm's flags are forced together via ``forced_flag``
    (one ExitStack per arm) so the arm traces a fresh executable, and
    the surrounding env is restored afterwards so later sections run
    the ambient dispatch. ``measure`` must build a FRESH ``jax.jit``
    per call: the flags are trace-time, so reusing a jitted callable
    across arms would silently serve the first arm's executable. A
    failed arm fails the run. On CPU the forced-pallas arms run kernels under the
    Pallas interpreter — a parity tool, not a fast path — so a
    pallas<xla reading on a cpu-labelled artifact is expected and
    honest (kernel_ab_note says so in-band)."""
    import contextlib

    from raft_tpu.utils.envflags import forced_flag
    for label, env in arms:
        with contextlib.ExitStack() as stack:
            for flag, val in env.items():
                stack.enter_context(forced_flag(flag, val))
            payload[f"value_{key}_{label}"] = round(measure(), 3)
    if platform == "cpu":
        payload["kernel_ab_note"] = (
            "cpu capture: forced-pallas arms run under the Pallas "
            "interpreter — interpret-mode parity evidence, not a "
            "fast path; speed deltas are TPU measurements")


def main(gru: str = "ab", motion: str = "ab"):
    platform = _platform()
    import jax
    import jax.numpy as jnp
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    # TPU-first inference policy: bf16 encoders/update, f32 corr volume.
    cfg = RAFTConfig(iters=ITERS, mixed_precision=(platform == "tpu"))
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    img1 = jax.random.uniform(rng, (1, H, W, 3), jnp.float32) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img1, img1,
                           iters=1)

    @jax.jit
    def fwd(i1, i2):
        # Scalar-reduce the flow so syncing is a 4-byte host readback.
        flow_up = model.apply(variables, i1, i2, test_mode=True)[1]
        return flow_up, jnp.sum(flow_up)

    def throughput(batch: int, fwd_fn=None) -> float:
        fwd_fn = fwd_fn or fwd
        img = jnp.broadcast_to(img1, (batch, H, W, 3))
        for _ in range(WARMUP):
            float(fwd_fn(img, img)[1])
        # Dispatch all reps, sync once — measures device pipeline rate
        # (how eval/training actually stream batches), not the host↔device
        # round-trip latency of a lone request.
        # Keep only the newest output reference: execution is async, so
        # reps still pipeline back-to-back, but earlier ~86 MB flow
        # buffers are freed as they complete instead of 10 being pinned.
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fwd_fn(img, img)
        float(out[1])
        return REPS * batch / (time.perf_counter() - t0)

    # The materialized-volume arm runs first as the provisional
    # headline; the on-demand banded arm then takes the headline — since
    # round 4 it is the framework's eval-default engine (corr_impl="auto";
    # measured 84.3 vs 56.1 pairs/s at Sintel and 22.2 vs 18.4 at KITTI,
    # BASELINE.md), and the reference itself sanctions the on-demand
    # path as a first-class eval option (core/corr.py:64-92, README
    # --alternate_corr).
    pairs_per_sec = throughput(BATCH)
    payload = {
        "metric": METRIC,
        "value": round(pairs_per_sec, 3),
        "unit": UNIT,
        "batch": BATCH,
        "platform": platform,
        "vs_baseline": round(pairs_per_sec / BASELINE_PAIRS_PER_SEC, 3),
        "value_all_pairs": round(pairs_per_sec, 3),
        "headline_engine": "all_pairs",
        # Fused-kernel dispatches the headline ran under (trace-time;
        # 'auto' = fused Pallas kernel on TPU when eligible)
        "gru": os.environ.get("RAFT_GRU_PALLAS") or "auto",
        "motion": os.environ.get("RAFT_MOTION_PALLAS") or "auto",
        "resolution": f"{H}x{W}",
        "iters": ITERS,
        "reps": REPS,
    }
    if _SMOKE_POINT:
        # env-shrunken operating point (CPU artifact capture): mark it so
        # this line is never read as the TPU trajectory
        payload["smoke_operating_point"] = True
    headline_fwd = fwd
    headline_model = model
    if platform != "cpu":
        # On-demand banded-correlation arm (identical numerics, asserted
        # by tests): per iteration it touches only each query tile's
        # y-band of the target features instead of re-reading the
        # materialized volume pyramid. It runs the default band mode
        # (all three modes compile — tests/test_chip_compile.py); a
        # compile or run error here fails the run.
        cfga = RAFTConfig(iters=ITERS,
                          mixed_precision=(platform == "tpu"),
                          alternate_corr=True)
        modela = RAFT(cfga)

        def fwda(i1, i2):
            flow_up = modela.apply(variables, i1, i2, test_mode=True)[1]
            return flow_up, jnp.sum(flow_up)

        headline_fwd = jax.jit(fwda)
        alt_rate = throughput(ALT_BATCH, headline_fwd)
        headline_model = modela
        payload["value_alternate_corr"] = round(alt_rate, 3)
        payload["value"] = round(alt_rate, 3)
        payload["vs_baseline"] = round(alt_rate / BASELINE_PAIRS_PER_SEC, 3)
        payload["headline_engine"] = "alternate_banded"
        payload["batch"] = ALT_BATCH
        payload["batch_all_pairs"] = BATCH
    # single-pair throughput on the headline engine, apples-to-apples
    # with the latency-bound 10 pairs/sec V100 estimate the baseline is
    # normalized to
    batch1 = throughput(1, headline_fwd)
    payload["value_batch1"] = round(batch1, 3)
    payload["vs_baseline_batch1"] = round(
        batch1 / BASELINE_PAIRS_PER_SEC, 3)

    def early_exit_arm():
        # Iterate-to-convergence arm: re-trace the headline engine with
        # the masked convergence exit threaded into the refine scan and
        # measure the SAME operating point. iters_saved is the measured
        # per-sample (ITERS - iters_used) — what the tolerance says the
        # fixed-count loop overspends — while value_early_exit shows
        # what the masking itself costs in throughput (the masked scan
        # still runs full length with converged samples frozen, so this
        # arm measures the accounting the serving quality ladder feeds
        # on, not a wall-clock shortcut).
        tol = float(os.environ.get("RAFT_BENCH_EE_TOL", "0.1"))
        patience = int(os.environ.get("RAFT_BENCH_EE_PATIENCE", "2"))

        def fwde(i1, i2, m=headline_model):
            _, flow_up, used = m.apply(variables, i1, i2,
                                       test_mode=True,
                                       early_exit=(tol, patience))
            return flow_up, jnp.sum(flow_up), used

        jfwde = jax.jit(fwde)
        payload["value_early_exit"] = round(
            throughput(payload["batch"], jfwde), 3)
        img = jnp.broadcast_to(img1, (payload["batch"], H, W, 3))
        used = jax.device_get(jfwde(img, img)[2])
        payload["early_exit"] = {"tol": tol, "patience": patience}
        payload["iters_saved"] = {
            "mean": round(float(ITERS - used.mean()), 3),
            "min": int(ITERS - used.max()),
            "max": int(ITERS - used.min()),
            "iters": ITERS,
        }

    early_exit_arm()

    def headline_ab(key: str, flag: str):
        # Headline-engine A/B pass through the module-level
        # kernel_ab_arm helper: re-trace the headline model with the
        # named Pallas kernel forced ON ('1') and OFF ('0') and record
        # both readings as value_{key}_{pallas,xla}. measure() builds a
        # fresh jit per arm (trace-time flag — see the helper).
        def measure():
            def fwdk(i1, i2, m=headline_model):
                flow_up = m.apply(variables, i1, i2,
                                  test_mode=True)[1]
                return flow_up, jnp.sum(flow_up)

            return throughput(payload["batch"], jax.jit(fwdk))

        kernel_ab_arm(payload, key,
                      (("pallas", {flag: "1"}), ("xla", {flag: "0"})),
                      measure, platform)

    if gru == "ab":
        headline_ab("gru", "RAFT_GRU_PALLAS")

    if motion == "ab":
        # Round-7 motion-encoder arm, same contract as the GRU arm.
        headline_ab("motion", "RAFT_MOTION_PALLAS")

    if platform == "cpu":
        # full-size secondaries on CPU take hours; they are TPU
        # measurements, not part of the CPU smoke contract
        payload["sparse_skipped"] = "cpu"
    else:
        # A/B arm: force the old float32 volume storage. The
        # materialized arm's corr_dtype="auto" resolves to bf16
        # storage at inference under mixed precision (round-3
        # default flip — measured flow delta mean 0.0026 px at
        # Sintel res, BASELINE.md), so the f32 arm documents what
        # the lever buys. corr_dtype only changes storage, not
        # parameters, so the headline's variables are reused — no
        # second eager init.
        cfg32 = RAFTConfig(iters=ITERS,
                           mixed_precision=(platform == "tpu"),
                           corr_dtype="float32")
        model32 = RAFT(cfg32)

        @jax.jit
        def fwd32(i1, i2):
            flow_up = model32.apply(variables, i1, i2,
                                    test_mode=True)[1]
            return flow_up, jnp.sum(flow_up)

        payload["value_f32_volume"] = round(
            throughput(BATCH, fwd32), 3)
        payload.update(_sparse_metrics())
    _emit(payload)


def _sparse_metrics() -> dict:
    """Secondary metric: SparseRAFT forward throughput at the fork's
    active training resolution (352x480, ``train_standard.sh:6``).
    Same dispatch/sync discipline as the headline metric."""
    import jax
    import jax.numpy as jnp
    from raft_tpu.config import OursConfig, sparse_corr_from_env
    from raft_tpu.models import SparseRAFT

    platform = _platform()
    h, w, batch = SPARSE_H, SPARSE_W, SPARSE_BATCH
    model = SparseRAFT(OursConfig(mixed_precision=(platform == "tpu"),
                                  alternate_corr=sparse_corr_from_env()))
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (batch, h, w, 3), jnp.float32) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img, img)

    @jax.jit
    def fwd(i1, i2):
        flow_low, flow_up = model.apply(variables, i1, i2, test_mode=True)
        return jnp.sum(flow_up)

    for _ in range(WARMUP):
        float(fwd(img, img))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fwd(img, img)
    float(out)
    rate = REPS * batch / (time.perf_counter() - t0)
    return {"sparse_forward_pairs_per_sec": round(rate, 3),
            "sparse_batch": batch, "sparse_resolution": [h, w]}


STEP_METRIC = "fused_step_vs_chained_pairs_per_sec_speedup"

# Trace-time env for each refine-step arm. 'fused' forces the
# one-launch chained motion-encoder→GRU(→flow-head) kernel
# (ops/step_pallas.py); 'chained' forces the two per-kernel launches it
# replaces — the packed [motion‖flow] handoff buffer round-trips HBM
# between them every refine iteration; 'xla' turns all three off (the
# pure XLA conv path both kernels are tested bit-compatible against).
STEP_ARM_ENVS = (
    ("fused", {"RAFT_STEP_PALLAS": "1"}),
    ("chained", {"RAFT_STEP_PALLAS": "0",
                 "RAFT_MOTION_PALLAS": "1",
                 "RAFT_GRU_PALLAS": "1"}),
    ("xla", {"RAFT_STEP_PALLAS": "0",
             "RAFT_MOTION_PALLAS": "0",
             "RAFT_GRU_PALLAS": "0"}),
)


def step_main(arm: str = "ab"):
    """``python bench.py --step {ab,fused,chained,xla}`` — one-launch
    refine-iteration benchmark (round 10, BENCH_r10).

    ``ab`` (the committed-artifact arm) measures the SAME headline
    forward (RAFT-large, test_mode, headline operating point) under all
    three ``STEP_ARM_ENVS`` dispatches and publishes the fused/chained
    throughput ratio as the headline value, with every arm's reading in
    ``per_arm``. ``fused``/``chained``/``xla`` run a single arm for
    debugging (value stays null — a ratio needs both measurements).

    Alongside wall-clock, each Pallas arm carries the host-independent
    claim the fusion actually makes: ``handoff_hbm_bytes_per_iter``,
    the per-refine-iteration HBM traffic of the motion→GRU handoff.
    The chained arm writes the packed ``[motion‖flow]`` buffer
    (``B·(H/8)·(W/8)·128`` values) out of the motion launch and reads
    it back into the GRU launch — one write + one read per iteration;
    the fused arm keeps it VMEM-resident (0 bytes). The xla arm's
    traffic is left null: XLA's own fusion decisions are not modeled
    here, and a guessed number would impersonate a measurement."""
    platform = _platform()
    import jax
    import jax.numpy as jnp
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    cfg = RAFTConfig(iters=ITERS, mixed_precision=(platform == "tpu"))
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    img1 = jax.random.uniform(rng, (1, H, W, 3), jnp.float32) * 255.0
    variables = model.init({"params": rng, "dropout": rng}, img1, img1,
                           iters=1)

    def throughput(batch: int, fwd_fn) -> float:
        # Same dispatch/sync discipline as the headline metric: WARMUP
        # synced runs, then REPS back-to-back dispatches, one readback.
        img = jnp.broadcast_to(img1, (batch, H, W, 3))
        for _ in range(WARMUP):
            float(fwd_fn(img, img)[1])
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fwd_fn(img, img)
        float(out[1])
        return REPS * batch / (time.perf_counter() - t0)

    def measure():
        # Fresh jit per arm — the step/motion/gru flags are trace-time,
        # so each arm must build its own executable (see kernel_ab_arm).
        def fwdk(i1, i2):
            flow_up = model.apply(variables, i1, i2, test_mode=True)[1]
            return flow_up, jnp.sum(flow_up)

        return throughput(BATCH, jax.jit(fwdk))

    # Handoff arithmetic (see docstring). The packed buffer is 128
    # channels (126 motion + 2 flow, ops/layout.py invariant 6) in the
    # refine chain's compute dtype: bf16 under mixed precision (TPU),
    # f32 on the smoke hosts.
    dtype_bytes = 2 if platform == "tpu" else 4
    handoff_bytes = 2 * BATCH * (H // 8) * (W // 8) * 128 * dtype_bytes

    arms = (STEP_ARM_ENVS if arm == "ab"
            else tuple(a for a in STEP_ARM_ENVS if a[0] == arm))
    payload = {
        "metric": STEP_METRIC,
        "value": None,
        "unit": "x",
        "batch": BATCH,
        "platform": platform,
        "resolution": f"{H}x{W}",
        "iters": ITERS,
        "reps": REPS,
        "step_arm": arm,
        "handoff_channels": 128,
        "handoff_dtype_bytes": dtype_bytes,
    }
    kernel_ab_arm(payload, "step", arms, measure, platform)

    per_arm = {}
    for label, _env in arms:
        rec = {}
        rate = payload.pop(f"value_step_{label}", None)
        err = payload.pop(f"step_{label}_error", None)
        if rate is not None:
            rec["pairs_per_sec"] = rate
        if err is not None:
            rec["error"] = err
        if label == "fused":
            rec["handoff_hbm_bytes_per_iter"] = 0
        elif label == "chained":
            rec["handoff_hbm_bytes_per_iter"] = handoff_bytes
        else:               # xla: not modeled — see docstring
            rec["handoff_hbm_bytes_per_iter"] = None
        per_arm[label] = rec
    payload["per_arm"] = per_arm

    fused = per_arm.get("fused", {}).get("pairs_per_sec")
    chained = per_arm.get("chained", {}).get("pairs_per_sec")
    if fused and chained:
        payload["value"] = round(fused / chained, 3)
    if platform != "tpu":
        payload["smoke_operating_point"] = True
        payload["criterion_note"] = (
            "cpu capture: both Pallas arms run under the Pallas "
            "interpreter, so the wall-clock ratio is plumbing/parity "
            "evidence (three distinct executables, same numbers), not "
            "the TPU speedup. The host-independent claim is the "
            "handoff arithmetic: the chained arm round-trips the "
            "packed [motion‖flow] buffer through HBM every refine "
            "iteration (handoff_hbm_bytes_per_iter) while the fused "
            "arm keeps it VMEM-resident; the on-TPU capture is "
            "tracked as ROADMAP debt")
    _emit(payload)




SERVING_METRIC = "serving_vs_sequential_batch1_speedup"


def serving_main(replicas: int = 1, trace: bool = False):
    """``python bench.py serving [--replicas N]`` — dynamic-batching
    serving benchmark.

    Drives the serving engine (raft_tpu/serving/) with concurrent
    closed-loop clients and publishes its sustained throughput against
    the thing it replaces: a sequential batch-1 request loop over the
    SAME predictor on the same host. Emits ONE BENCH-compatible JSON
    line (same contract as the headline mode).

    Operating point is platform-adaptive: on TPU the flagship RAFT-large
    at Sintel resolution / iters=12 (the batch-1 gap this subsystem
    exists to close — BENCH_r05: 31.5 pairs/s at b1 vs 99.0 at b128);
    on CPU a small-model smoke point that completes in minutes and
    STILL verifies every response bit-for-bit. CPU hosts with one core
    (this container) have no dispatch gap to recover — the artifact says
    so explicitly in ``criterion_note`` instead of faking a speedup.

    ``--replicas N`` (default 1) serves through an N-replica
    :class:`~raft_tpu.serving.fleet.ServingFleet` instead of one
    engine. The artifact records ``replicas``, a ``topology`` label
    (``single-replica`` keeps the existing single-engine trajectory
    comparable across rounds) and per-replica warmup time/compiles —
    on one host extra replicas add routing, not compute, so the
    interesting numbers are the warmup-sharing and failover machinery,
    not the throughput.

    ``--trace`` enables request-scoped tracing for the run and writes
    the Perfetto-loadable Chrome trace JSON next to the bench; its path
    ships in the artifact as ``trace_artifact`` (validated by
    ``scripts/check_bench_schema.py``: the file must exist and parse as
    trace JSON). Off by default — the headline numbers stay measured on
    the zero-instrumentation path.
    """
    import jax

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import (ServingConfig, ServingEngine, loadgen,
                                  make_fleet)

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shapes = [(436, 1024)]
        small, iters = False, ITERS
        max_batch, concurrency, n_requests = 32, 16, 512
        max_wait_ms = 5.0
    else:
        shapes = [(64, 96), (61, 93)]     # two raws, one padded bucket
        small, iters = True, 4
        max_batch, concurrency, n_requests = 8, 8, 48
        max_wait_ms = 4.0

    tracer = None
    if trace:
        from raft_tpu.observability import enable_tracing
        tracer = enable_tracing()   # before engine build: captured at init

    predictor = load_predictor("random", small=small, iters=iters)
    frames = loadgen.make_frames(shapes, per_shape=2, seed=0)
    refs = loadgen.batched_reference_flows(frames=frames,
                                           predictor=predictor,
                                           max_batch=max_batch)
    seq = loadgen.sequential_baseline(predictor, frames,
                                      n_requests=max(n_requests // 4, 8))

    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        buckets=tuple(shapes), persistent_cache=True)
    if replicas <= 1:
        engine = ServingEngine(predictor, cfg)
        t0 = time.perf_counter()
        warm = engine.warmup()
        warmup_per_replica = {"r0": {
            "seconds": round(time.perf_counter() - t0, 3),
            "compiles": int(sum(v["compiles"] for v in warm.values()))}}
        engine.start(warmup=False)
        server, metrics_owner = engine, engine.metrics
        host_stage_ms = engine.stages.summary()
        mean_batch = engine.metrics.mean_batch_size
        padded_slots = lambda: engine.metrics.padded_slots  # noqa: E731
        queue_peak = lambda: engine.metrics.queue_depth_peak  # noqa: E731
        compiles = lambda: engine.metrics.compiles  # noqa: E731
        quality_hist = engine.metrics.quality_histogram
        early_exit_saved = lambda: (  # noqa: E731
            engine.metrics.early_exit_iters_saved)
        close = engine.close
    else:
        fleet = make_fleet(predictor, replicas, cfg)
        fleet.start(warm_spares=True)
        warmup_per_replica = {
            rid: {k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in stats.items()}
            for rid, stats in fleet.warmup_stats.items()}
        engines = fleet.engines.values()
        server, metrics_owner = fleet, fleet.metrics

        def mean_batch():
            hist = fleet.metrics.batch_histogram()
            n = sum(hist.values())
            return (sum(k * v for k, v in hist.items()) / n) if n else 0.0

        host_stage_ms = None   # filled post-run, per replica
        padded_slots = lambda: sum(  # noqa: E731
            e.metrics.padded_slots for e in engines)
        queue_peak = lambda: max(  # noqa: E731
            e.metrics.queue_depth_peak for e in engines)
        compiles = lambda: sum(  # noqa: E731
            e.metrics.compiles for e in engines)

        def quality_hist():
            merged = {}
            for e in engines:
                for k, v in e.metrics.quality_histogram().items():
                    merged[k] = merged.get(k, 0) + v
            return merged

        early_exit_saved = lambda: sum(  # noqa: E731
            e.metrics.early_exit_iters_saved for e in engines)
        close = fleet.close

    try:
        res = loadgen.run_load(server, frames, n_requests=n_requests,
                               concurrency=concurrency, references=refs)
    finally:
        close()
    if host_stage_ms is None:
        host_stage_ms = {rid: e.stages.summary()
                         for rid, e in fleet.engines.items()}

    speedup = (res["throughput_rps"] / seq["throughput_rps"]
               if seq["throughput_rps"] else None)
    payload = {
        "metric": SERVING_METRIC,
        "value": round(speedup, 3) if speedup else None,
        "unit": "x",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "shapes": [list(s) for s in shapes],
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "replicas": replicas,
        "topology": ("single-replica" if replicas <= 1
                     else f"fleet-{replicas}"),
        "warmup_per_replica": warmup_per_replica,
        "serving_pairs_per_sec": round(res["throughput_rps"], 3),
        "sequential_batch1_pairs_per_sec": round(
            seq["throughput_rps"], 3),
        "latency_p50_ms": round(res["latency_ms"]["p50"], 2),
        "latency_p95_ms": round(res["latency_ms"]["p95"], 2),
        "latency_p99_ms": round(res["latency_ms"]["p99"], 2),
        "batch_histogram": {str(k): v for k, v in
                            sorted(res["batch_histogram"].items())},
        "mean_batch_size": round(mean_batch(), 2),
        "padded_slots": padded_slots(),
        "queue_depth_peak": queue_peak(),
        "post_warmup_compiles": compiles(),
        # Served-quality accounting (graceful brownout): which GRU
        # iteration counts responses were actually served at. With no
        # iters_ladder configured this is all full quality — the key
        # still ships so round-over-round artifacts are comparable.
        "quality_histogram": {str(k): v for k, v in
                              sorted(quality_hist().items(),
                                     reverse=True)},
        "early_exit_iters_saved": early_exit_saved(),
        "iters_ladder": list(cfg.iters_ladder),
        "responses_bit_exact": res["ok"],
        "dropped": len(res["dropped"]),
        "mismatched": len(res["mismatched"]),
        "host_stage_ms": host_stage_ms,
    }
    if tracer is not None:
        payload["trace_artifact"] = tracer.write(
            "/tmp/raft_bench_serving_trace.json")
    if replicas > 1:
        snap = metrics_owner.snapshot()
        payload["fleet"] = {
            "routed": int(snap["fleet_routed"]),
            "failovers": int(snap["fleet_failovers"]),
            "retries": int(snap["fleet_retries"]),
            "shed": int(snap["fleet_shed"]),
            "per_replica_routed": {
                rid: int(snap[f"fleet_{rid}_routed"])
                for rid in fleet.replica_ids},
        }
    if platform != "tpu":
        # Honesty clause (bench.py discipline: context travels with the
        # artifact, values are never faked): the batch-1 gap is a device
        # dispatch-overhead phenomenon. A 1-core CPU host is
        # compute-bound at every batch size, so the ≥2x criterion is
        # measurable only on an accelerator — the committed TPU context
        # below is what serving recovers there, not this host's number.
        payload["criterion_note"] = (
            "≥2x speedup is an accelerator dispatch-bound phenomenon; "
            f"this {ncores}-core {platform} host is compute-bound at "
            "every batch size (measured b8/b1 ratio ~1.0-1.25x), so "
            "the speedup here reflects batching+pipelining overheads "
            "amortized, not the dispatch gap")
        payload["tpu_reference_context"] = {
            "file": "BENCH_r05 (round-5 on-chip capture)",
            "batch1_pairs_per_sec": 31.5,
            "batch128_pairs_per_sec": 98.7,
            "note": "labelled context from the committed TPU capture, "
                    "not a substitute measurement",
        }
    _emit(payload)




WIRE_METRIC = "serving_staged_bytes_ratio_f32_over_u8"


def wire_main(wire: str = "ab"):
    """``python bench.py serving --wire {u8,f32,ab}`` — wire-format
    byte benchmark (round 8).

    Measures what the host path actually memcpy's per request on each
    wire dtype: ``serving_staged_bytes`` is accumulated by the engine's
    staging arena at stack time (real traffic, tail-padding included),
    so the uint8 wire's advantage is a measured counter, not
    ``sizeof`` arithmetic. ``ab`` (the committed-artifact arm) runs
    both wires plus a MIXED-dtype pass on the same dual-dtype-warmed
    engine and records the f32/u8 staged-bytes-per-request ratio as
    the headline — the acceptance bar is >= 3x (the dtype alone gives
    4x; sub-max_batch tail padding dilutes per-request attribution on
    short runs, hence the margin). The mixed pass must trigger ZERO
    fresh XLA compiles — warmup pre-compiles both wire dtypes per
    bucket, so heterogeneous client dtypes never compile under load.

    The ``low_res`` response rides along: the same engine serves a
    block of 1/8-grid responses and the artifact records returned
    bytes per request for full vs low-res (the D2H + host-copy lever
    for throughput-over-fidelity clients). Same operating points and
    honesty clauses as ``serving_main``."""
    import jax
    import numpy as np

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving.metrics import CompileWatch

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shapes = [(436, 1024)]
        small, iters = False, ITERS
        max_batch, concurrency, n_requests = 32, 16, 256
        max_wait_ms = 5.0
    else:
        shapes = [(64, 96), (61, 93)]     # two raws, one padded bucket
        small, iters = True, 4
        max_batch, concurrency, n_requests = 8, 8, 48
        max_wait_ms = 4.0

    predictor = load_predictor("random", small=small, iters=iters)
    frames_u8 = loadgen.make_frames(shapes, per_shape=2, seed=0)
    frames_f32 = loadgen.make_frames(shapes, per_shape=2, seed=0,
                                     dtype=np.float32)
    refs_u8 = loadgen.batched_reference_flows(predictor, frames_u8,
                                              max_batch=max_batch)
    refs_f32 = loadgen.batched_reference_flows(predictor, frames_f32,
                                               max_batch=max_batch)

    engine = ServingEngine(predictor, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        buckets=tuple(shapes), persistent_cache=True))
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = round(time.perf_counter() - t0, 3)
    engine.start(warmup=False)

    arms = {"u8": (frames_u8, refs_u8), "f32": (frames_f32, refs_f32)}
    arm_names = ["u8", "f32"] if wire == "ab" else [wire]
    per_arm = {}
    try:
        for name in arm_names:
            frames, refs = arms[name]
            before = engine.metrics.snapshot()
            res = loadgen.run_load(engine, frames,
                                   n_requests=n_requests,
                                   concurrency=concurrency,
                                   references=refs)
            after = engine.metrics.snapshot()
            per_arm[name] = {
                "staged_bytes_per_request": round(
                    (after["serving_staged_bytes"]
                     - before["serving_staged_bytes"]) / n_requests, 1),
                "returned_bytes_per_request": round(
                    (after["serving_returned_bytes"]
                     - before["serving_returned_bytes"]) / n_requests,
                    1),
                "pairs_per_sec": round(res["throughput_rps"], 3),
                "latency_p50_ms": round(res["latency_ms"]["p50"], 2),
                "responses_bit_exact": res["ok"],
                "dropped": len(res["dropped"]),
                "mismatched": len(res["mismatched"]),
            }
        mixed_compiles = None
        low_res_bytes_per_request = None
        if wire == "ab":
            # Mixed-dtype traffic on the dual-dtype-warmed engine: the
            # zero-post-warmup-compile contract must hold across wires.
            mixed = frames_u8 + frames_f32
            mixed_refs = refs_u8 + refs_f32
            with CompileWatch() as watch:
                res_mix = loadgen.run_load(engine, mixed,
                                           n_requests=n_requests,
                                           concurrency=concurrency,
                                           references=mixed_refs)
            mixed_compiles = watch.compiles
            per_arm["mixed"] = {
                "responses_bit_exact": res_mix["ok"],
                "dropped": len(res_mix["dropped"]),
                "mismatched": len(res_mix["mismatched"]),
                "post_warmup_compiles": mixed_compiles,
            }
            # low_res: returned bytes per request at 1/8 grid.
            before = engine.metrics.snapshot()
            futs = [engine.submit(*frames_u8[i % len(frames_u8)],
                                  low_res=True)
                    for i in range(len(frames_u8) * 2)]
            for f in futs:
                f.result(300)
            after = engine.metrics.snapshot()
            low_res_bytes_per_request = round(
                (after["serving_returned_bytes"]
                 - before["serving_returned_bytes"]) / len(futs), 1)
    finally:
        engine.close()

    ratio = None
    if "u8" in per_arm and "f32" in per_arm:
        u8b = per_arm["u8"]["staged_bytes_per_request"]
        ratio = (round(per_arm["f32"]["staged_bytes_per_request"] / u8b,
                       3) if u8b else None)
    payload = {
        "metric": WIRE_METRIC,
        "value": ratio,
        "unit": "x",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "shapes": [list(s) for s in shapes],
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "wire_arm": wire,
        "warmup_seconds": warmup_s,
        "warmup_compiles": int(sum(v["compiles"]
                                   for v in warm.values())),
        "per_wire": per_arm,
        "mixed_traffic_post_warmup_compiles": mixed_compiles,
        "low_res_returned_bytes_per_request": low_res_bytes_per_request,
        "host_stage_ms": engine.stages.summary(),
    }
    if platform != "tpu":
        payload["criterion_note"] = (
            "staged-bytes ratio is dtype arithmetic and holds on any "
            f"host; this {ncores}-core {platform} smoke point proves "
            "the counters, the bit-exactness, and the zero-compile "
            "mixed-traffic contract — the wall-clock win from 4x less "
            "host memcpy + H2D is a TPU-host phenomenon to capture "
            "on-chip")
    _emit(payload)




HIGHRES_METRIC = "highres_sharded_vs_unsharded_batch1_latency_speedup"


def highres_main(shards: int = 0):
    """``python bench.py serving --highres [--shards N]`` — multi-chip
    high-resolution serving benchmark (spatial sharding).

    The one workload single-chip batching can't help is a lone high-res
    request: it is latency-bound and unbatchable, and all-pairs
    correlation makes its cost quadratic in resolution. This mode
    measures what the spatially-sharded serving path buys for exactly
    that request: batch-1 latency of the sharded executable (rows split
    over the mesh's spatial axis, shard_map'd banded lookup) against
    the unsharded batch-1 executable at the SAME padded shape, plus a
    mixed-traffic section proving the sharded bucket serves from its
    own dispatch stream with zero post-warmup compiles while small-
    batch traffic flows beside it.

    On TPU the mesh spans the chips and the speedup is the headline;
    on the CPU smoke host the "devices" are forced host-platform
    threads sharing the same cores, so sharding adds collective
    overhead instead of compute — the artifact says so in
    ``criterion_note`` and carries ``smoke_operating_point`` rather
    than faking a win. What the smoke host DOES prove: bit-level
    response integrity, zero post-warmup compiles, and stream overlap.
    """
    import jax
    import numpy as np

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving.metrics import CompileWatch

    platform = _platform()
    n_dev = len(jax.devices())
    if shards <= 0:
        shards = n_dev if platform == "tpu" else min(4, n_dev)
    if shards < 2 or n_dev < shards:
        raise SystemExit(
            f"need >= 2 devices to shard (have {n_dev}, want {shards}); "
            "on CPU run with XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8")
    if platform == "tpu":
        highres, small_shapes = (436, 1024), [(184, 320)]
        small, iters, max_batch = False, ITERS, 8
        n_requests, concurrency = 64, 8
    else:
        highres, small_shapes = (96, 128), [(36, 60), (33, 57)]
        small, iters, max_batch = True, 2, 4
        n_requests, concurrency = 24, 6

    predictor = load_predictor("random", small=small, iters=iters)
    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=3.0,
        buckets=tuple(small_shapes), sharded_buckets=(highres,),
        sharded_shards=shards,
        sharded_area_threshold=highres[0] * highres[1],
        persistent_cache=True)
    engine = ServingEngine(predictor, cfg)
    mesh = engine._sharded_mesh

    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup = {"seconds": round(time.perf_counter() - t0, 3),
              "compiles": int(sum(v["compiles"] for v in warm.values())),
              "buckets": sorted(str(k) for k in warm)}

    # -- batch-1 latency: sharded vs unsharded at the same padded shape.
    # Direct dispatch (no queue) isolates the executable, which is what
    # the mesh changes; the queueing cost is identical for both.
    rng = np.random.default_rng(0)
    ph, pw = highres
    a = rng.uniform(0, 255, (1, ph, pw, 3)).astype(np.float32)
    b = rng.uniform(0, 255, (1, ph, pw, 3)).astype(np.float32)

    def _lat(fn, reps: int = REPS) -> dict:
        for _ in range(WARMUP):
            np.asarray(fn()[1])
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            np.asarray(fn()[1])
            ts.append((time.perf_counter() - t) * 1000.0)
        ts.sort()
        return {"p50_ms": round(ts[len(ts) // 2], 2),
                "min_ms": round(ts[0], 2),
                "max_ms": round(ts[-1], 2)}

    sharded_lat = _lat(
        lambda: predictor.sharded_dispatch(a, b, mesh=mesh))
    unsharded_lat = _lat(lambda: predictor.dispatch_batch(a, b))
    speedup = (unsharded_lat["p50_ms"] / sharded_lat["p50_ms"]
               if sharded_lat["p50_ms"] else None)

    # -- mixed traffic: highres + small-batch through ONE engine, zero
    # post-warmup compiles, per-bucket streams overlapping. References
    # per path: the batched executable for small frames, the sharded
    # executable for highres frames — each response must bit-match the
    # executable that contractually serves its bucket.
    small_frames = loadgen.make_frames(small_shapes, per_shape=2, seed=1)
    hi_frames = loadgen.make_frames([highres], per_shape=2, seed=2)
    frames = small_frames + hi_frames
    refs = loadgen.batched_reference_flows(
        frames=small_frames, predictor=predictor, max_batch=max_batch)
    for im1, im2 in hi_frames:
        out = predictor.sharded_dispatch(im1[None], im2[None], mesh=mesh)
        refs.append(np.asarray(out[1][0]))
    engine.start(warmup=False)
    try:
        with CompileWatch() as cw:
            res = loadgen.run_load(engine, frames, n_requests=n_requests,
                                   concurrency=concurrency,
                                   references=refs)
    finally:
        engine.close()

    payload = {
        "metric": HIGHRES_METRIC,
        "value": round(speedup, 3) if speedup else None,
        "unit": "x",
        "platform": platform,
        "devices": n_dev,
        "mesh": f"1x{shards}",
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "highres_shape": list(highres),
        "small_shapes": [list(s) for s in small_shapes],
        "sharded_batch1_latency": sharded_lat,
        "unsharded_batch1_latency": unsharded_lat,
        "warmup": warmup,
        "mixed_traffic": {
            "n_requests": n_requests,
            "concurrency": concurrency,
            "completed": res["completed"],
            "dropped": len(res["dropped"]),
            "responses_bit_exact": res["ok"],
            "post_warmup_compiles": cw.compiles,
            "sharded_requests": int(
                engine.metrics.snapshot().get(
                    "serving_sharded_requests", 0)),
            "batch_histogram": {str(k): v for k, v in
                                sorted(res["batch_histogram"].items())},
            "throughput_rps": round(res["throughput_rps"], 3),
        },
    }
    if platform != "tpu":
        payload["smoke_operating_point"] = True
        payload["criterion_note"] = (
            "forced host-platform devices are threads on shared CPU "
            "cores: row-sharding adds halo/collective overhead without "
            "adding compute, so sharded latency >= unsharded here by "
            "construction. The CPU artifact proves correctness (bit-"
            "exact responses), zero post-warmup compiles, and stream "
            "overlap; the latency win is a multi-chip phenomenon")
        payload["tpu_expectation_note"] = (
            "on a TPU pod slice the mesh spans real chips: each holds "
            "1/d of every activation and of the (HW)^2 correlation "
            "volume, so batch-1 high-res latency scales down with the "
            "mesh — the round-5 8-way spatial-parallel capture is the "
            "trajectory reference; on-TPU serving capture is tracked "
            "as ROADMAP debt")
    _emit(payload)




STREAMING_METRIC = "streaming_warm_vs_stateless_pairs_per_sec_speedup"


def streaming_main():
    """``python bench.py streaming`` — session-aware streaming serving
    benchmark (warm start + encoder feature-map reuse).

    Drives N concurrent streaming sessions over temporally coherent
    synthetic streams and publishes their WARM steady-state throughput
    against the thing they replace: the same streams submitted as
    stateless ``(frame_k, frame_k+1)`` pairs through the same engine
    (every pair pays two fnet passes and full iterations). The frames,
    closed-loop client structure and timed-pair counts are identical
    between the two arms, so the ratio isolates exactly what sessions
    save: one encoder pass per warm frame plus the warm-start iteration
    discount. Emits ONE BENCH-compatible JSON line.

    Unlike the dispatch-gap serving benchmark this speedup is real on
    ANY platform — the saved encoder pass and GRU iterations are
    compute, not dispatch overhead — but CPU-smoke numbers still travel
    with their platform label and the accuracy context (warm-vs-cold
    flow drift per pair) so nobody mistakes a 1-core smoke point for a
    TPU capture.
    """
    import jax
    import numpy as np

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving.metrics import CompileWatch

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shape = (436, 1024)
        small, iters, warm_iters = False, ITERS, 6
        max_batch, n_streams, n_frames = 8, 16, 24
        max_wait_ms = 5.0
    else:
        shape = (64, 96)
        small, iters, warm_iters = True, 4, 2
        max_batch, n_streams, n_frames = 4, 6, 12
        max_wait_ms = 4.0

    predictor = load_predictor("random", small=small, iters=iters)
    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        buckets=(shape,), warm_buckets=(shape,),
        warm_iters=warm_iters, persistent_cache=True)
    engine = ServingEngine(predictor, cfg)
    t0 = time.perf_counter()
    warm_stats = engine.warmup()
    warmup = {
        "seconds": round(time.perf_counter() - t0, 3),
        "compiles": int(sum(v["compiles"] for v in warm_stats.values()))}
    engine.start(warmup=False)
    try:
        with CompileWatch() as watch:
            base = loadgen.run_pair_stream_load(
                engine, n_streams, n_frames, shape=shape,
                collect_flows=True)
            stream = loadgen.run_stream_load(
                engine, n_streams, n_frames, shape=shape,
                collect_flows=True)
    finally:
        engine.close()

    # Accuracy context: per-pair drift of the warm session flow vs the
    # stateless flow over the SAME frames (pair 0 is the session's cold
    # pair — same executable family, listed separately), plus both
    # arms' EPE against the streams' constant ground-truth shift.
    warm_drift, cold_drift, epe_stream, epe_base = [], [], [], []
    for (gt, sflows), (_, bflows) in zip(stream["flows"], base["flows"]):
        for k, (sf, bf) in enumerate(zip(sflows, bflows)):
            d = float(np.mean(np.linalg.norm(sf - bf, axis=-1)))
            (cold_drift if k == 0 else warm_drift).append(d)
            epe_stream.append(
                float(np.mean(np.linalg.norm(sf - gt, axis=-1))))
            epe_base.append(
                float(np.mean(np.linalg.norm(bf - gt, axis=-1))))

    sessions = [rec["session"]
                for rec in stream["per_stream"].values()]
    hit_rates = [s["encoder_cache_hit_rate"] for s in sessions]
    expected_rate = (n_frames - 1) / n_frames
    speedup = (stream["pairs_per_s"] / base["pairs_per_s"]
               if base["pairs_per_s"] else None)
    lat = [rec["latency_ms"] for rec in stream["per_stream"].values()]
    payload = {
        "metric": STREAMING_METRIC,
        "value": round(speedup, 3) if speedup else None,
        "unit": "x",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "warm_iters": warm_iters,
        "shape": list(shape),
        "n_streams": n_streams,
        "n_frames_per_stream": n_frames,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "warmup": warmup,
        "streaming_pairs_per_sec": round(stream["pairs_per_s"], 3),
        "stateless_pairs_per_sec": round(base["pairs_per_s"], 3),
        "steady_pairs_per_arm": stream["steady_pairs"],
        "dropped": stream["dropped"] + base["dropped"],
        "per_stream_latency_p50_ms": round(
            float(np.median([l["p50"] for l in lat])), 2),
        "per_stream_latency_p99_ms": round(
            float(max(l["p99"] for l in lat)), 2),
        "encoder_cache_hit_rate_min": round(min(hit_rates), 4),
        "encoder_cache_hit_rate_expected": round(expected_rate, 4),
        "warm_pairs_total": sum(s["warm_pairs"] for s in sessions),
        "cold_pairs_total": sum(s["cold_pairs"] for s in sessions),
        "post_warmup_compiles": watch.compiles,
        "warm_vs_stateless_flow_drift_epe": {
            "warm_mean": round(float(np.mean(warm_drift)), 4),
            "warm_max": round(float(np.max(warm_drift)), 4),
            "cold_pair_mean": round(float(np.mean(cold_drift)), 4),
        },
        "epe_vs_gt": {
            "streaming_mean": round(float(np.mean(epe_stream)), 4),
            "stateless_mean": round(float(np.mean(epe_base)), 4),
        },
    }
    if platform != "tpu":
        # Honesty clause: this is a real compute saving (not a dispatch
        # artifact), so the ≥1.3x criterion IS meaningful on CPU — but
        # the absolute pairs/s and the random-weight EPE context are
        # smoke numbers, not a TPU capture, and say so.
        payload["criterion_note"] = (
            "warm speedup comes from skipping one fnet pass per frame "
            f"and running {warm_iters} vs {iters} GRU iterations — a "
            "compute saving measurable on this "
            f"{ncores}-core {platform} smoke host; absolute pairs/s "
            "and the random-weight EPE context are NOT TPU numbers")
        payload["tpu_reference_context"] = {
            "file": "BENCH_r05 (round-5 on-chip capture)",
            "note": "no committed TPU streaming capture yet; stateless "
                    "serving context only — labelled context, not a "
                    "substitute measurement",
        }
    _emit(payload)




CONTBATCH_METRIC = "contbatch_vs_bucketed_mixed_iters_throughput_speedup"


def contbatch_main(arm: str = "ab"):
    """``python bench.py serving --contbatch {ab,on,off}`` — iteration-
    granular continuous batching benchmark (round 9, BENCH_r09).

    The workload is MIXED-iteration traffic: requests spread across the
    quality ladder (full / degraded levels) with early exit live, the
    shape brownout and per-request ``iters`` produce in production. The
    bucketed monolithic path fragments that traffic into one
    ``(H, W, lvl, wire)`` bucket per level — each dispatching the full
    ``max_batch``-slot executable around whatever handful of requests
    its lane collected, tail-padding the rest — while the continuous
    scheduler packs every level into ONE slot table, retires each slot
    the step its request's budget (or early-exit convergence) lands,
    and refills it from the queue on the next step.

    ``ab`` (the committed-artifact arm) runs both paths over identical
    frames/levels/references and publishes the continuous/bucketed
    throughput ratio as the headline (acceptance bar: >= 1.3x on this
    traffic). ``on``/``off`` run a single arm for debugging. Every
    response in both arms is graded against per-level monolithic
    references honoring each arm's early-exit contract (see the
    reference builder below) — bit-exact on the bucketed arm, <= 1e-4
    EPE on the continuous arm (same math, differently fused
    executables) — and both arms must serve with ZERO post-warmup
    compiles. Same operating points and honesty clauses as
    ``serving_main``."""
    import jax
    import numpy as np

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving.metrics import CompileWatch
    from raft_tpu.utils.padder import InputPadder

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shapes = [(436, 1024)]
        small, full_iters = False, ITERS
        max_batch, concurrency, n_requests = 32, 16, 256
        max_wait_ms = 5.0
        ladder = (8, 4)
    else:
        shapes = [(64, 96), (61, 93)]     # two raws, one padded bucket
        small, full_iters = True, 4
        max_batch, concurrency, n_requests = 8, 8, 48
        max_wait_ms = 4.0
        ladder = (2, 1)
    levels = [full_iters, *ladder]

    predictor = load_predictor("random", small=small, iters=full_iters)
    # Early exit live: loose tolerance so a fraction of requests
    # converge before their budget — the continuous scheduler turns
    # those freed slot-iterations into admissions; references below are
    # computed with the SAME setting so they remain the served truth.
    predictor.early_exit = (5.0, 1)
    frames = loadgen.make_frames(shapes, per_shape=2, seed=0,
                                 dtype=np.float32)

    def _refs_at(lvl, legacy: bool):
        refs = []
        for im1, im2 in frames:
            padder = InputPadder(im1.shape, mode="sintel", factor=8)
            p1, p2 = padder.pad(im1, im2)
            i1 = np.repeat(p1[None], max_batch, axis=0)
            i2 = np.repeat(p2[None], max_batch, axis=0)
            out = (predictor.dispatch_batch(i1, i2) if legacy
                   else predictor.dispatch_batch(i1, i2, iters=lvl))
            refs.append(padder.unpad(np.asarray(out[1])[0]))
        return refs

    # Per-ARM references, because the two paths make different (both
    # correct) early-exit promises at full quality: the bucketed
    # engine serves full-quality requests through the legacy no-iters
    # executable, where early exit does not apply; the continuous
    # scheduler applies per-slot early exit to EVERY request — that
    # wall-clock is precisely what this benchmark measures. Ladder
    # levels go through the early-exit-enabled iters executables on
    # both paths.
    refs_cont = {lvl: _refs_at(lvl, legacy=False) for lvl in levels}
    refs_mono = dict(refs_cont)
    refs_mono[full_iters] = _refs_at(full_iters, legacy=True)

    def _run_arm(continuous: bool) -> dict:
        cfg = ServingConfig(
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            buckets=tuple(shapes), iters_ladder=ladder,
            continuous=continuous, contbatch_steps=1,
            persistent_cache=True)
        engine = ServingEngine(predictor, cfg)
        t0 = time.perf_counter()
        warm = engine.warmup()
        warm_s = round(time.perf_counter() - t0, 3)
        engine.start(warmup=False)
        try:
            with CompileWatch() as watch:
                res = loadgen.run_mixed_iters_load(
                    engine, frames, n_requests=n_requests,
                    levels=levels,
                    refs_by_iters=(refs_cont if continuous
                                   else refs_mono),
                    concurrency=concurrency)
        finally:
            engine.close()
        snap = res["metrics"]
        rec = {
            "mixed_iters_pairs_per_sec": round(res["throughput_rps"], 3),
            "completed": res["completed"],
            "dropped": len(res["dropped"]),
            "mismatched": len(res["mismatched"]),
            "worst_epe_vs_monolithic": round(res["worst_epe"], 8),
            "post_warmup_compiles": watch.compiles,
            "warmup_seconds": warm_s,
            "warmup_compiles": int(sum(v["compiles"]
                                       for v in warm.values())),
            "latency_p50_ms": round(res["latency_ms"]["p50"], 2),
            "latency_p99_ms": round(res["latency_ms"]["p99"], 2),
            "level_counts": {str(k): v
                             for k, v in res["level_counts"].items()},
        }
        if continuous:
            rec["contbatch"] = {
                "slots": max_batch,
                "steps_per_dispatch": 1,
                "admits": int(snap["serving_contbatch_admits"]),
                "retires": int(snap["serving_contbatch_retires"]),
                "scheduler_steps": int(snap["serving_contbatch_steps"]),
                "mean_slot_occupancy": round(
                    snap["serving_contbatch_mean_occupancy"], 2),
                "freed_iters": int(snap["serving_contbatch_freed_iters"]),
                "early_exit_iters_saved": int(
                    snap["serving_early_exit_iters_saved"]),
            }
        return rec

    per_arm = {}
    if arm in ("ab", "off"):
        per_arm["bucketed"] = _run_arm(continuous=False)
    if arm in ("ab", "on"):
        per_arm["continuous"] = _run_arm(continuous=True)

    speedup = None
    if "continuous" in per_arm and "bucketed" in per_arm:
        base = per_arm["bucketed"]["mixed_iters_pairs_per_sec"]
        if base:
            speedup = round(
                per_arm["continuous"]["mixed_iters_pairs_per_sec"]
                / base, 3)
    payload = {
        "metric": CONTBATCH_METRIC,
        "value": speedup,
        "unit": "x",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "full_iters": full_iters,
        "iters_ladder": list(ladder),
        "levels": levels,
        "early_exit": list(predictor.early_exit),
        "shapes": [list(s) for s in shapes],
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "contbatch_arm": arm,
        "per_arm": per_arm,
    }
    if platform != "tpu":
        payload["smoke_operating_point"] = True
        payload["criterion_note"] = (
            "unlike the dispatch-gap serving headline, this ratio is "
            "utilization arithmetic and holds on any host: both arms "
            "run the same per-iteration math on the same "
            f"{ncores}-core {platform} host, and the win is dense slot "
            "occupancy vs per-level bucket fragmentation + tail "
            "padding (throughput scales with the mean-iters/max-iters "
            "ratio of the traffic). Absolute pairs/s is a smoke "
            "number; the on-TPU capture is tracked as ROADMAP debt")
    _emit(payload)




GATEWAY_METRIC = "gateway_vs_inprocess_p50_latency_overhead_ms"


def gateway_main(arm: str = "ab"):
    """``python bench.py serving --gateway {ab,on,off}`` — socket-hop
    overhead of the multi-process serving tier (BENCH_gateway).

    Both arms run the SAME predictor, engine config, frames, and
    closed-loop load: the ``in_process`` arm submits straight to a
    :class:`~raft_tpu.serving.engine.ServingEngine` (the path every
    serving benchmark to date measured); the ``gateway`` arm puts that
    same engine behind a :class:`~raft_tpu.serving.worker.WorkerServer`
    socket in this process and routes through a
    :class:`~raft_tpu.serving.gateway.ServingGateway` over a file lease
    store — so the delta is exactly the network tier's toll (length-
    prefixed framing, the uint8 wire bytes over a local socket into the
    worker's staging arena, lease-routed dispatch) and not a different
    model, batcher, or host. The headline is client-observed p50
    latency through the gateway minus in-process p50, in ms (both from
    ``run_load``'s submit-to-result clock, the number a caller actually
    feels). ``on``/``off`` run a single arm for debugging.

    Honesty contract: every response in BOTH arms is checked bit-exact
    against same-executable references, and both arms must serve with
    ZERO post-warmup compiles — the gateway path rides the exact
    executables the in-process path warmed."""
    import dataclasses
    import tempfile

    import jax

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving.gateway import GatewayConfig, ServingGateway
    from raft_tpu.serving.metrics import CompileWatch
    from raft_tpu.serving.netproto import FileLeaseStore
    from raft_tpu.serving.worker import WorkerConfig, WorkerServer

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shapes = [(436, 1024)]
        small, iters = False, ITERS
        max_batch, concurrency, n_requests = 16, 16, 128
        max_wait_ms = 5.0
    else:
        shapes = [(64, 96), (61, 93)]     # two raws, one padded bucket
        small, iters = True, 2
        max_batch, concurrency, n_requests = 4, 8, 48
        max_wait_ms = 3.0

    predictor = load_predictor("random", small=small, iters=iters)
    frames = loadgen.make_frames(shapes, per_shape=2, seed=0)
    refs = loadgen.batched_reference_flows(frames=frames,
                                           predictor=predictor,
                                           max_batch=max_batch)
    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        buckets=tuple(shapes), persistent_cache=True)

    def _arm_record(res, watch, warm_s) -> dict:
        # Single replica per arm, so per_replica has exactly one entry:
        # its client-observed (submit -> result) latency is the number
        # both arms are compared on.
        client = next(iter(res["per_replica"].values()))["latency_ms"]
        return {
            "completed": res["completed"],
            "dropped": len(res["dropped"]),
            "mismatched": len(res["mismatched"]),
            "p50_ms": round(client["p50"], 3),
            "p99_ms": round(client["p99"], 3),
            "throughput_rps": round(res["throughput_rps"], 3),
            "post_warmup_compiles": watch.compiles,
            "warmup_seconds": warm_s,
        }

    def _run_in_process() -> dict:
        engine = ServingEngine(predictor, cfg)
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = round(time.perf_counter() - t0, 3)
        engine.start(warmup=False)
        try:
            with CompileWatch() as watch:
                res = loadgen.run_load(
                    engine, frames, n_requests=n_requests,
                    concurrency=concurrency, references=refs,
                    timeout=600.0)
        finally:
            engine.close()
        return _arm_record(res, watch, warm_s)

    def _run_gateway(lease_dir: str) -> dict:
        store = FileLeaseStore(lease_dir)
        engine = ServingEngine(predictor, dataclasses.replace(
            cfg, replica_id="w0"))
        server = WorkerServer(
            engine,
            WorkerConfig(worker_id="w0", lease_dir=lease_dir,
                         heartbeat_interval_s=0.2,
                         buckets=tuple(shapes), max_batch=max_batch,
                         max_wait_ms=max_wait_ms, step=0),
            lease_store=store)
        t0 = time.perf_counter()
        server.start(warmup=True)
        warm_s = round(time.perf_counter() - t0, 3)
        gw = ServingGateway(store, GatewayConfig(
            queue_timeout_ms=600_000, lease_ttl_s=2.0,
            poll_interval_s=0.1, dispatch_threads=concurrency,
            expected_step=0))
        try:
            gw.start()
            t_join = time.monotonic() + 120.0
            while not gw.live_workers():
                if time.monotonic() > t_join:
                    raise RuntimeError("worker never became routable")
                time.sleep(0.05)
            with CompileWatch() as watch:
                res = loadgen.run_load(
                    gw, frames, n_requests=n_requests,
                    concurrency=concurrency, references=refs,
                    timeout=600.0)
            lease = store.read_all().get("w0")
            lease_compiles = (lease.extra.get("post_warmup_compiles")
                              if lease is not None else None)
        finally:
            gw.close()
            server.stop()
        rec = _arm_record(res, watch, warm_s)
        rec["worker_lease_compiles"] = lease_compiles
        return rec

    per_arm = {}
    if arm in ("ab", "off"):
        per_arm["in_process"] = _run_in_process()
    if arm in ("ab", "on"):
        with tempfile.TemporaryDirectory() as lease_dir:
            per_arm["gateway"] = _run_gateway(lease_dir)

    overhead = None
    if "in_process" in per_arm and "gateway" in per_arm:
        overhead = round(per_arm["gateway"]["p50_ms"]
                         - per_arm["in_process"]["p50_ms"], 3)
    payload = {
        "metric": GATEWAY_METRIC,
        "value": overhead,
        "unit": "ms",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "shapes": [list(s) for s in shapes],
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "gateway_arm": arm,
        "per_arm": per_arm,
    }
    if platform != "tpu":
        payload["smoke_operating_point"] = True
        payload["criterion_note"] = (
            "both arms run the same small-model executables on this "
            f"{ncores}-core {platform} host, so the p50 DELTA isolates "
            "the local-socket gateway hop (framing + wire bytes + "
            "lease routing) at a smoke operating point; absolute "
            "latencies are smoke numbers, and the flagship-shape "
            "on-TPU capture is tracked as ROADMAP debt")
    _emit(payload)




EDGE_METRIC = "edge_vs_inprocess_p50_latency_overhead_ms"


def edge_main(arm: str = "ab"):
    """``python bench.py serving --edge {ab,on,off}`` — the HTTP front
    door's toll on a client request (BENCH_edge).

    Both arms run the SAME predictor, engine config, frames, and
    closed-loop concurrency. The ``in_process`` arm submits straight to
    a :class:`~raft_tpu.serving.engine.ServingEngine`; the ``edge`` arm
    serves the same engine behind a :class:`~raft_tpu.serving.worker
    .WorkerServer` socket, routes through a :class:`~raft_tpu.serving
    .gateway.ServingGateway`, and fronts THAT with the
    :class:`~raft_tpu.serving.edge.EdgeServer` — real HTTP/1.1 clients
    (``submit_flow``) doing admission, header parsing, body staging and
    response encoding per request. The headline is client-observed p50
    through the full edge stack minus in-process p50, in ms — what
    putting the hardened front door (plus the gateway hop it sits on)
    in front of a request actually costs. ``on``/``off`` run one arm.

    Honesty contract: every response in BOTH arms is checked bit-exact
    against same-executable references, and both arms serve with ZERO
    post-warmup compiles."""
    import dataclasses
    import tempfile

    import jax
    import numpy as np

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
    from raft_tpu.serving import edge as edge_mod
    from raft_tpu.serving.gateway import GatewayConfig, ServingGateway
    from raft_tpu.serving.metrics import CompileWatch, _percentile
    from raft_tpu.serving.netproto import FileLeaseStore
    from raft_tpu.serving.worker import WorkerConfig, WorkerServer

    platform = _platform()
    ncores = os.cpu_count() or 1
    if platform == "tpu":
        shapes = [(436, 1024)]
        small, iters = False, ITERS
        max_batch, concurrency, n_requests = 16, 16, 128
        max_wait_ms = 5.0
    else:
        shapes = [(64, 96), (61, 93)]     # two raws, one padded bucket
        small, iters = True, 2
        max_batch, concurrency, n_requests = 4, 8, 48
        max_wait_ms = 3.0

    predictor = load_predictor("random", small=small, iters=iters)
    frames = loadgen.make_frames(shapes, per_shape=2, seed=0)
    refs = loadgen.batched_reference_flows(frames=frames,
                                           predictor=predictor,
                                           max_batch=max_batch)
    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        buckets=tuple(shapes), persistent_cache=True)

    def _run_in_process() -> dict:
        engine = ServingEngine(predictor, cfg)
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = round(time.perf_counter() - t0, 3)
        engine.start(warmup=False)
        try:
            with CompileWatch() as watch:
                res = loadgen.run_load(
                    engine, frames, n_requests=n_requests,
                    concurrency=concurrency, references=refs,
                    timeout=600.0)
        finally:
            engine.close()
        client = next(iter(res["per_replica"].values()))["latency_ms"]
        return {
            "completed": res["completed"],
            "dropped": len(res["dropped"]),
            "mismatched": len(res["mismatched"]),
            "p50_ms": round(client["p50"], 3),
            "p99_ms": round(client["p99"], 3),
            "throughput_rps": round(res["throughput_rps"], 3),
            "post_warmup_compiles": watch.compiles,
            "warmup_seconds": warm_s,
        }

    def _run_edge_http(addr) -> dict:
        """Closed-loop HTTP clients against the edge; latency is the
        full submit_flow round trip (the number a caller feels)."""
        lock = threading.Lock()
        it = iter(range(n_requests))
        lat_ms, mismatched, dropped = [], [], []

        def client():
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                fi = i % len(frames)
                im1, im2 = frames[fi]
                t0 = time.perf_counter()
                resp = edge_mod.submit_flow(addr, im1, im2,
                                            timeout=600.0)
                dt = (time.perf_counter() - t0) * 1e3
                if resp is None or resp.status != 200:
                    with lock:
                        dropped.append(i)
                    continue
                flow = edge_mod.decode_flow(resp)
                with lock:
                    lat_ms.append(dt)
                    if not np.array_equal(flow, refs[fi]):
                        mismatched.append(i)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900.0)
        wall = time.perf_counter() - t0
        return {
            "completed": len(lat_ms),
            "dropped": len(dropped),
            "mismatched": len(mismatched),
            "p50_ms": round(_percentile(lat_ms, 50), 3),
            "p99_ms": round(_percentile(lat_ms, 99), 3),
            "throughput_rps": round(len(lat_ms) / wall, 3),
        }

    def _run_edge(lease_dir: str) -> dict:
        store = FileLeaseStore(lease_dir)
        engine = ServingEngine(predictor, dataclasses.replace(
            cfg, replica_id="w0"))
        server = WorkerServer(
            engine,
            WorkerConfig(worker_id="w0", lease_dir=lease_dir,
                         heartbeat_interval_s=0.2,
                         buckets=tuple(shapes), max_batch=max_batch,
                         max_wait_ms=max_wait_ms, step=0),
            lease_store=store)
        t0 = time.perf_counter()
        server.start(warmup=True)
        warm_s = round(time.perf_counter() - t0, 3)
        gw = ServingGateway(store, GatewayConfig(
            queue_timeout_ms=600_000, lease_ttl_s=2.0,
            poll_interval_s=0.1, dispatch_threads=concurrency,
            expected_step=0))
        es = None
        try:
            gw.start()
            t_join = time.monotonic() + 120.0
            while not gw.live_workers():
                if time.monotonic() > t_join:
                    raise RuntimeError("worker never became routable")
                time.sleep(0.05)
            es = edge_mod.EdgeServer(gw).start_in_thread()
            with CompileWatch() as watch:
                rec = _run_edge_http(es.addr)
            lease = store.read_all().get("w0")
            rec["post_warmup_compiles"] = watch.compiles
            rec["warmup_seconds"] = warm_s
            rec["worker_lease_compiles"] = (
                lease.extra.get("post_warmup_compiles")
                if lease is not None else None)
        finally:
            if es is not None:
                es.shutdown_sync()     # closes the gateway too
            else:
                gw.close()
            server.stop()
        return rec

    per_arm = {}
    if arm in ("ab", "off"):
        per_arm["in_process"] = _run_in_process()
    if arm in ("ab", "on"):
        with tempfile.TemporaryDirectory() as lease_dir:
            per_arm["edge"] = _run_edge(lease_dir)

    overhead = None
    if "in_process" in per_arm and "edge" in per_arm:
        overhead = round(per_arm["edge"]["p50_ms"]
                         - per_arm["in_process"]["p50_ms"], 3)
    payload = {
        "metric": EDGE_METRIC,
        "value": overhead,
        "unit": "ms",
        "platform": platform,
        "host_cores": ncores,
        "model": "raft-small" if small else "raft-large",
        "iters": iters,
        "shapes": [list(s) for s in shapes],
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "edge_arm": arm,
        "per_arm": per_arm,
    }
    if platform != "tpu":
        payload["smoke_operating_point"] = True
        payload["criterion_note"] = (
            "both arms run the same small-model executables on this "
            f"{ncores}-core {platform} host, so the p50 DELTA isolates "
            "the HTTP front door stacked on the local-socket gateway "
            "hop (admission, header parse, body staging, response "
            "encoding) at a smoke operating point; absolute latencies "
            "are smoke numbers, and the flagship-shape on-TPU capture "
            "is tracked as ROADMAP debt")
    _emit(payload)




def _cli(argv) -> None:
    """Dispatch one benchmark mode. Whatever the mode raises propagates:
    a failed run exits non-zero."""
    if argv and argv[0] == "streaming":
        streaming_main()
        return
    if argv and argv[0] == "serving":
        if "--highres" in argv[1:]:
            # Multi-chip path: on hosts without accelerators the mesh
            # comes from forced host-platform devices. Must be in the
            # environment before jax initializes its backend (first
            # jax.devices() call inside highres_main) — a no-op for the
            # CPU platform's count when already set, and irrelevant on
            # TPU where the real chips are the mesh.
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
            ap = argparse.ArgumentParser(
                prog="bench.py serving --highres")
            ap.add_argument("--highres", action="store_true")
            ap.add_argument("--shards", type=int, default=0,
                            help="spatial mesh width (default: all "
                                 "devices on TPU, 4 on the CPU "
                                 "smoke host)")
            highres_main(shards=ap.parse_args(argv[1:]).shards)
            return
        ap = argparse.ArgumentParser(prog="bench.py serving")
        ap.add_argument("--replicas", type=int, default=1,
                        help="serve through an N-replica fleet "
                             "(default: 1, the single-engine "
                             "trajectory point)")
        ap.add_argument("--wire", choices=("u8", "f32", "ab"),
                        default=None,
                        help="wire-format byte benchmark instead of "
                             "the throughput benchmark: 'u8'/'f32' "
                             "measure one wire dtype's staged bytes "
                             "per request, 'ab' runs both plus a "
                             "mixed-dtype zero-compile pass and "
                             "records the f32/u8 ratio (the "
                             "BENCH_r08 artifact)")
        ap.add_argument("--contbatch", choices=("ab", "on", "off"),
                        default=None,
                        help="iteration-granular continuous "
                             "batching benchmark instead of the "
                             "throughput benchmark: 'ab' runs "
                             "mixed-iters traffic through both the "
                             "continuous scheduler and the "
                             "bucketed monolithic path and records "
                             "the throughput ratio (the BENCH_r09 "
                             "artifact); 'on'/'off' run one arm")
        ap.add_argument("--gateway", choices=("ab", "on", "off"),
                        default=None,
                        help="multi-process gateway-hop benchmark "
                             "instead of the throughput benchmark: "
                             "'ab' serves the same load in-process "
                             "and through the socket gateway and "
                             "records the p50 latency overhead "
                             "(the BENCH_gateway artifact); "
                             "'on'/'off' run one arm")
        ap.add_argument("--edge", choices=("ab", "on", "off"),
                        default=None,
                        help="HTTP front-door benchmark instead of "
                             "the throughput benchmark: 'ab' serves "
                             "the same load in-process and through "
                             "the full edge -> gateway -> worker "
                             "stack over real HTTP and records the "
                             "p50 latency overhead (the BENCH_edge "
                             "artifact); 'on'/'off' run one arm")
        ap.add_argument("--trace", action="store_true",
                        help="record a request-scoped trace of the "
                             "benchmark run and ship its path as "
                             "the artifact's trace_artifact key "
                             "(Perfetto-loadable Chrome trace "
                             "JSON)")
        args = ap.parse_args(argv[1:])
        if args.edge is not None:
            edge_main(arm=args.edge)
        elif args.gateway is not None:
            gateway_main(arm=args.gateway)
        elif args.contbatch is not None:
            contbatch_main(arm=args.contbatch)
        elif args.wire is not None:
            wire_main(wire=args.wire)
        else:
            serving_main(replicas=args.replicas, trace=args.trace)
        return
    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument("--gru", choices=("ab", "pallas", "xla"),
                    default="ab",
                    help="GRU-cell arm: 'ab' (default) measures the "
                         "headline under the ambient RAFT_GRU_PALLAS "
                         "and adds a forced pallas-vs-xla A/B pass; "
                         "'pallas'/'xla' force one dispatch for the "
                         "whole run (recorded in the payload)")
    ap.add_argument("--motion", choices=("ab", "pallas", "xla"),
                    default="ab",
                    help="motion-encoder arm (RAFT_MOTION_PALLAS), "
                         "same semantics as --gru: 'ab' (default) "
                         "adds a forced pallas-vs-xla A/B pass; "
                         "'pallas'/'xla' force one dispatch for the "
                         "whole run")
    ap.add_argument("--step", choices=("ab", "fused", "chained",
                                       "xla"),
                    default=None,
                    help="one-launch refine-iteration benchmark "
                         "instead of the headline: 'ab' measures "
                         "the fused single-launch step kernel "
                         "(RAFT_STEP_PALLAS) against the chained "
                         "motion+GRU launches and the pure-XLA "
                         "path and records the fused/chained "
                         "speedup plus each arm's handoff HBM "
                         "bytes (the BENCH_r10 artifact); "
                         "'fused'/'chained'/'xla' run one arm")
    args = ap.parse_args(argv)
    if args.step is not None:
        step_main(arm=args.step)
        return
    if args.gru == "pallas":
        os.environ["RAFT_GRU_PALLAS"] = "1"
    elif args.gru == "xla":
        os.environ["RAFT_GRU_PALLAS"] = "0"
    if args.motion == "pallas":
        os.environ["RAFT_MOTION_PALLAS"] = "1"
    elif args.motion == "xla":
        os.environ["RAFT_MOTION_PALLAS"] = "0"
    main(gru=args.gru, motion=args.motion)


if __name__ == "__main__":
    _cli(sys.argv[1:])
