"""The serving engine: warmup, pipelined dispatch, request lifecycle.

Closes the batch-1 gap (BENCH_r05: 31.5 pairs/s at batch 1 vs 99.0 at
batch 128 per chip) for streams of independent requests by putting three
mechanisms behind one ``submit() -> Future`` API:

* **Dynamic batching** — client threads pad (InputPadder, client-side so
  pad work rides the producers) and enqueue into the shape-bucketed
  :class:`~raft_tpu.serving.batcher.ShapeBucketBatcher`; batches close
  on max-size or deadline, partial batches are tail-padded by
  repeating the last request (the batched-eval trick: one executable
  per bucket, never per partial size), and two priority classes per
  bucket let interactive traffic batch ahead of opt-in background work.
* **Pipelined multi-bucket dispatch** — a router thread hands each
  closed batch to its bucket's :class:`_BucketStream`, whose dispatch
  thread stacks and *dispatches* batch N+1 while the device still
  computes batch N (`jax.Array` dispatch is non-blocking; only the
  stream's completion thread syncs, via ``np.asarray``). Streams are
  independent per bucket, so a big-bucket batch in flight never
  head-of-line-blocks small-bucket traffic — both buckets' batches are
  dispatched and synced concurrently. Each stream's bounded in-flight
  queue (``pipeline_depth``) provides per-bucket backpressure so a slow
  device can't queue unbounded work, and streams for shapes outside the
  configured buckets are capped (``max_dynamic_streams``, LRU-retired)
  so arbitrary-shape traffic can't grow threads without bound. With
  ``donate`` (default on TPU)
  the input image buffers are donated to the executable, so
  steady-state serving holds one batch of inputs per active bucket,
  not one per pipeline slot.
* **Warmup + persistent compile cache** — ``warmup()`` pre-compiles the
  executable for every configured bucket (counted by the
  :class:`~raft_tpu.serving.metrics.CompileWatch` probe), and
  :func:`raft_tpu.utils.compile_cache.enable_compile_cache` turns on
  XLA's on-disk cache (``$JAX_COMPILATION_CACHE_DIR``, else the
  checkout's ``.jax_cache/`` — the one wiring every entry point uses) so a
  serving process restart pays seconds, not minutes, before its first
  request. The zero-compile contract extends over the trace-time kernel
  flags (``RAFT_CORR_BACKEND``/``RAFT_CORR_BAND``, ``RAFT_GRU_PALLAS``):
  each bucket executable bakes the dispatch the environment held when it
  was warmed — with the fused Pallas GRU cell enabled, warmup compiles
  the kernel path once per bucket and steady-state requests stay at zero
  compiles (probe-asserted in ``tests/test_gru_pallas.py``). Flip those
  flags before engine construction, never between warmup and serving.
* **Uint8 wire format + staging arena** — requests whose pixels are
  integral [0, 255] (auto-detected once at submit; see ``wire_cast``)
  stay uint8 through padding, batching and the H2D transfer — 4x fewer
  host-path bytes — and normalize in-model to bit-identical flow; the
  wire dtype tags the bucket key and the executable cache key, and
  warmup compiles BOTH dtypes per bucket so mixed traffic never
  compiles. Batches are staged into preallocated recycled host buffers
  (:class:`~raft_tpu.utils.staging.StagingArena` — one memcpy per
  request, no per-batch pad-then-stack allocation), and
  ``submit(low_res=True)`` shrinks the return path too: the 1/8-grid
  flow, 64x fewer D2H bytes, with host-side :func:`upsample_flow`
  recovery.

On top of those sits the **robustness layer** (Clipper-style: degrade
gracefully, never let one failure take out its co-batched neighbors):

* **Circuit breaker** — ``breaker_threshold`` consecutive dispatch/sync
  failures trip the :class:`~raft_tpu.serving.health.CircuitBreaker`
  OPEN: submits (and queued batches) fail fast with
  :class:`~raft_tpu.serving.health.EngineUnhealthy` instead of queueing
  doomed work behind a sick device; after ``breaker_cooldown_s`` the
  next batch through is the half-open probe that closes it again.
* **Batch error isolation** — when a dispatched batch fails (at
  dispatch or at sync), the engine retries every member once as a
  full-padded *single*, so one poisoned input fails alone instead of
  failing its whole batch (injectable via
  ``RAFT_FAULT_SERVING_POISON_NTH``).
* **Health/readiness** — ``health()`` summarizes the engine for a load
  balancer probe (``starting/warming/ready/degraded/open/closed``),
  and every robustness signal (swaps, rollbacks, breaker trips, queue
  depth, in-flight batches) streams through
  :class:`~raft_tpu.serving.metrics.ServingMetrics`.
* **Hot model swap** — :meth:`swap_predictor` atomically replaces the
  predictor between batches (the dispatch path reads it under a lock),
  the primitive :class:`~raft_tpu.serving.reload.HotReloader` builds
  canary-validated checkpoint reload on. In-flight batches already
  captured the old weights at dispatch and complete normally.

The engine *reuses* :class:`raft_tpu.evaluate.FlowPredictor` — including
its ``corr_impl="auto"`` per-shape engine choice and its compiled-
executable cache — rather than duplicating the forward; the serve path
adds only queueing, stacking and unpadding around
``FlowPredictor.dispatch_batch``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from raft_tpu.observability import registry as obs_registry
from raft_tpu.observability import tracer as tracing
from raft_tpu.observability.slo import SloTracker
from raft_tpu.resilience import active_injector
from raft_tpu.serving import health as health_mod
from raft_tpu.serving.batcher import (PRIORITY_HIGH, PRIORITY_LOW,
                                      BacklogFull, QueuedRequest,
                                      RequestTimedOut, ShapeBucketBatcher)
from raft_tpu.serving.brownout import BrownoutController
from raft_tpu.serving.health import CircuitBreaker, EngineUnhealthy
from raft_tpu.serving.metrics import (CompileWatch, ServingMetrics,
                                      xla_compile_count)
from raft_tpu.utils.compile_cache import enable_compile_cache
from raft_tpu.utils.padder import InputPadder
from raft_tpu.utils.profiling import HostStageTimer
from raft_tpu.utils.staging import StagingArena

# Shared no-op context for `with <stage>, <maybe-span>:` sites — the
# disabled-tracing path must not allocate a context manager per batch.
_NULL = contextlib.nullcontext()

# -- wire format ---------------------------------------------------------
#
# RAFT normalizes [0, 255] images INSIDE the jitted forward
# (models/normalize.py), so the host path has no reason to widen
# integral pixels to float32: a uint8 request stays uint8 through
# padding, the staging arena, and the H2D transfer — 4x fewer bytes on
# every host copy — and only widens on device, where the normalization
# makes the result bit-identical to the float32 path (astype of an
# integral value in [0, 255] is exact). The wire dtype is detected ONCE
# at submit, tagged onto the request's bucket key (so uint8 and float32
# traffic batch separately, each against its own pre-warmed
# executable), and carried in the FlowPredictor cache keys.

WIRE_U8 = "u8"
WIRE_F32 = "f32"
_WIRE_TAGS = (WIRE_U8, WIRE_F32)


def wire_cast(image: np.ndarray):
    """Detect one image's wire format: ``("u8", arr)`` for uint8 input
    or any float/int array whose values are integral and in [0, 255]
    (cast to uint8 — exact, see models/normalize.py), else
    ``("f32", arr)`` with the array in float32. The single O(N) host
    check of the request path, paid in the submitting client's thread
    like padding."""
    a = np.asarray(image)
    if a.dtype == np.uint8:
        return WIRE_U8, a
    f = a.astype(np.float32, copy=False)
    with np.errstate(invalid="ignore"):    # NaN -> uint8 is rejected
        u = f.astype(np.uint8)             # below, not warned about
    # Round-trip equality rejects non-integral values, out-of-range
    # values (uint8 wraps them) and NaN in one vectorized pass.
    if np.array_equal(u.astype(np.float32), f):
        return WIRE_U8, u
    return WIRE_F32, f


def request_wire(image1: np.ndarray, image2: np.ndarray):
    """Wire format of one request PAIR: uint8 only when both frames
    qualify; a mixed pair falls back to float32 for both (exact — the
    uint8 side widens losslessly), so the pair always enters one
    executable with one dtype."""
    t1, a1 = wire_cast(image1)
    t2, a2 = wire_cast(image2)
    if t1 == t2:
        return t1, a1, a2
    return (WIRE_F32, a1.astype(np.float32, copy=False),
            a2.astype(np.float32, copy=False))


def _wire_of(bucket: Tuple) -> str:
    """The wire tag of a batcher bucket key (always its LAST element on
    engine-built buckets; tolerate untagged keys for tooling that
    constructs buckets by hand)."""
    return bucket[-1] if bucket and bucket[-1] in _WIRE_TAGS else WIRE_F32


def _base_of(bucket: Tuple) -> Tuple:
    """A bucket key with its wire tag stripped — what every
    length/value-based bucket parser matches against. The tag strings
    can never collide with the other tail elements ("warm"/"cold"/
    "mesh"/ints), so stripping is unambiguous."""
    return (bucket[:-1] if bucket and bucket[-1] in _WIRE_TAGS
            else bucket)


def upsample_flow(flow_low: np.ndarray, padder: Optional[InputPadder] = None,
                  factor: int = 8) -> np.ndarray:
    """Host-side full-resolution recovery for a ``low_res=True``
    response: align-corners bilinear upsample of the 1/8-grid flow with
    the vectors scaled by ``factor`` — the model's ``upflow8``
    arithmetic in pure numpy, so no executable is compiled (the
    zero-post-warmup-compile contract is why this lives host-side).
    ``padder`` (stamped on low_res futures as ``future.padder``) crops
    the result back to the raw resolution.

    NOT bit-identical to the full-resolution response: the model's
    in-graph convex upsampling uses a learned per-pixel mask the 1/8
    flow alone doesn't carry. ``low_res`` trades that fidelity for 64x
    fewer D2H + response bytes; callers who need the exact full-res
    flow submit without it."""
    tr = tracing.current()   # module-level helper: no engine to hold
    with (tr.span("upsample_flow") if tr is not None else _NULL):
        return _upsample_flow_impl(flow_low, padder, factor)


def _upsample_flow_impl(flow_low, padder, factor) -> np.ndarray:
    f = np.asarray(flow_low, np.float32)
    squeeze = f.ndim == 3
    if squeeze:
        f = f[None]
    b, h, w, c = f.shape
    H, W = h * factor, w * factor
    ys = (np.linspace(0.0, h - 1.0, H, dtype=np.float32) if h > 1
          else np.zeros(H, np.float32))
    xs = (np.linspace(0.0, w - 1.0, W, dtype=np.float32) if w > 1
          else np.zeros(W, np.float32))
    y0 = np.floor(ys).astype(np.intp)
    x0 = np.floor(xs).astype(np.intp)
    y1 = np.minimum(y0 + 1, h - 1)
    x1 = np.minimum(x0 + 1, w - 1)
    # float32 - intp promotes to float64; keep the weights (and so the
    # response) in float32.
    wy = (ys - y0).astype(np.float32)[None, :, None, None]
    wx = (xs - x0).astype(np.float32)[None, None, :, None]
    rows = f[:, y0] * (1.0 - wy) + f[:, y1] * wy          # (b, H, w, c)
    out = rows[:, :, x0] * (1.0 - wx) + rows[:, :, x1] * wx
    out = np.float32(factor) * out
    if squeeze:
        out = out[0]
    if padder is not None:
        out = padder.unpad(out)
    return np.ascontiguousarray(out)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs for one :class:`ServingEngine`.

    Attributes:
      max_batch: executable batch size per bucket; batches close at this
        many requests and partial batches are tail-padded up to it.
      max_wait_ms: deadline for a non-full bucket, from its oldest
        request's submit. The latency/throughput dial: 0 serves
        whatever queued (lowest latency), larger values fill batches.
      buckets: raw image ``(H, W)`` shapes to pre-compile at warmup
        (padded internally — pass what requests will carry, e.g.
        ``(436, 1024)`` for Sintel). Requests outside the configured
        buckets still serve, paying their compile on first contact
        (counted in ``metrics.compiles``); their dispatch streams are
        transient, capped by ``max_dynamic_streams``.
      pad_mode: InputPadder mode for every request ("sintel" centers
        vertical padding, "kitti" bottom-pads).
      factor: pad-to multiple (8 for stride-8 RAFT features).
      max_pending: backlog cap; submits beyond it raise
        :class:`~raft_tpu.serving.batcher.BacklogFull` — except a HIGH
        submit, which first sheds the youngest queued LOW request.
      queue_timeout_ms: per-request time-in-queue budget. A request
        still undispatched this long after submit has its future
        completed with :class:`~raft_tpu.serving.batcher
        .RequestTimedOut` instead of occupying a batch slot — under
        overload clients get a fast, clear error rather than an
        arbitrarily stale result. Counted in ``metrics.timeouts``.
        ``None``/``0`` disables (requests wait forever).
      pipeline_depth: dispatched-but-unsynced batches allowed in flight
        *per bucket stream* (2 = classic double buffering: host stacks
        N+1 while device runs N). Buckets pipeline independently — see
        :class:`_BucketStream`.
      max_dynamic_streams: cap on live dispatch streams for buckets
        OUTSIDE the configured ``buckets`` set (each stream is a
        thread pair + a pipeline queue; ``submit`` accepts arbitrary
        shapes, so without a cap varied traffic would grow threads
        without bound). Configured buckets keep permanent streams;
        beyond the cap the least-recently-used dynamic stream is
        drained (its queued and in-flight work still resolves) and
        retired — the shape simply gets a fresh stream on its next
        batch.
      donate: donate input image buffers to the executable. ``None``
        resolves to True on TPU, False elsewhere (CPU/older backends
        warn and ignore donation).
      persistent_cache: falsy → leave XLA's cache config alone; truthy
        → ``enable_compile_cache()``: ``$JAX_COMPILATION_CACHE_DIR`` if
        set, else the checkout's ``.jax_cache/`` (a string value is
        accepted for old configs and means the same as True — the
        directory is placed from outside, never from code).
      breaker_threshold: consecutive dispatch/sync failures that trip
        the circuit breaker OPEN (submit then fails fast with
        :class:`~raft_tpu.serving.health.EngineUnhealthy`).
      breaker_cooldown_s: seconds OPEN before the breaker half-opens
        and lets one probe batch test the device again.
      replica_id: name of this engine within a serving fleet
        (:mod:`raft_tpu.serving.fleet`). When set, every response
        future is stamped with ``future.replica_id`` so load
        generators and fleet drills can attribute each response (and
        each failure) to the engine that produced it.
      warm_buckets: raw ``(H, W)`` shapes expected to carry *stream*
        traffic (``open_stream``). Warmup pre-compiles the session
        path's three executables per shape — encode, cold refine (full
        ``iters``), warm refine (``warm_iters``) — and their
        ``(padded, "warm")``/``(padded, "cold")`` dispatch streams are
        dedicated (never LRU-retired). Stream traffic outside this set
        still serves, paying first-contact compiles.
      warm_iters: GRU iterations for WARM stream pairs (cold pairs and
        stateless requests keep the predictor's full ``iters``). The
        streaming quality/latency dial: warm frames start from the
        propagated previous flow, so they converge in fewer iterations.
        ``None`` leaves the predictor's own ``warm_iters`` (→ full
        ``iters`` when unset there too).
      iters_ladder: strictly-descending GRU iteration counts below the
        predictor's full ``iters`` (e.g. ``(8, 6, 4)`` under 12) — the
        graceful-brownout quality ladder. Warmup pre-compiles every
        configured bucket at every ladder level (and warm stream
        buckets at each capped warm level), ``submit(iters=...)``
        accepts exactly ``{full iters} ∪ ladder`` (anything else is a
        ``ValueError`` — never a silent compile), and the
        :class:`~raft_tpu.serving.brownout.BrownoutController` steps
        LOW traffic down these levels under pressure. Empty = no
        ladder: ``submit(iters=full)`` still works, everything else is
        rejected.
      brownout_high_water: pressure (queued requests plus in-flight
        batches) at or above which the brownout controller steps LOW
        traffic one rung down the ladder. ``0`` (default) disables the
        controller — the ladder is then only reachable via explicit
        ``submit(iters=...)``.
      brownout_low_water: pressure at or below which the controller
        steps back up one rung (must be < high_water — the hysteresis
        band).
      brownout_dwell_ms: minimum milliseconds between ladder steps in
        either direction (flap damping).
      sharded_buckets: raw ``(H, W)`` shapes served through the
        spatially-sharded dispatch path (``FlowPredictor
        .sharded_dispatch``: one request's image rows — and its (HW)²
        correlation volume — split over ``sharded_shards`` chips, the
        multi-chip latency path for high-res pairs that cannot batch).
        Padded with ``factor = sharded_shards * factor`` so the padded
        rows always divide the spatial axis (and the /8 feature rows
        divide it too — the sharded banded kernel's requirement).
        Warmup pre-compiles each one's executable; their
        ``(ph, pw, "mesh")`` buckets live on their own permanent
        :class:`_BucketStream`, so big-shard and small-batch traffic
        dispatch concurrently through the per-bucket streams.
      sharded_shards: spatial shard count for the sharded path (the
        serving mesh is ``(1, sharded_shards)`` over the first that
        many visible devices). Required >= 2 whenever
        ``sharded_buckets`` or ``sharded_area_threshold`` is set.
      sharded_area_threshold: raw ``H * W`` pixel area at or above
        which ANY submitted shape auto-routes to the sharded path
        (oversized requests need the latency/memory help even when
        their exact shape wasn't configured; such shapes pay a
        first-contact compile like any unconfigured bucket). ``0``
        (default) disables auto-routing — only ``sharded_buckets``
        shapes go sharded.
      sharded_max_batch: dispatch size of sharded buckets (default 1:
        the path exists for latency-bound single requests, and
        batching multiplies per-chip activation memory at exactly the
        resolutions that needed sharding). Other buckets keep
        ``max_batch``.
      continuous: iteration-granular continuous batching
        (:class:`~raft_tpu.serving.contbatch.ContinuousScheduler`).
        ``True`` routes stateless traffic on configured ``buckets``
        through per-shape slot tables — requests occupy device slots
        only for the GRU iterations they actually use, so early exit
        and the iters ladder become wall-clock instead of counted
        savings, and every quality level shares ONE ``(ph, pw,
        "cont")`` bucket and one step-executable family instead of a
        bucket each. ``False`` pins the monolithic path. ``None``
        (default) defers to the ``RAFT_CONTBATCH`` env flag ('1' = on;
        'auto'/'0' = off — opt-in until an on-TPU capture, BASELINE.md
        round 9). Stream, sharded and unconfigured-shape traffic
        always keeps the monolithic path. With the scheduler off the
        serve path is byte-identical to previous builds.
      contbatch_steps: update iterations per continuous ``step``
        launch (the scheduling quantum: smaller chunks retire/admit
        sooner at more launch overhead; one executable per value).
      contbatch_slots: slot-table width per continuous bucket
        (``0`` → ``max_batch``).
      trace: force request-scoped tracing on for this engine (mints a
        process tracer via :func:`raft_tpu.observability.enable_tracing`
        if none is installed). Default off: the engine still picks up a
        tracer that was enabled *before* construction, and when neither
        holds, the request path carries no trace ids, no span
        allocations, and is bit-identical to pre-tracing builds
        (asserted by tests/test_observability.py).
      trace_capacity: ring capacity used when ``trace=True`` has to
        mint the tracer (ignored when one already exists).
      metrics_port: when set, serve this engine's telemetry registry
        over stdlib HTTP on ``127.0.0.1:<port>`` (``/metrics``
        Prometheus text, ``/metrics.json``). ``0`` binds an ephemeral
        port (see ``ServingEngine.metrics_server``); ``None`` (default)
        starts no server.
      metrics_host: bind host for the telemetry HTTP server. Loopback
        by default; a multi-host deployment that scrapes workers
        off-box sets an interface address (or ``"0.0.0.0"``) here —
        the same bind-host story as the serving edge listener.
      slo_ms: per-priority-class latency objectives,
        ``(("high", 50.0), ("low", 250.0))``-style. When non-empty the
        engine feeds every completion into an
        :class:`~raft_tpu.observability.slo.SloTracker` whose rolling
        violation ratios ride the engine registry as ``slo_*`` gauges.
    """

    max_batch: int = 8
    max_wait_ms: float = 5.0
    buckets: Tuple[Tuple[int, int], ...] = ()
    pad_mode: str = "sintel"
    factor: int = 8
    max_pending: int = 2048
    queue_timeout_ms: Optional[float] = None
    pipeline_depth: int = 2
    max_dynamic_streams: int = 8
    donate: Optional[bool] = None
    persistent_cache: object = None
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 30.0
    replica_id: Optional[str] = None
    warm_buckets: Tuple[Tuple[int, int], ...] = ()
    warm_iters: Optional[int] = None
    iters_ladder: Tuple[int, ...] = ()
    brownout_high_water: int = 0
    brownout_low_water: int = 0
    brownout_dwell_ms: float = 250.0
    sharded_buckets: Tuple[Tuple[int, int], ...] = ()
    sharded_shards: int = 0
    sharded_area_threshold: int = 0
    sharded_max_batch: int = 1
    continuous: Optional[bool] = None
    contbatch_steps: int = 2
    contbatch_slots: int = 0
    trace: bool = False
    trace_capacity: int = 65536
    metrics_port: Optional[int] = None
    metrics_host: str = "127.0.0.1"
    slo_ms: Tuple[Tuple[str, float], ...] = ()


class _BucketStream:
    """One bucket's independent dispatch/completion pipeline.

    The engine's router thread hands closed batches to the stream's
    ``work`` queue; the stream's dispatch thread stacks + dispatches
    them (non-blocking) into its own bounded ``inflight`` queue, and
    its completion thread syncs. Because every bucket owns its own
    pair of threads and its own ``pipeline_depth`` backpressure bound,
    a large-bucket batch that takes long on the device never
    head-of-line-blocks another bucket's traffic — multi-bucket
    concurrent dispatch, the single-stream-limit lift the ROADMAP
    carried. Bit-exactness is unaffected: each request still runs
    through its bucket's one executable (pinned by
    tests/test_serving.py::TestConcurrentDispatch).

    Streams are created lazily by the router (one per padded shape
    that actually sees traffic) and torn down by a ``None`` sentinel
    on ``work`` — when the engine closes, or early for shapes outside
    the configured buckets once ``max_dynamic_streams`` is reached
    (least-recently-used first; the sentinel drains queued and
    in-flight work to futures before the threads exit, so retirement
    never drops a request).
    """

    def __init__(self, engine: "ServingEngine",
                 bucket: Tuple[int, int]):
        self.engine = engine
        self.bucket = bucket
        self.last_used = time.monotonic()
        self.work: queue.Queue = queue.Queue()
        self.inflight: queue.Queue = queue.Queue(
            maxsize=max(engine.config.pipeline_depth, 1))
        name = "serving-" + "x".join(str(p) for p in bucket)
        self.dispatcher = threading.Thread(
            target=self._dispatch_loop, name=f"{name}-dispatch",
            daemon=True)
        self.completer = threading.Thread(
            target=self._completion_loop, name=f"{name}-complete",
            daemon=True)
        self.dispatcher.start()
        self.completer.start()

    def put(self, batch) -> None:
        self.work.put(batch)

    def close(self) -> None:
        """Ask the stream to drain its queued work and exit."""
        self.work.put(None)

    def join(self, timeout: Optional[float] = None) -> None:
        self.dispatcher.join(timeout)
        self.completer.join(timeout)

    def _dispatch_loop(self) -> None:
        eng = self.engine
        try:
            while True:
                batch = self.work.get()
                if batch is None:
                    break
                eng._dispatch_one(batch, self.inflight)
        except BaseException as e:   # fatal: fail fast, not silently
            eng._set_fatal(e)
            while True:
                try:
                    left = self.work.get_nowait()
                except queue.Empty:
                    break
                if left:
                    for r in left:
                        r.future.set_exception(e)
                        eng._trace_end(r, "fatal")
                    eng.metrics.record_error(len(left))
        finally:
            self.inflight.put(None)

    def _completion_loop(self) -> None:
        eng = self.engine
        while True:
            item = self.inflight.get()
            if item is None:
                break
            batch, out, staged = item
            is_stream = bool(batch) and batch[0].session is not None
            # The return-path half of the wire-format work: sync (D2H)
            # only the outputs some batch member actually needs.
            # flow_up is skipped when the whole batch opted into
            # low_res responses — 64x fewer D2H bytes per all-low
            # batch; flow_low is skipped unless a member wants it
            # (streams always need it for the warm-start handoff).
            want_full = is_stream or any(not r.low_res for r in batch)
            want_low = is_stream or any(r.low_res for r in batch)
            tr = eng._tracer
            try:
                with eng.stages.stage("sync"), \
                        (tr.span("sync", args={"n": len(batch)})
                         if tr is not None else _NULL):
                    flow_up = np.asarray(out[1]) if want_full else None
                    flow_low = np.asarray(out[0]) if want_low else None
                    if is_stream:
                        fmap2 = np.asarray(out[2])
                    if flow_up is not None:
                        eng.stages.add_bytes("sync", flow_up.nbytes)
                    if flow_low is not None:
                        eng.stages.add_bytes("sync", flow_low.nbytes)
            except Exception as e:
                with eng._state_lock:
                    eng._inflight_batches -= 1
                eng.breaker.record_failure()
                eng._isolate_failed_batch(batch, e)
                continue
            # Outputs are host-side: the executable is done with its
            # inputs, so the staging buffers can be recycled.
            eng.arena.release(*staged)
            with eng._state_lock:
                eng._inflight_batches -= 1
            eng.breaker.record_success()
            now = time.monotonic()
            served_iters = eng._bucket_iters(self.bucket)
            if not is_stream and len(out) > 2:
                # Early-exit path: out[2] is per-sample iterations
                # actually run (tail-pad slots excluded from the
                # savings — they aren't served work).
                used = np.asarray(out[2])[:len(batch)]
                saved = int(np.maximum(served_iters - used, 0).sum())
                if saved:
                    eng.metrics.record_early_exit_saved(saved)
            eng.metrics.record_quality(served_iters, n=len(batch))
            returned = 0
            with eng.stages.stage("unpad"), \
                    (tr.span("unpad", args={"n": len(batch)})
                     if tr is not None else _NULL):
                for j, r in enumerate(batch):
                    if is_stream:
                        # State handoff BEFORE resolving the future:
                        # this pair's fmap2 slice is the session's next
                        # fmap1, its low-res flow the next flow_init
                        # seed. The client's next submit serializes on
                        # the future, so it always sees restored state.
                        r.session._complete(fmap2[j:j + 1].copy(),
                                            flow_low[j].copy())
                    if r.low_res:
                        result = flow_low[j].copy()
                    else:
                        result = r.padder.unpad(flow_up[j])
                    returned += result.nbytes
                    r.future.set_result(result)
                    eng._trace_end(r, "ok")
                    latency = now - r.t_submit
                    eng.metrics.record_done(latency)
                    if eng.slo is not None:
                        eng.slo.observe(r.priority, latency)
            eng.metrics.record_returned_bytes(returned)


class ServingEngine:
    """Latency/throughput-focused request front-end over a
    :class:`~raft_tpu.evaluate.FlowPredictor`.

    Lifecycle::

        predictor = load_predictor(ckpt, ...)          # evaluate.py
        engine = ServingEngine(predictor, ServingConfig(
            max_batch=32, max_wait_ms=5.0, buckets=((436, 1024),)))
        engine.start()                                  # warms buckets
        fut = engine.submit(image1, image2)             # thread-safe
        flow = fut.result()                             # (H, W, 2) numpy
        engine.health()                                 # LB probe dict
        engine.close()                                  # drains in-flight

    Futures resolve to the *unpadded* full-resolution flow, bit-identical
    to ``padder.unpad(predictor(padded1, padded2)[1])`` for the same
    inputs (tail-padded batch slots don't perturb real samples —
    per-sample batch independence, pinned by tests/test_serving.py).
    """

    def __init__(self, predictor, config: Optional[ServingConfig] = None):
        import jax

        self.predictor = predictor
        self.config = config or ServingConfig()
        if self.config.persistent_cache:
            enable_compile_cache()
        donate = self.config.donate
        if donate is None:
            donate = jax.default_backend() == "tpu"
        predictor.donate_images = donate
        self._donate = donate
        if self.config.warm_iters is not None:
            # Part of the refine executable cache key — set before any
            # warmup/serve compile so warm buckets warm the right
            # executable.
            predictor.warm_iters = self.config.warm_iters
        self._full_iters = int(predictor.iters)
        self._base_warm_iters = int(predictor.warm_iters
                                    or self._full_iters)
        ladder = tuple(int(v) for v in self.config.iters_ladder)
        if ladder:
            bad = [v for v in ladder if not 1 <= v < self._full_iters]
            if bad:
                raise ValueError(
                    f"iters_ladder levels must sit strictly below the "
                    f"predictor's full iters={self._full_iters} (and be "
                    f">= 1), got {ladder}")
            if any(a <= b for a, b in zip(ladder, ladder[1:])):
                raise ValueError("iters_ladder must be strictly "
                                 f"descending, got {ladder}")
        self._iters_ladder = ladder
        # submit(iters=...) accepts exactly these (warmed) levels.
        self._iters_levels = frozenset({self._full_iters, *ladder})
        # Warm stream pairs ladder to min(base warm, level): a level
        # above the warm count would *raise* warm quality under
        # overload. Only effs that differ from the base need their own
        # executable/bucket.
        self._warm_effs = tuple(sorted(
            {min(self._base_warm_iters, v) for v in ladder}
            - {self._base_warm_iters}, reverse=True))
        self.brownout: Optional[BrownoutController] = None
        if ladder and self.config.brownout_high_water >= 1:
            self.brownout = BrownoutController(
                ladder,
                high_water=self.config.brownout_high_water,
                low_water=self.config.brownout_low_water,
                dwell_s=self.config.brownout_dwell_ms / 1e3)
        self.metrics = ServingMetrics()
        self.stages = HostStageTimer()
        # Preallocated host staging buffers, recycled batch-to-batch by
        # the completion threads (see StagingArena).
        self.arena = StagingArena()
        self.breaker = CircuitBreaker(
            threshold=self.config.breaker_threshold,
            cooldown_s=self.config.breaker_cooldown_s)
        # Spatially-sharded serving path (the multi-chip latency path
        # for high-res, unbatchable requests): a (1, sharded_shards)
        # serving mesh held by the ENGINE, not the predictor — the one
        # predictor keeps serving the unsharded batched buckets while
        # sharded buckets dispatch through predictor.sharded_dispatch
        # (disjoint ("sharded", ...) executable-cache keys).
        self._sharded_mesh = None
        self._sharded_shards = int(self.config.sharded_shards)
        self._sharded_factor = self.config.factor
        sharded_wanted = (self.config.sharded_buckets
                          or self.config.sharded_area_threshold)
        if sharded_wanted:
            if self._sharded_shards < 2:
                raise ValueError(
                    "sharded_buckets/sharded_area_threshold need "
                    f"sharded_shards >= 2, got "
                    f"{self.config.sharded_shards} (the sharded path "
                    "splits one request's rows across chips)")
            n_dev = len(jax.devices())
            if n_dev < self._sharded_shards:
                raise ValueError(
                    f"sharded_shards={self._sharded_shards} exceeds the "
                    f"{n_dev} visible devices — this host cannot hold "
                    "the serving mesh")
            from raft_tpu.parallel import make_mesh
            self._sharded_mesh = make_mesh(
                n_data=1, n_spatial=self._sharded_shards,
                devices=jax.devices()[:self._sharded_shards])
            # Padding to sharded_shards * factor makes every sharded
            # bucket's rows divide the spatial axis (least multiple >=
            # H — InputPadder's pad math) AND keeps the /8 feature rows
            # divisible, so sharded_dispatch never needs its internal
            # extra-pad fallback on the serving path.
            self._sharded_factor = (self._sharded_shards
                                    * self.config.factor)
        self._sharded_padded = frozenset(
            InputPadder((*hw, 3), mode=self.config.pad_mode,
                        factor=self._sharded_factor).padded_shape
            for hw in self.config.sharded_buckets)
        # Routing matches RAW shapes: a small configured bucket may pad
        # to the same shape as a sharded bucket under the coarser
        # sharded factor, and must keep its batched path regardless.
        self._sharded_raw = frozenset(
            (int(h), int(w)) for h, w in self.config.sharded_buckets)
        self._batched_raw = (
            frozenset((int(h), int(w)) for h, w in self.config.buckets)
            | frozenset((int(h), int(w))
                        for h, w in self.config.warm_buckets))
        self.batcher = ShapeBucketBatcher(
            max_batch=self.config.max_batch,
            max_wait_s=self.config.max_wait_ms / 1e3,
            max_pending=self.config.max_pending,
            max_batch_for=self._bucket_max)
        self._inflight_batches = 0
        # bucket -> _BucketStream, created lazily by the router thread
        # (the only writer); _streams_lock guards reads from other
        # threads (health, close). Streams for configured buckets are
        # permanent; dynamic (out-of-bucket) streams are capped at
        # max_dynamic_streams, retired LRU-first into _retired where
        # they drain and exit (joined at close).
        self._streams: Dict[Tuple, _BucketStream] = {}
        # Stateless buckets key on the padded (H, W); stream (session)
        # buckets extend it with a "warm"/"cold" tag — warm frames batch
        # separately from cold (different executables and iteration
        # counts), and both tags of a configured warm bucket keep
        # permanent dispatch streams.
        self._stateless_padded = frozenset(
            InputPadder((*hw, 3), mode=self.config.pad_mode,
                        factor=self.config.factor).padded_shape
            for hw in self.config.buckets)
        self._warm_padded = frozenset(
            InputPadder((*hw, 3), mode=self.config.pad_mode,
                        factor=self.config.factor).padded_shape
            for hw in self.config.warm_buckets)
        # Every ladder level of a configured bucket (and every capped
        # warm level of a warm bucket) is pre-compiled by warmup, so
        # their streams are dedicated too — stepping the brownout
        # ladder must never retire/recreate a stream mid-overload.
        # Each entry exists once per wire dtype (the tag is the LAST
        # bucket-key element): warmup compiles both, so uint8 and
        # float32 traffic on a configured bucket are equally permanent.
        self._dedicated_buckets = (
            frozenset((*p, wt) for p in self._stateless_padded
                      for wt in _WIRE_TAGS)
            | frozenset((*p, kind, wt) for p in self._warm_padded
                        for kind in ("warm", "cold")
                        for wt in _WIRE_TAGS)
            | frozenset((*p, lvl, wt) for p in self._stateless_padded
                        for lvl in ladder for wt in _WIRE_TAGS)
            | frozenset((*p, "warm", eff, wt) for p in self._warm_padded
                        for eff in self._warm_effs
                        for wt in _WIRE_TAGS)
            # Sharded buckets keep their own permanent streams: the
            # whole point is big-shard dispatch overlapping the
            # small-batch streams, so they must never be LRU-retired
            # under mixed traffic.
            | frozenset((*p, "mesh", wt) for p in self._sharded_padded
                        for wt in _WIRE_TAGS))
        # Continuous (iteration-granular) batching: config wins when
        # set; None defers to the RAFT_CONTBATCH env flag, read ONCE
        # here at construction (like donation — never between warmup
        # and serving, so the executable family can't change under
        # load). Only configured stateless buckets route continuous:
        # their step family is warmed, and unconfigured shapes keep
        # the bounded dynamic-stream path.
        cont = self.config.continuous
        if cont is None:
            from raft_tpu.utils.envflags import resolve_contbatch
            cont = resolve_contbatch() == "1"
        self.contbatch = None
        if cont:
            from raft_tpu.serving.contbatch import ContinuousScheduler
            self.contbatch = ContinuousScheduler(self)
        self._retired: List[_BucketStream] = []
        self._streams_lock = threading.Lock()
        self._router: Optional[threading.Thread] = None
        self._started = False
        self._warming = False
        self._closed = False
        self._fatal: Optional[BaseException] = None
        # Serializes predictor reads on the dispatch path against
        # swap_predictor (hot reload): swaps land *between* batches,
        # never mid-dispatch.
        self._swap_lock = threading.Lock()
        # Degradation flags beyond the breaker (e.g. "canary-rollback"
        # while the reloader pins the old model past a bad checkpoint).
        self._degraded_reasons: set = set()
        self._state_lock = threading.Lock()
        self._submit_seq = 0
        self._stream_seq = 0
        m = self.metrics
        m.set_gauge_source("queue_depth", self.batcher.pending)
        m.set_gauge_source("inflight_batches",
                           lambda: self._inflight_batches)
        m.set_gauge_source("breaker_trips", lambda: self.breaker.trips)
        m.set_gauge_source(
            "sharded_shards",
            lambda: (self._sharded_shards
                     if self._sharded_mesh is not None else 0))
        if self.contbatch is not None:
            m.set_gauge_source("contbatch_occupied",
                               self.contbatch.occupied)
        m.set_gauge_source(
            "health_state",
            lambda: health_mod.HEALTH_CODES[self.health_state()])
        if self.brownout is not None:
            ctl = self.brownout
            m.set_gauge_source("brownout_level", lambda: ctl.level)
            m.set_gauge_source("brownout_transitions",
                               lambda: ctl.transitions)
            m.set_gauge_source("brownout_time_s",
                               ctl.time_in_brownout_s)

        # -- observability ---------------------------------------------
        # Tracer reference is captured ONCE, here: every hot-path site
        # tests `self._tracer is not None` and nothing else, so with
        # tracing off the request path mints no ids and allocates no
        # span objects (tests/test_observability.py asserts both).
        if config.trace:
            tracing.enable(config.trace_capacity)
        self._tracer = tracing.current()
        # Per-engine registry (NOT the process default): instrument
        # names are deterministic per engine, golden-pinned by
        # tests/test_observability.py, and two engines in one process
        # (fleet) never fight over label-free gauges.
        self.registry = obs_registry.MetricsRegistry()
        self.metrics.attach_registry(self.registry)
        self.slo: Optional[SloTracker] = None
        if config.slo_ms:
            self.slo = SloTracker(dict(config.slo_ms))
            self.slo.attach_registry(self.registry)
        self.metrics_server = None
        if config.metrics_port is not None:
            self.metrics_server = obs_registry.start_http_server(
                self.registry, config.metrics_port,
                host=config.metrics_host)

    # -- trace plumbing -------------------------------------------------
    #
    # The root span protocol: submit() mints a trace_id (unless the
    # fleet minted one and passed it down) and opens the async
    # "request" span on it; _trace_end closes it exactly where the
    # request's future resolves — completion loop, isolation retry,
    # timeout/fastfail drain, shed, eviction, or fatal drain. The
    # drill's invariant (`open_flows() == []` once all futures
    # resolve) holds because every resolution site calls _trace_end.

    def _trace_end(self, req, status: str) -> None:
        """Close ``req``'s root span with a terminal status."""
        tr = self._tracer
        if tr is not None and req.trace is not None:
            tr.end_async("request", req.trace, args={"status": status})

    # -- lifecycle ------------------------------------------------------

    def start(self, warmup: bool = True) -> "ServingEngine":
        if self._started:
            raise RuntimeError("engine already started")
        if warmup and (self.config.buckets or self.config.warm_buckets):
            self.warmup()
        self._router = threading.Thread(
            target=self._route_loop, name="serving-route", daemon=True)
        self._started = True
        self._router.start()
        return self

    def warmup(self, buckets: Optional[Tuple[Tuple[int, int], ...]] = None
               ) -> Dict[Tuple[int, int], Dict[str, float]]:
        """Pre-compile the (max_batch, padded H, padded W) executable for
        every configured bucket through the exact serve-path code
        (``dispatch_batch`` → ``FlowPredictor._fn`` cache). After this,
        no request whose padded shape lands in a configured bucket
        triggers a fresh XLA compile. Returns per-bucket
        ``{"compiles": n, "seconds": s}`` stats. ``buckets`` overrides
        the configured set (the fleet warms spare buckets through it —
        cache hits when the executable cache is shared).

        ``warm_buckets`` (configured-set runs only) each warm the
        session path's three executables — encode, cold refine, warm
        refine — through the exact stream-dispatch code, recorded under
        the ``(ph, pw, "session")`` key. With that done, mixed
        warm/cold stream traffic on those shapes runs at zero
        post-warmup compiles, the same contract as stateless buckets."""
        stats: Dict[Tuple, Dict[str, float]] = {}
        self._warming = True
        try:
            for raw_hw in (self.config.buckets
                           if buckets is None else buckets):
                padder = InputPadder((*raw_hw, 3),
                                     mode=self.config.pad_mode,
                                     factor=self.config.factor)
                ph, pw = padder.padded_shape
                # Two distinct host arrays: with donation on, aliasing
                # one device buffer into both donated args would be
                # rejected. Each bucket warms BOTH wire dtypes (uint8
                # requests batch against their own executable — see
                # wire_cast), recorded under the one existing stats
                # key, so mixed uint8/float32 traffic stays at zero
                # post-warmup compiles.
                z1 = np.zeros((self.config.max_batch, ph, pw, 3),
                              np.float32)
                z2 = np.zeros_like(z1)
                u1 = np.zeros((self.config.max_batch, ph, pw, 3),
                              np.uint8)
                u2 = np.zeros_like(u1)
                t0 = time.perf_counter()
                with CompileWatch() as w:
                    out = self.predictor.dispatch_batch(z1, z2)
                    np.asarray(out[1])        # sync: compile + one run
                    out = self.predictor.dispatch_batch(u1, u2)
                    np.asarray(out[1])
                stats[(ph, pw)] = {"compiles": float(w.compiles),
                                   "seconds": time.perf_counter() - t0}
                for lvl in self._iters_ladder:
                    # Every brownout ladder level gets its executable
                    # here — stepping the ladder under overload swaps
                    # batcher buckets, never compiles.
                    t0 = time.perf_counter()
                    with CompileWatch() as w:
                        out = self.predictor.dispatch_batch(
                            z1, z2, iters=lvl)
                        np.asarray(out[1])
                        out = self.predictor.dispatch_batch(
                            u1, u2, iters=lvl)
                        np.asarray(out[1])
                    stats[(ph, pw, lvl)] = {
                        "compiles": float(w.compiles),
                        "seconds": time.perf_counter() - t0}
                if self.contbatch is not None:
                    # The whole continuous step family for this shape:
                    # bootstrap + every pow2 admission width in both
                    # wire dtypes + chunk step + finalize. After this,
                    # mixed ladder/early-exit/wire traffic through the
                    # slot table runs at zero compiles.
                    t0 = time.perf_counter()
                    with CompileWatch() as w:
                        self.contbatch.warmup_bucket(ph, pw)
                    stats[(ph, pw, "cont")] = {
                        "compiles": float(w.compiles),
                        "seconds": time.perf_counter() - t0}
            for raw_hw in (self.config.warm_buckets
                           if buckets is None else ()):
                stats.update(self._warmup_session_bucket(raw_hw))
            for raw_hw in (self.config.sharded_buckets
                           if buckets is None else ()):
                # Sharded executables warm through the exact serve-path
                # entry (sharded_dispatch with the engine's serving
                # mesh) at the sharded batch size — after this, sharded
                # traffic on configured shapes is zero-compile like any
                # other bucket (including the lazy output crops, which
                # this same path compiles when a shape needs them).
                padder = InputPadder((*raw_hw, 3),
                                     mode=self.config.pad_mode,
                                     factor=self._sharded_factor)
                ph, pw = padder.padded_shape
                z1 = np.zeros((self.config.sharded_max_batch, ph, pw, 3),
                              np.float32)
                z2 = np.zeros_like(z1)
                u1 = np.zeros_like(z1, dtype=np.uint8)
                u2 = np.zeros_like(u1)
                t0 = time.perf_counter()
                with CompileWatch() as w:
                    # Sync BOTH outputs: a low_res response on an
                    # extra-padded sharded shape materializes the lazy
                    # flow_low crop, which compiles its own tiny slice
                    # executable — warm it here, not under load.
                    out = self.predictor.sharded_dispatch(
                        z1, z2, mesh=self._sharded_mesh)
                    np.asarray(out[1])
                    np.asarray(out[0])
                    out = self.predictor.sharded_dispatch(
                        u1, u2, mesh=self._sharded_mesh)
                    np.asarray(out[1])
                    np.asarray(out[0])
                stats[(ph, pw, "mesh")] = {
                    "compiles": float(w.compiles),
                    "seconds": time.perf_counter() - t0}
        finally:
            self._warming = False
        return stats

    def _warmup_session_bucket(self, raw_hw) -> Dict[Tuple, Dict]:
        """Pre-compile one stream bucket's encode / cold-refine /
        warm-refine executables through the real session dispatch
        entries (``encode_dispatch`` / ``refine_dispatch``)."""
        padder = InputPadder((*raw_hw, 3), mode=self.config.pad_mode,
                             factor=self.config.factor)
        ph, pw = padder.padded_shape
        mb = self.config.max_batch
        t0 = time.perf_counter()
        with CompileWatch() as w:
            # flow_init lives at the model's stride-8 feature
            # resolution (independent of the pad factor)
            init = np.zeros((mb, ph // 8, pw // 8, 2), np.float32)
            # Both wire dtypes: a stream whose frames arrive uint8 runs
            # the uint8 encode/refine executables end to end (fmaps are
            # float32 model outputs either way).
            for dt in (np.float32, np.uint8):
                z = np.zeros((mb, ph, pw, 3), dt)
                fm = np.asarray(self.predictor.encode_dispatch(z))
                # Distinct host copies per donated arg (fmap1 is
                # donated, fmap2 never — it's the cache handoff the
                # completion thread syncs).
                out = self.predictor.refine_dispatch(
                    np.zeros_like(z), fm.copy(), fm)
                np.asarray(out[1])
                out = self.predictor.refine_dispatch(
                    np.zeros_like(z), fm.copy(), fm, flow_init=init,
                    warm=True)
                np.asarray(out[1])
                for eff in self._warm_effs:
                    # Browned-out warm levels (min(warm_iters, ladder
                    # level), dedup'd) — warm pairs step the ladder at
                    # zero compiles too.
                    out = self.predictor.refine_dispatch(
                        np.zeros_like(z), fm.copy(), fm, flow_init=init,
                        warm=True, iters=eff)
                    np.asarray(out[1])
        return {(ph, pw, "session"): {
            "compiles": float(w.compiles),
            "seconds": time.perf_counter() - t0}}

    def close(self, timeout: Optional[float] = None) -> None:
        """Stop accepting requests, drain every queued/in-flight request
        to its future, join the worker threads."""
        if self._closed:
            return
        self._closed = True
        self.batcher.close()
        if self._started:
            # The router drains the batcher into the streams and then
            # sends each stream its shutdown sentinel (in its finally
            # block), so joining router-then-streams resolves every
            # queued and in-flight request before close() returns.
            self._router.join(timeout)
            with self._streams_lock:
                streams = list(self._streams.values())
            # Retired streams already got their sentinel; join them
            # too so every accepted request resolved before close()
            # returns. (_retired is only appended by the router
            # thread, which has exited by now.)
            for s in streams + self._retired:
                s.join(timeout)
            if self.contbatch is not None:
                # After the router exits every accepted continuous
                # request sits in a worker inbox or an occupied slot;
                # close() drains both to futures (0 dropped — the
                # kill-under-load contract).
                self.contbatch.close(timeout)
        if self.metrics_server is not None:
            self.metrics_server.shutdown()
            self.metrics_server = None

    def __enter__(self) -> "ServingEngine":
        if not self._started:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- health / hot swap ----------------------------------------------

    def health_state(self) -> str:
        """The engine's readiness state, one of
        :mod:`raft_tpu.serving.health`'s ``STARTING / WARMING / READY /
        DEGRADED / BROWNOUT / OPEN / CLOSED``. The single string a load
        balancer routes on: ``ready``, ``degraded`` and ``brownout``
        take traffic, everything else doesn't. Fault states win over
        BROWNOUT: a browned-out engine that also trips its breaker
        reports the fault."""
        if self._closed:
            return health_mod.CLOSED
        if self._warming:
            return health_mod.WARMING
        if not self._started:
            return health_mod.STARTING
        b = self.breaker.state
        if b == CircuitBreaker.OPEN:
            return health_mod.OPEN
        with self._state_lock:
            degraded = bool(self._degraded_reasons)
        if b == CircuitBreaker.HALF_OPEN or degraded:
            return health_mod.DEGRADED
        if self.brownout is not None and self.brownout.level > 0:
            return health_mod.BROWNOUT
        return health_mod.READY

    def health(self) -> Dict[str, object]:
        """Readiness probe payload: the state string plus the numbers
        an operator wants next to it (breaker state/trips/failure
        streak, degradation reasons, queue depth, in-flight batches,
        swap/rollback totals)."""
        state = self.health_state()
        with self._state_lock:
            reasons = sorted(self._degraded_reasons)
        return {
            "state": state,
            "ready": health_mod.is_routable(state),
            "brownout": (self.brownout.stats()
                         if self.brownout is not None else None),
            "breaker": self.breaker.state,
            "breaker_trips": self.breaker.trips,
            "consecutive_failures": self.breaker.consecutive_failures,
            "degraded_reasons": reasons,
            "queue_depth": self.batcher.pending(),
            "inflight_batches": self._inflight_batches,
            "swaps": self.metrics.swaps,
            "rollbacks": self.metrics.rollbacks,
        }

    def set_degraded(self, reason: str) -> None:
        """Flag a non-breaker degradation (e.g. the hot reloader pinned
        the current model after a canary rollback). The engine keeps
        serving; ``health()`` reports ``degraded`` until cleared."""
        with self._state_lock:
            self._degraded_reasons.add(reason)

    def clear_degraded(self, reason: str) -> None:
        with self._state_lock:
            self._degraded_reasons.discard(reason)

    def swap_predictor(self, new_predictor) -> None:
        """Atomically swap the serving model between batches.

        Every bucket stream reads the ``self.predictor`` *reference*
        under the swap lock before dispatching, so each batch runs
        entirely on one model — batches dispatched before the swap
        captured the old weights and complete normally; the next batch
        per stream runs the new model. No request is dropped or torn
        across models. This is the commit point of
        :class:`~raft_tpu.serving.reload.HotReloader`; counted in
        ``metrics.swaps`` and clears any ``canary-rollback``
        degradation from a previously pinned bad checkpoint."""
        self._install_predictor(new_predictor)
        self.metrics.record_swap()
        self.clear_degraded("canary-rollback")

    def _install_predictor(self, new_predictor) -> None:
        """Install a predictor without counting a swap or touching the
        degradation flags — the fleet's rollback-restore and chaos-kill
        paths, where a ``swaps`` tick would corrupt the 'exactly one
        canary swap' accounting the drills assert on."""
        try:
            new_predictor.donate_images = self._donate
        except AttributeError:
            pass                    # chaos stubs need not carry the flag
        with self._swap_lock:
            self.predictor = new_predictor

    def record_rollback(self, reason: str) -> None:
        """A canary-failed reload was rolled back: count it and mark
        the engine degraded (serving safely, but refusing a newer
        committed checkpoint — an operator signal, not an outage)."""
        self.metrics.record_rollback()
        self.set_degraded("canary-rollback")

    # -- client API -----------------------------------------------------

    # -- spatially-sharded (high-resolution) routing ---------------------

    @property
    def hosts_sharded(self) -> bool:
        """Whether this engine holds a serving mesh — the fleet's
        capacity gate: sharded buckets route only to replicas whose
        device set can host the mesh."""
        return self._sharded_mesh is not None

    def _bucket_max(self, bucket) -> int:
        """Per-bucket dispatch size (the batcher's ``max_batch_for``):
        sharded buckets run at ``sharded_max_batch``, everything else
        at the global ``max_batch``. (Wire-dtype tags don't change the
        dispatch size — strip before matching.)"""
        bucket = _base_of(bucket)
        if len(bucket) == 3 and bucket[2] == "mesh":
            return self.config.sharded_max_batch
        return self.config.max_batch

    def sharded_route(self, raw_shape) -> Optional[Tuple]:
        """The sharded-vs-batched routing decision for one raw request
        shape: returns the ``(ph, pw, "mesh")`` bucket the request
        would serve under (padded at ``sharded_shards * factor``), or
        ``None`` for the ordinary batched path.

        Raw shapes listed in ``sharded_buckets`` always route sharded.
        Shapes explicitly configured as batched (``buckets`` /
        ``warm_buckets``) always keep their batched path — even above
        the area threshold, and even when the coarser sharded pad
        factor would land them on a sharded bucket's padded shape.
        Everything else routes sharded when its raw pixel area reaches
        ``sharded_area_threshold``. Shared with the fleet so
        engine-level and fleet-level bucket keys (and the
        ``"HxW@mesh"`` rendezvous digests) agree."""
        if self._sharded_mesh is None:
            return None
        h, w = int(raw_shape[0]), int(raw_shape[1])
        sharded = (h, w) in self._sharded_raw
        if not sharded:
            thr = self.config.sharded_area_threshold
            sharded = (bool(thr) and h * w >= thr
                       and (h, w) not in self._batched_raw)
        if not sharded:
            return None
        padded = InputPadder((h, w, 3), mode=self.config.pad_mode,
                             factor=self._sharded_factor).padded_shape
        return (*padded, "mesh")

    def submit(self, image1: np.ndarray, image2: np.ndarray,
               priority: str = PRIORITY_HIGH,
               iters: Optional[int] = None,
               low_res: bool = False,
               trace_id: Optional[int] = None,
               deadline_s: Optional[float] = None):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        resolving to the unpadded ``(H, W, 2)`` flow (float32 numpy).
        ``image1``/``image2``: (H, W, 3) arrays in [0, 255], any
        resolution (padded here, in the caller's thread). uint8 input —
        or float/int input whose values are integral and in range,
        auto-detected here once — serves over the uint8 wire format:
        staged, stacked and H2D-transferred at 1 byte/channel (4x fewer
        host-path bytes) with bit-identical flow (normalization happens
        in-model; see ``wire_cast``).
        ``priority``: ``"high"`` (default — batches first) or ``"low"``
        (background class: batched after HIGH, first shed under a full
        backlog). ``iters``: explicit GRU iteration count — must be the
        predictor's full count or a configured ``iters_ladder`` level
        (anything else raises ``ValueError`` naming the warmed levels;
        an unwarmed count would silently compile under load). ``None``
        (default) serves full quality, except LOW requests on
        configured buckets while the brownout controller holds a
        degraded level. ``low_res=True`` resolves the future to the
        1/8-scale flow on the PADDED grid instead — ``(ph/8, pw/8, 2)``
        float32, 64x fewer D2H/response bytes; the request's padder is
        stamped on the future (``future.padder``) so callers can
        recover full resolution host-side via :func:`upsample_flow`
        (documented as NOT bit-equal to the in-graph convex
        upsampling). ``trace_id``: a pre-minted id for the request's
        trace track — passed by the fleet so an engine attempt's
        ``request`` span lands on the same Perfetto lane as the fleet's
        outer ``fleet_request`` span; clients leave it ``None``
        (ignored when tracing is disabled). ``deadline_s``: an absolute
        ``time.monotonic()`` deadline carried in from an upstream hop
        (the network gateway propagates the client's budget this way);
        the request's queue deadline becomes the EARLIER of this and
        the config-derived ``queue_timeout_ms`` one, so a request whose
        budget was mostly spent upstream expires here instead of
        serving a too-late answer. Thread-safe.
        """
        if iters is not None:
            iters = int(iters)
            if iters not in self._iters_levels:
                levels = sorted(self._iters_levels, reverse=True)
                raise ValueError(
                    f"iters={iters} is not a warmed quality level on "
                    f"this engine; configured levels are {levels} "
                    f"(full quality {self._full_iters}"
                    + (f" plus ladder {list(self._iters_ladder)}"
                       if self._iters_ladder else
                       "; no iters_ladder configured") + ")")
        self._check_accepting()
        if image1.shape != image2.shape:
            raise ValueError(f"frame shapes differ: {image1.shape} vs "
                             f"{image2.shape}")
        sharded_bucket = self.sharded_route(image1.shape)
        if sharded_bucket is not None:
            if iters is not None and iters != self._full_iters:
                raise ValueError(
                    f"per-request iters={iters} is not supported on the "
                    "spatially-sharded serving path (degraded-quality "
                    "sharded buckets would need their own warmed "
                    "executables) — sharded requests always serve full "
                    "quality")
            return self._submit_sharded(image1, image2, priority,
                                        sharded_bucket, low_res=low_res,
                                        trace_id=trace_id,
                                        deadline_s=deadline_s)
        # Root span: opened here (all validation raises are behind us,
        # so every opened span has a future that will resolve), closed
        # by _trace_end wherever that future resolves. With tracing
        # off, `tr is None` and the request carries no id at all.
        tr = self._tracer
        rid = None
        if tr is not None:
            rid = tr.mint() if trace_id is None else trace_id
            tr.begin_async("request", rid,
                           args={"priority": priority, "iters": iters,
                                 "shape": list(map(int, image1.shape)),
                                 "low_res": low_res})
        with self.stages.stage("pad"), \
                (tr.span("pad", trace_id=rid) if tr is not None
                 else _NULL):
            wire, image1, image2 = request_wire(image1, image2)
            padder = InputPadder(image1.shape, mode=self.config.pad_mode,
                                 factor=self.config.factor)
            im1, im2 = padder.pad(image1, image2)
        padded = padder.padded_shape
        bucket_iters = None
        degradable = False
        if iters is not None and iters != self._full_iters:
            # Explicit client choice: honored for either priority
            # class, never re-bucketed by the controller.
            bucket_iters = iters
        elif (iters is None and priority == PRIORITY_LOW
              and self.brownout is not None
              and padded in self._stateless_padded):
            # Controller-managed traffic: serve at the current ladder
            # level, and mark the request so level changes re-bucket it
            # while it still waits in the queue.
            degradable = True
            lvl = self.brownout.level
            if lvl:
                bucket_iters = self._iters_ladder[lvl - 1]
        req_iters = None
        if self.contbatch is not None and padded in self._stateless_padded:
            # Continuous path: quality is per-request state, not a
            # bucket key — every iters level and both wire dtypes share
            # the one (ph, pw, "cont") bucket and its slot table (the
            # scheduler groups admissions by dtype). The bucket key is
            # wire-untagged by design: the ONE exception to the
            # wire-tag-last convention, because the executable family
            # it routes to is carry-resident and dtype-agnostic past
            # admission.
            bucket = (*padded, "cont")
            req_iters = (bucket_iters if bucket_iters is not None
                         else (iters or self._full_iters))
        else:
            bucket = ((*padded, wire) if bucket_iters is None
                      else (*padded, bucket_iters, wire))
        t_submit = time.monotonic()
        timeout = self.config.queue_timeout_ms
        deadline = (t_submit + timeout / 1e3) if timeout else None
        if deadline_s is not None:
            deadline = (deadline_s if deadline is None
                        else min(deadline, deadline_s))
        with self._state_lock:
            self._submit_seq += 1
            seq = self._submit_seq
        req = QueuedRequest(im1, im2, padder, bucket=bucket,
                            t_submit=t_submit, deadline=deadline,
                            priority=priority,
                            poisoned=active_injector()
                            .poisons_request(seq),
                            degradable=degradable,
                            low_res=low_res, trace=rid,
                            iters=req_iters)
        if low_res:
            # Pad geometry for host-side upsample_flow recovery.
            req.future.padder = padder
        return self._enqueue_request(req)

    def _submit_sharded(self, image1, image2, priority,
                        bucket, low_res: bool = False,
                        trace_id: Optional[int] = None,
                        deadline_s: Optional[float] = None) -> "Future":
        """Enqueue one request onto its ``(ph, pw, "mesh", wire)``
        sharded bucket: padded at the sharded factor (rows always
        divide the spatial axis), never brownout-degradable (the
        sharded path serves full quality only), dispatched through the
        bucket's own permanent stream at ``sharded_max_batch``.
        ``bucket`` arrives wire-untagged from :meth:`sharded_route`
        (the fleet shares that routing and stays dtype-agnostic); the
        tag is appended here."""
        tr = self._tracer
        rid = None
        if tr is not None:
            rid = tr.mint() if trace_id is None else trace_id
            tr.begin_async("request", rid,
                           args={"priority": priority, "sharded": True,
                                 "shape": list(map(int, image1.shape)),
                                 "low_res": low_res})
        with self.stages.stage("pad"), \
                (tr.span("pad", trace_id=rid) if tr is not None
                 else _NULL):
            wire, image1, image2 = request_wire(image1, image2)
            padder = InputPadder(image1.shape, mode=self.config.pad_mode,
                                 factor=self._sharded_factor)
            im1, im2 = padder.pad(image1, image2)
        t_submit = time.monotonic()
        timeout = self.config.queue_timeout_ms
        deadline = (t_submit + timeout / 1e3) if timeout else None
        if deadline_s is not None:
            deadline = (deadline_s if deadline is None
                        else min(deadline, deadline_s))
        with self._state_lock:
            self._submit_seq += 1
            seq = self._submit_seq
        req = QueuedRequest(im1, im2, padder, bucket=(*bucket, wire),
                            t_submit=t_submit, deadline=deadline,
                            priority=priority,
                            poisoned=active_injector()
                            .poisons_request(seq),
                            degradable=False,
                            low_res=low_res, trace=rid)
        if low_res:
            req.future.padder = padder
        self.metrics.record_sharded()
        return self._enqueue_request(req)

    def _check_accepting(self) -> None:
        """The submit-time admission gates, shared by the stateless and
        stream paths."""
        if not self._started:
            raise RuntimeError("engine not started (call start())")
        if self._closed:
            raise RuntimeError("engine is closed")
        if self._fatal is not None:
            raise RuntimeError(
                "serving engine hit a fatal dispatch error") \
                from self._fatal
        if not self.breaker.admits():
            # Fail fast: the device path is failing consistently;
            # queueing would only delay the same failure.
            self.metrics.record_breaker_fastfail()
            self.metrics.record_reject()
            raise EngineUnhealthy(
                f"circuit breaker open after "
                f"{self.breaker.consecutive_failures} consecutive "
                f"dispatch failures; retrying after "
                f"{self.config.breaker_cooldown_s:.1f}s cooldown")

    def _enqueue_request(self, req: QueuedRequest):
        """Stamp, enqueue, and account one built request; returns its
        future (shared tail of the stateless and stream submit paths)."""
        if self.config.replica_id is not None:
            # Response attribution inside a fleet: loadgen and the
            # fleet drills read this off the future to name the engine
            # that produced (or failed) each response.
            req.future.replica_id = self.config.replica_id
        try:
            evicted = self.batcher.enqueue(req)
        except BacklogFull:
            # Shed counted on top of the rejection: the shed rate is
            # the capacity signal, the reject total the error rate.
            self.metrics.record_shed(req.priority)
            self.metrics.record_reject()
            self._trace_end(req, "shed")
            raise
        except RuntimeError:
            self.metrics.record_reject()
            self._trace_end(req, "rejected")
            raise
        if evicted is not None:
            # A queued LOW request was shed to admit this HIGH one; its
            # client gets the same BacklogFull it would have gotten at
            # submit time, just later.
            evicted.future.set_exception(BacklogFull(
                "shed from the backlog by a higher-priority request"))
            self.metrics.record_shed(evicted.priority)
            self.metrics.record_reject()
            self._trace_end(evicted, "evicted")
        self.metrics.record_submit(self.batcher.pending(),
                                   priority=req.priority)
        return req.future

    # -- streaming (session) API ----------------------------------------

    def open_stream(self, stream_id: Optional[str] = None):
        """Open a :class:`~raft_tpu.serving.session.StreamSession`
        against this engine — the stateful per-stream API: feed frames
        one at a time, the session carries the previous flow (warm
        start) and the previous frame's feature map (encoder cache)
        between them. Cheap: no resources are held until the first
        frame arrives."""
        from raft_tpu.serving.session import StreamSession
        if stream_id is None:
            with self._state_lock:
                self._stream_seq += 1
                stream_id = f"stream-{self._stream_seq}"
        return StreamSession(self, stream_id)

    def _prime_encode(self, padded_frame: np.ndarray) -> np.ndarray:
        """Standalone encode of one padded frame (session prime /
        re-prime): tail-pad to the bucket's ``max_batch`` so it reuses
        the SAME encode executable the stream batches run — a prime
        never compiles on a warmed bucket. Synchronous, in the client
        thread (like padding, host prep rides the producers). Returns
        the ``(1, H/8, W/8, C)`` host feature map."""
        self._check_accepting()
        tr = self._tracer
        with (tr.span("prime_encode",
                      args={"shape": list(map(int, padded_frame.shape))})
              if tr is not None else _NULL):
            stack = np.repeat(padded_frame[None],
                              self.config.max_batch, 0)
            with self._swap_lock:
                predictor = self.predictor
            c0 = xla_compile_count()
            fmap = predictor.encode_dispatch(stack)
            out = np.asarray(fmap)[:1].copy()
        self.metrics.record_encoder_cache(hit=False)
        compiles = xla_compile_count() - c0
        if compiles:
            self.metrics.record_batch(1, 1, compiles=compiles)
        return out

    def _submit_stream(self, session, image1, image2, padder, fmap1,
                       flow_init, priority: str = PRIORITY_HIGH):
        """Enqueue one stream pair (called by ``StreamSession.submit``
        with already-padded frames and the cached fmap1). Warm pairs
        (``flow_init`` given) and cold pairs batch in separate
        ``(ph, pw, "warm"/"cold")`` buckets — distinct executables,
        distinct iteration counts — alongside, never inside, stateless
        traffic. Under brownout, LOW *warm* pairs on configured warm
        buckets step down the ladder too — capped at the base warm
        count (``min(warm_iters, level)``), bucketed as ``(ph, pw,
        "warm", eff)``. Cold/prime pairs keep the cold policy: they
        seed the stream's state, and a degraded seed would poison
        every warm frame after it."""
        self._check_accepting()
        warm = flow_init is not None
        padded = padder.padded_shape
        # The pair's wire dtype: frames were wire-cast per frame by
        # StreamSession.submit (the O(N) check runs once per frame,
        # not once per pair), so only the dtype pairing is decided
        # here — uint8 when BOTH padded frames are uint8; a mixed
        # u8/f32 consecutive pair widens to float32 exactly, so the
        # executable always sees one dtype.
        if image1.dtype == np.uint8 and image2.dtype == np.uint8:
            wire = WIRE_U8
        else:
            wire = WIRE_F32
            image1 = np.asarray(image1, np.float32)
            image2 = np.asarray(image2, np.float32)
        bucket = (*padded, "warm" if warm else "cold", wire)
        degradable = False
        if (warm and priority == PRIORITY_LOW
                and self.brownout is not None
                and padded in self._warm_padded):
            degradable = True
            lvl = self.brownout.level
            if lvl:
                eff = min(self._base_warm_iters,
                          self._iters_ladder[lvl - 1])
                if eff != self._base_warm_iters:
                    bucket = (*padded, "warm", eff, wire)
        t_submit = time.monotonic()
        timeout = self.config.queue_timeout_ms
        deadline = (t_submit + timeout / 1e3) if timeout else None
        with self._state_lock:
            self._submit_seq += 1
            seq = self._submit_seq
        tr = self._tracer
        rid = None
        if tr is not None:
            rid = tr.mint()
            tr.begin_async("request", rid,
                           args={"priority": priority,
                                 "stream": session.stream_id,
                                 "warm": warm})
            # Warm starts are the streaming path's whole trick — make
            # each one legible on the request lane.
            tr.async_instant("warm_start" if warm else "cold_start",
                             rid, args={"stream": session.stream_id})
        req = QueuedRequest(
            image1, image2, padder, bucket=bucket,
            t_submit=t_submit, deadline=deadline, priority=priority,
            poisoned=active_injector().poisons_request(seq),
            session=session, flow_init=flow_init, fmap1=fmap1,
            degradable=degradable, trace=rid)
        fut = self._enqueue_request(req)
        self.metrics.record_stream_submit(warm)
        self.metrics.record_encoder_cache(hit=True)
        return fut

    def predict(self, image1: np.ndarray, image2: np.ndarray,
                timeout: Optional[float] = 120.0) -> np.ndarray:
        """Synchronous convenience wrapper over :meth:`submit`."""
        return self.submit(image1, image2).result(timeout)

    # -- worker threads -------------------------------------------------

    def _set_fatal(self, e: BaseException) -> None:
        """An unexpected (non-Exception) error escaped a worker thread:
        record it so submit fails fast, and stop accepting requests."""
        self._fatal = e
        self.batcher.close()

    def _stream_for(self, bucket: Tuple[int, int]) -> _BucketStream:
        # Router-thread only: creation is single-threaded, the lock
        # orders the dict writes against concurrent readers.
        stream = self._streams.get(bucket)
        if stream is None:
            if bucket not in self._dedicated_buckets:
                self._retire_idle_streams()
            stream = _BucketStream(self, bucket)
            with self._streams_lock:
                self._streams[bucket] = stream
        stream.last_used = time.monotonic()
        return stream

    def _retire_idle_streams(self) -> None:
        """Make room for one more dynamic stream under the
        ``max_dynamic_streams`` cap: close the least-recently-used
        streams of non-configured buckets (their ``None`` sentinel
        drains queued and in-flight work before the threads exit — no
        request is dropped) and move them to ``_retired`` for the
        final join at close. Dedicated (configured-bucket) streams are
        never retired."""
        cap = max(1, self.config.max_dynamic_streams)
        dynamic = [(b, s) for b, s in self._streams.items()
                   if b not in self._dedicated_buckets]
        overflow = len(dynamic) - (cap - 1)
        if overflow <= 0:
            return
        dynamic.sort(key=lambda item: item[1].last_used)
        for b, s in dynamic[:overflow]:
            s.close()
            self._retired.append(s)
            with self._streams_lock:
                del self._streams[b]

    def _route_loop(self) -> None:
        """Pull closed batches off the batcher and hand each to its
        bucket's stream. Routing never touches the device, so one
        bucket's backpressure (a full ``inflight`` queue) stalls only
        that bucket's dispatch thread, never this loop."""
        try:
            while True:
                batch = self.batcher.next_batch(timeout=0.1)
                if batch is None:
                    break
                # next_batch returns [] at least every 0.1 s even when
                # idle, so the controller is sampled continuously —
                # including while the backlog drains with no new
                # arrivals (the step-back-up path).
                self._brownout_tick()
                if not batch:
                    continue
                if batch[0].bucket[-1] == "cont":
                    # Continuous bucket: the batcher still closed the
                    # batch (deadline/size), but it joins a standing
                    # slot table instead of a monolithic dispatch.
                    self.contbatch.put(batch)
                    continue
                self._stream_for(batch[0].bucket).put(batch)
        except BaseException as e:  # fatal: fail fast, not silently
            self._set_fatal(e)
            while True:
                left = self.batcher.next_batch(timeout=0)
                if not left:
                    break
                for r in left:
                    r.future.set_exception(e)
                    self._trace_end(r, "fatal")
                self.metrics.record_error(len(left))
        finally:
            with self._streams_lock:
                streams = list(self._streams.values())
            for stream in streams:
                stream.close()

    def _brownout_tick(self) -> None:
        """Feed the controller one pressure sample (router thread);
        apply a level change by re-bucketing queued degradable LOW
        requests so already-waiting work degrades (or recovers) too,
        with its original deadlines intact."""
        ctl = self.brownout
        if ctl is None:
            return
        with self._state_lock:
            inflight = self._inflight_batches
        pressure = self.batcher.pending() + inflight
        if self.contbatch is not None:
            # Work the batcher no longer sees but the device still
            # owes: occupied slots + admissions queued at the workers.
            pressure += self.contbatch.load()
        old, new = ctl.observe(pressure)
        if new != old:
            tr = self._tracer
            on_move = None
            if tr is not None:
                tr.complete("brownout_level_change", 0.0,
                            args={"from": old, "to": new},
                            cat="brownout")

                def on_move(req, new_key, _tr=tr, _new=new):
                    if req.trace is not None:
                        _tr.async_instant(
                            "rebucket", req.trace,
                            args={"level": _new,
                                  "bucket": repr(new_key)})
            self.batcher.rebucket_low(self._brownout_bucket_for,
                                      on_move=on_move)
            if self.contbatch is not None:
                # In-flight slots re-target their remaining budgets in
                # place — free host arithmetic, no re-bucketing, no
                # per-rung executables. Queued continuous requests need
                # nothing: the worker re-reads the level for degradable
                # traffic at admission.
                target = (self._full_iters if new == 0
                          else self._iters_ladder[new - 1])
                self.contbatch.retarget(target)

    def _brownout_bucket_for(self, req: QueuedRequest):
        """Rebucket mapper: the bucket a queued controller-managed LOW
        request belongs in at the CURRENT ladder level (``None`` =
        leave it alone). Explicit ``submit(iters=...)`` requests are
        never marked degradable, so a client's chosen level is honored
        even while its request waits in a bucket the ladder also
        uses."""
        if not req.degradable:
            return None
        if req.bucket[-1] == "cont":
            # Continuous requests never re-bucket: quality is
            # per-request state, applied by the slot worker at
            # admission from the then-current level.
            return None
        lvl = self.brownout.level
        base = req.bucket[:2]
        wire = _wire_of(req.bucket)   # quality steps keep the wire dtype
        if req.session is not None:          # warm stream pair
            eff = (self._base_warm_iters if lvl == 0
                   else min(self._base_warm_iters,
                            self._iters_ladder[lvl - 1]))
            return ((*base, "warm", wire)
                    if eff == self._base_warm_iters
                    else (*base, "warm", eff, wire))
        return ((*base, wire) if lvl == 0
                else (*base, self._iters_ladder[lvl - 1], wire))

    def _bucket_iters(self, bucket: Tuple) -> int:
        """GRU iteration count the executable serving ``bucket`` runs —
        the served-quality level the metrics histogram records. The
        wire tag is quality-neutral: strip it before matching."""
        bucket = _base_of(bucket)
        if len(bucket) == 4:                          # (ph, pw, "warm", eff)
            return int(bucket[3])
        if len(bucket) == 3:
            if isinstance(bucket[2], int):            # (ph, pw, iters)
                return int(bucket[2])
            if bucket[2] == "warm":
                return self._base_warm_iters
        return self._full_iters                       # stateless / cold

    def _stack(self, batch: List[QueuedRequest]):
        n = len(batch)
        cap = self._bucket_max(batch[0].bucket)
        r0 = batch[0]
        shape = (cap, *r0.image1.shape)
        # Staging arena: preallocated per-(shape, dtype) host buffers —
        # each request's frames are written ONCE directly into their
        # batch slot (single memcpy; the old np.stack + np.concatenate
        # pad-then-stack allocated and copied every batch). Recycled by
        # the completion thread after the batch's outputs sync. In the
        # uint8 wire format the buffer itself is 4x smaller.
        i1 = self.arena.acquire(shape, r0.image1.dtype)
        i2 = self.arena.acquire(shape, r0.image1.dtype)
        tr = self._tracer
        with self.stages.stage("stack", nbytes=i1.nbytes + i2.nbytes), \
                (tr.span("stack", args={"n": n, "bucket":
                                        repr(r0.bucket)})
                 if tr is not None else _NULL):
            for j, r in enumerate(batch):
                i1[j] = r.image1
                i2[j] = r.image2
            if n < cap:
                # Tail-pad by repeating the last request — same rule as
                # batched eval; one executable per bucket (at the
                # bucket's own dispatch size — sharded buckets run at
                # sharded_max_batch), never one per partial size.
                i1[n:] = i1[n - 1]
                i2[n:] = i2[n - 1]
        self.metrics.record_staged_bytes(i1.nbytes + i2.nbytes)
        return i1, i2

    def _dispatch_arrays(self, batch: List[QueuedRequest], i1, i2):
        """The guarded device entry: fault-injection hooks (a poisoned
        request in the batch, or an injected transient dispatch error)
        fire before the device is touched. The predictor *reference* is
        read under the swap lock (so a hot reload lands between
        batches, never tearing one), but the dispatch itself runs
        outside it — bucket streams must be able to dispatch
        concurrently without serializing on the lock."""
        inj = active_injector()
        if any(r.poisoned for r in batch):
            raise RuntimeError(
                "injected poisoned input in dispatched batch")
        inj.maybe_fail_serving_dispatch()
        with self._swap_lock:
            predictor = self.predictor
        bucket = _base_of(batch[0].bucket)
        if len(bucket) == 3 and bucket[2] == "mesh":
            # Spatially-sharded bucket: rows over the serving mesh's
            # spatial axis through the predictor's ("sharded", ...)
            # executable family — the same cache the batched buckets
            # use, so one predictor (and its hot-reload clones) serves
            # both paths.
            return predictor.sharded_dispatch(
                i1, i2, mesh=self._sharded_mesh)
        if len(bucket) == 3 and isinstance(bucket[2], int):
            # Degraded-quality (or explicit-iters) bucket: its own
            # pre-warmed executable at that iteration count.
            return predictor.dispatch_batch(i1, i2, iters=bucket[2])
        return predictor.dispatch_batch(i1, i2)

    def _dispatch_stream_arrays(self, batch: List[QueuedRequest]):
        """Stack and dispatch one stream (session) batch: ONE encoder
        pass over the new frames, cached fmap1s re-fed from the
        sessions' host caches, then the warm or cold refine executable.
        Returns ``((flow_low, flow_up, fmap2), staged)`` — fmap2 rides
        along so the completion thread can hand each slice back to its
        session as the next pair's fmap1, and ``staged`` is the tuple
        of arena buffers to release once the outputs sync. Same
        fault-injection and swap-lock contract as ``_dispatch_arrays``;
        numpy-only host prep (eager ``jnp`` stacking would compile tiny
        executables and break the zero-compile contract)."""
        n = len(batch)
        mb = self.config.max_batch
        warm = batch[0].flow_init is not None
        r0 = batch[0]
        i1 = self.arena.acquire((mb, *r0.image1.shape), r0.image1.dtype)
        i2 = self.arena.acquire((mb, *r0.image1.shape), r0.image1.dtype)
        fm1 = self.arena.acquire((mb, *r0.fmap1.shape[1:]),
                                 r0.fmap1.dtype)
        finit = (self.arena.acquire((mb, *r0.flow_init.shape),
                                    r0.flow_init.dtype)
                 if warm else None)
        staged = (i1, i2, fm1, finit)
        nbytes = sum(b.nbytes for b in staged if b is not None)
        with self.stages.stage("stack", nbytes=nbytes):
            for j, r in enumerate(batch):
                i1[j] = r.image1
                i2[j] = r.image2
                fm1[j] = r.fmap1[0]
                if warm:
                    finit[j] = r.flow_init
            if n < mb:
                i1[n:] = i1[n - 1]
                i2[n:] = i2[n - 1]
                fm1[n:] = fm1[n - 1]
                if warm:
                    finit[n:] = finit[n - 1]
        self.metrics.record_staged_bytes(nbytes)
        inj = active_injector()
        if any(r.poisoned for r in batch):
            raise RuntimeError(
                "injected poisoned input in dispatched batch")
        inj.maybe_fail_serving_dispatch()
        with self._swap_lock:
            predictor = self.predictor
        fmap2 = predictor.encode_dispatch(i2)
        bucket = _base_of(batch[0].bucket)
        # (ph, pw, "warm", eff): browned-out warm pairs refine at the
        # capped ladder level instead of the base warm count.
        iters = bucket[3] if len(bucket) == 4 else None
        flow_low, flow_up = predictor.refine_dispatch(
            i1, fm1, fmap2, flow_init=finit, warm=warm, iters=iters)
        return (flow_low, flow_up, fmap2), staged

    def _dispatch_one(self, batch: List[QueuedRequest],
                      inflight: queue.Queue) -> None:
        # Expire requests whose time-in-queue budget ran out while they
        # waited for a batch slot: complete them with a clear error and
        # don't spend device compute on them.
        now = time.monotonic()
        expired = [r for r in batch if r.expired(now)]
        if expired:
            for r in expired:
                r.future.set_exception(RequestTimedOut(
                    f"request spent {(now - r.t_submit) * 1e3:.1f} ms "
                    f"in queue (queue_timeout_ms="
                    f"{self.config.queue_timeout_ms})"))
                self._trace_end(r, "timeout")
            self.metrics.record_timeout(len(expired))
            batch = [r for r in batch if not r.expired(now)]
            if not batch:
                return
        if not self.breaker.admits():
            # OPEN mid-cooldown: this batch was queued before the trip
            # (or raced it). Fail it fast rather than feeding a failing
            # device — the same contract submit gives new requests.
            exc = EngineUnhealthy(
                "circuit breaker open; request drained without dispatch")
            for r in batch:
                r.future.set_exception(exc)
                self._trace_end(r, "fastfail")
            self.metrics.record_breaker_fastfail(len(batch))
            self.metrics.record_error(len(batch))
            return
        n = len(batch)
        tr = self._tracer
        if tr is not None:
            # Queue-wait rendered retroactively, one slice per request
            # ending now: t_submit and the tracer share a monotonic
            # timebase, so the duration is exact even though the start
            # predates the slice's recording.
            t_q = time.monotonic()
            for r in batch:
                tr.complete("queue", t_q - r.t_submit, trace_id=r.trace,
                            args={"priority": r.priority})
        c0 = xla_compile_count()
        try:
            with self.stages.stage("dispatch"), \
                    (tr.span("dispatch",
                             args={"n": n,
                                   "bucket": repr(batch[0].bucket)})
                     if tr is not None else _NULL):
                # Non-blocking: device_put + async dispatch. The device
                # computes while this thread loops back to stack the
                # next batch.
                if batch[0].session is not None:
                    out, staged = self._dispatch_stream_arrays(batch)
                else:
                    i1, i2 = self._stack(batch)
                    out = self._dispatch_arrays(batch, i1, i2)
                    staged = (i1, i2)
        except Exception as e:
            self.breaker.record_failure()
            self._isolate_failed_batch(batch, e)
            return
        self.metrics.record_batch(n, self._bucket_max(batch[0].bucket),
                                  compiles=xla_compile_count() - c0)
        # Bounded per-bucket queue: blocks when pipeline_depth batches
        # of THIS bucket are already in flight — backpressure instead
        # of unbounded device queueing, without stalling other buckets.
        # The staging buffers ride along; the completion thread
        # releases them only after the outputs sync.
        with self._state_lock:
            self._inflight_batches += 1
        inflight.put((batch, out, staged))

    def _isolate_failed_batch(self, batch: List[QueuedRequest],
                              cause: BaseException) -> None:
        """Batch error isolation: a failed batch (dispatch or sync) is
        retried once as full-padded singles, so one poisoned input (or
        a value-dependent device error) fails alone instead of failing
        every co-batched neighbor. Singles reuse the bucket's
        ``max_batch`` executable (self-tail-padded), so isolation never
        compiles. A lone request has no neighbors to save — it just
        fails with the original error."""
        if len(batch) <= 1:
            for r in batch:
                r.future.set_exception(cause)
                self._trace_end(r, "error")
            self.metrics.record_error(len(batch))
            return
        tr = self._tracer
        for r in batch:
            is_stream = r.session is not None
            if tr is not None and r.trace is not None:
                tr.async_instant("retry_single", r.trace,
                                 args={"cause": type(cause).__name__})
            try:
                if is_stream:
                    out, staged = self._dispatch_stream_arrays([r])
                    with self.stages.stage("sync"):
                        flow_up = np.asarray(out[1])
                        flow_low = np.asarray(out[0])
                        fmap2 = np.asarray(out[2])
                else:
                    i1, i2 = self._stack([r])
                    out = self._dispatch_arrays([r], i1, i2)
                    staged = (i1, i2)
                    with self.stages.stage("sync"):
                        flow_up = np.asarray(out[1])
                        flow_low = (np.asarray(out[0]) if r.low_res
                                    else None)
            except Exception as e:
                # A failed stream pair drops its session state: the
                # fmap/flow handoff was consumed at submit, so the next
                # submit on that session re-primes and restarts cold.
                # (Its staging buffers are dropped, not pooled.)
                r.future.set_exception(e)
                self._trace_end(r, "error")
                self.metrics.record_error(1)
                self.breaker.record_failure()
                continue
            self.arena.release(*staged)
            if is_stream:
                r.session._complete(fmap2[:1].copy(), flow_low[0].copy())
            served_iters = self._bucket_iters(r.bucket)
            if not is_stream and len(out) > 2:
                saved = max(served_iters - int(np.asarray(out[2])[0]), 0)
                if saved:
                    self.metrics.record_early_exit_saved(saved)
            self.metrics.record_quality(served_iters)
            result = (flow_low[0].copy() if r.low_res
                      else r.padder.unpad(flow_up[0]))
            self.metrics.record_returned_bytes(result.nbytes)
            r.future.set_result(result)
            self._trace_end(r, "ok")
            latency = time.monotonic() - r.t_submit
            self.metrics.record_done(latency)
            if self.slo is not None:
                self.slo.observe(r.priority, latency)
            self.metrics.record_isolated_retry()
            self.breaker.record_success()


def make_engine(model_path: str, serving: Optional[ServingConfig] = None,
                **predictor_kw) -> ServingEngine:
    """One-call constructor: ``load_predictor`` (torch ``.pth``, orbax
    dir, fixture ``.npz`` or ``"random"``) + engine. ``predictor_kw``
    forwards to :func:`raft_tpu.evaluate.load_predictor` (``small``,
    ``iters``, ``corr_impl``, ...)."""
    from raft_tpu.evaluate import load_predictor

    predictor = load_predictor(model_path, **predictor_kw)
    return ServingEngine(predictor, serving)
