"""Serving observability: latency percentiles, batch shape accounting,
queue depth, throughput, and an XLA compile-count probe.

The serving engine's contract ("after warmup no request triggers a fresh
compile", "the batcher recovers large-batch efficiency") is only
checkable if the numbers are first-class, so this module keeps them all
in one thread-safe place:

* :func:`xla_compile_count` / :class:`CompileWatch` — the process-wide
  backend-compile counter of :mod:`raft_tpu.utils.compile_count`
  (training and the dataset pass read it too), exported from here. The
  warmup routine uses it to prove the configured buckets compiled,
  tests use it to prove post-warmup requests didn't.
* :class:`ServingMetrics` — request/response counters (per priority
  class), a rolling latency window (p50/p95/p99), the batch-size
  histogram (how well the dynamic batcher is filling batches),
  padded-slot waste, queue-depth peak, wall-clock throughput, and the
  robustness-layer counters: model ``swaps`` / canary ``rollbacks``
  (hot reload), ``isolated_retries`` (batch error isolation singles),
  ``breaker_fastfails`` (requests rejected while the circuit breaker
  was open). Live *gauges* — current queue depth, in-flight batch
  count, health-state code, breaker trip count — are wired by the
  engine as callables (:meth:`ServingMetrics.set_gauge_source`) so
  every snapshot reads the instantaneous value. ``snapshot()`` returns
  a flat dict of floats shaped for :meth:`raft_tpu.utils.logger
  .TrainLogger.write_dict`, so serving metrics stream to the same
  JSONL/TensorBoard sinks as training scalars.
"""

from __future__ import annotations

import threading
import time
from collections import Counter, deque
from typing import Dict, Optional

from raft_tpu.utils.compile_count import (  # noqa: F401  exported here
    CompileWatch, compile_events, xla_compile_count)

# -- percentiles --------------------------------------------------------

def _percentile(sorted_vals, q: float) -> float:
    """Linear-interpolation percentile over an already-sorted list
    (numpy-free so the hot path never materializes arrays)."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (len(sorted_vals) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


class ServingMetrics:
    """Thread-safe counters for one :class:`~raft_tpu.serving.engine
    .ServingEngine`.

    Latencies are request submit → result-set wall times over a rolling
    window (default 10k — p99 over a bounded recent window, not the
    run's full history). The batch-size histogram counts *real* request
    counts per dispatched batch; ``padded_slots`` accumulates the
    tail-padding waste (slots computed but thrown away), so
    ``padded_slots / (sum(hist k*v) + padded_slots)`` is the compute
    overhead the deadline policy is paying for latency.
    """

    def __init__(self, latency_window: int = 10000):
        self._lock = threading.Lock()
        self._lat: deque = deque(maxlen=latency_window)
        self.batch_hist: Counter = Counter()
        self.requests = 0          # accepted submits
        self.requests_by_class = Counter()   # priority -> accepted
        self.rejected = 0          # backlog-full / closed rejections
        self.sheds = 0             # BacklogFull load-sheds specifically
        self.sheds_by_class = Counter()      # priority -> sheds
        self.responses = 0         # futures resolved with a result
        self.errors = 0            # futures resolved with an exception
        self.timeouts = 0          # futures resolved with RequestTimedOut
        self.batches = 0
        self.padded_slots = 0
        self.compiles = 0          # fresh XLA compiles on the serve path
        self.queue_depth_peak = 0
        self.swaps = 0             # hot checkpoint reloads served live
        self.rollbacks = 0         # canary-failed reloads rolled back
        self.isolated_retries = 0  # batch-failure singles that served
        self.breaker_fastfails = 0  # requests failed fast while OPEN
        # streaming (session) accounting: warm vs cold pair submits, and
        # the encoder feature-map cache — a hit is a pair whose fmap1
        # came from the previous frame's cached fmap2 (one encoder pass
        # instead of two), a miss is a session prime/re-prime encode.
        self.warm_requests = 0
        self.cold_stream_requests = 0
        self.encoder_hits = 0
        self.encoder_misses = 0
        # spatially-sharded (high-resolution) requests: submits routed
        # onto a (ph, pw, "mesh") bucket — rows split over the serving
        # mesh instead of batched. The multi-chip latency path's
        # traffic share in one counter.
        self.sharded_requests = 0
        # served-quality accounting (graceful brownout): how many
        # responses served at each GRU iteration count — the SLO story
        # in one histogram (full-quality level vs the ladder's degraded
        # levels) — and the total refine iterations the convergence
        # early exit skipped (per-sample iters_requested - iters_used,
        # summed over early-exit-enabled responses).
        self.quality_hist: Counter = Counter()
        self.early_exit_iters_saved = 0
        # continuous (iteration-granular) batching accounting: admits /
        # retires are slot-table membership changes, steps counts
        # step_dispatch launches, occupancy_sum accumulates occupied
        # slots per step (mean occupancy = sum / steps — the scheduler's
        # fill factor), and freed_iters is the budget the slot table
        # handed back by retiring samples the moment they converged or
        # hit their per-request iters (the wall-clock the monolithic
        # masked scan would have burned).
        self.contbatch_admits = 0
        self.contbatch_retires = 0
        self.contbatch_steps = 0
        self.contbatch_occupancy_sum = 0
        self.contbatch_freed_iters = 0
        self.contbatch_retargets = 0
        # wire-format byte accounting: staged_bytes is what the host
        # actually memcpy'd into the staging arena per dispatched batch
        # (uint8 wire → 4x less than float32), returned_bytes is what
        # the completion thread handed back to clients (low_res → 64x
        # less). staged_bytes / requests is the bench.py --wire headline.
        self.staged_bytes = 0
        self.returned_bytes = 0
        # name -> zero-arg callable; the engine wires live gauges
        # (queue depth, in-flight batches, health code, breaker trips)
        # so snapshot() reads the instantaneous value.
        self._gauge_sources: Dict[str, object] = {}
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    # -- recording (engine-internal) -----------------------------------

    def set_gauge_source(self, name: str, fn) -> None:
        """Register a live gauge: ``snapshot()`` emits
        ``serving_<name> = float(fn())`` (0.0 if the callable raises —
        a gauge must never take the scalar stream down)."""
        with self._lock:
            self._gauge_sources[name] = fn

    def record_submit(self, queue_depth: int,
                      priority: str = "high") -> None:
        with self._lock:
            self.requests += 1
            self.requests_by_class[priority] += 1
            if self._t_first is None:
                self._t_first = time.perf_counter()
            if queue_depth > self.queue_depth_peak:
                self.queue_depth_peak = queue_depth

    def record_reject(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_shed(self, priority: str = "high") -> None:
        """A ``BacklogFull`` load-shed (a rejected submit, or a queued
        LOW request evicted for an arriving HIGH). Counted on top of
        ``record_reject`` (every shed is a rejection; closed-engine
        rejections are not sheds): the shed rate is the capacity-planning
        signal, the reject total is the client-visible error rate."""
        with self._lock:
            self.sheds += 1
            self.sheds_by_class[priority] += 1

    def record_swap(self) -> None:
        """A hot checkpoint reload passed its canary and was swapped
        into the live engine."""
        with self._lock:
            self.swaps += 1

    def record_rollback(self) -> None:
        """A hot checkpoint reload FAILED its canary and was rolled
        back (the previous model stays pinned). Page-worthy: newer
        committed checkpoints exist that this replica refuses to
        serve."""
        with self._lock:
            self.rollbacks += 1

    def record_isolated_retry(self, n: int = 1) -> None:
        """Requests from a failed batch that served successfully on the
        retry-as-singles isolation pass (their batch neighbor — e.g. a
        poisoned input — would otherwise have failed them)."""
        with self._lock:
            self.isolated_retries += n

    def record_breaker_fastfail(self, n: int = 1) -> None:
        """Requests failed fast with ``EngineUnhealthy`` while the
        dispatch circuit breaker was open (at submit or drained from
        the queue)."""
        with self._lock:
            self.breaker_fastfails += n

    def record_sharded(self, n: int = 1) -> None:
        """A submit routed onto the spatially-sharded serving path (on
        top of ``record_submit``, which counts it in the request
        totals)."""
        with self._lock:
            self.sharded_requests += n

    def record_stream_submit(self, warm: bool) -> None:
        """A stream-session pair accepted (on top of ``record_submit``,
        which counts it in the request totals): ``warm`` pairs refine
        from the propagated previous flow at ``warm_iters``, cold pairs
        are a session's first pair (or its post-state-drop restart) at
        full ``iters``."""
        with self._lock:
            if warm:
                self.warm_requests += 1
            else:
                self.cold_stream_requests += 1

    def record_encoder_cache(self, hit: bool) -> None:
        """Encoder feature-map cache accounting: a hit is a pair served
        with a cached fmap1 (one fnet pass), a miss is a session prime
        or post-failure re-prime (a standalone fnet pass). Per stream of
        N frames the steady state is 1 miss + (N-1) hits → hit rate
        (N-1)/N; failovers/state drops add honest misses."""
        with self._lock:
            if hit:
                self.encoder_hits += 1
            else:
                self.encoder_misses += 1

    def record_quality(self, iters: int, n: int = 1) -> None:
        """``n`` responses served at ``iters`` GRU iterations (recorded
        at completion, so a request re-bucketed down the ladder while
        queued counts at the level that actually served it)."""
        with self._lock:
            self.quality_hist[int(iters)] += n

    def record_early_exit_saved(self, iters_saved: int) -> None:
        """Refine iterations the convergence early exit masked out,
        summed per-sample over a completed batch."""
        with self._lock:
            self.early_exit_iters_saved += int(iters_saved)

    def record_contbatch_admit(self, n: int = 1) -> None:
        """Requests scattered into freed slots of a continuous slot
        table (on top of ``record_submit``)."""
        with self._lock:
            self.contbatch_admits += n

    def record_contbatch_retire(self, n: int, freed_iters: int) -> None:
        """``n`` slots retired (converged or per-request iters hit),
        freeing ``freed_iters`` refine iterations of slot budget the
        monolithic masked scan would have burned as padding."""
        with self._lock:
            self.contbatch_retires += n
            self.contbatch_freed_iters += int(freed_iters)

    def record_contbatch_step(self, occupied: int) -> None:
        """One ``step_dispatch`` launch with ``occupied`` live slots —
        mean occupancy (``occupancy_sum / steps``) is the scheduler's
        fill factor."""
        with self._lock:
            self.contbatch_steps += 1
            self.contbatch_occupancy_sum += int(occupied)

    def record_contbatch_retarget(self, n: int = 1) -> None:
        """In-flight slots whose remaining-iters budget was re-targeted
        in place on a brownout rung change (no re-bucketing, no fresh
        executable)."""
        with self._lock:
            self.contbatch_retargets += n

    def record_staged_bytes(self, n: int) -> None:
        """Bytes the host copied into the staging arena for one
        dispatched batch (both input planes, tail-padding included —
        the real memcpy traffic, so the uint8 wire's 4x shows up
        here, not in a back-of-envelope)."""
        with self._lock:
            self.staged_bytes += int(n)

    def record_returned_bytes(self, n: int) -> None:
        """Bytes handed back to clients through resolved futures
        (post-unpad full-res flow, or the 1/8-grid ``low_res``
        response)."""
        with self._lock:
            self.returned_bytes += int(n)

    def record_batch(self, size: int, padded_to: int,
                     compiles: int = 0) -> None:
        with self._lock:
            self.batches += 1
            self.batch_hist[size] += 1
            self.padded_slots += max(padded_to - size, 0)
            self.compiles += compiles

    def record_done(self, latency_s: float) -> None:
        with self._lock:
            self.responses += 1
            self._lat.append(latency_s)
            self._t_last = time.perf_counter()

    def record_error(self, n: int = 1) -> None:
        with self._lock:
            self.errors += n
            self._t_last = time.perf_counter()

    def record_timeout(self, n: int = 1) -> None:
        """Requests whose queue-timeout deadline expired before
        dispatch. Counted separately from ``errors``: a timeout is the
        shedding policy working, not the engine failing."""
        with self._lock:
            self.timeouts += n
            self._t_last = time.perf_counter()

    # -- reading --------------------------------------------------------

    def latencies_s(self) -> list:
        """Copy of the rolling latency window, in seconds. The fleet
        aggregator pools these across replicas so fleet percentiles are
        computed over the raw samples, not averaged per-replica
        percentiles (which would be statistically meaningless)."""
        with self._lock:
            return list(self._lat)

    def latency_ms(self) -> Dict[str, float]:
        with self._lock:
            vals = sorted(self._lat)
        return {"p50": _percentile(vals, 50) * 1e3,
                "p95": _percentile(vals, 95) * 1e3,
                "p99": _percentile(vals, 99) * 1e3,
                "mean": (sum(vals) / len(vals) * 1e3) if vals else 0.0}

    def throughput(self) -> float:
        """Completed responses per second of serving wall time (first
        submit → last completion)."""
        with self._lock:
            if self._t_first is None or self._t_last is None:
                return 0.0
            dt = self._t_last - self._t_first
            return self.responses / dt if dt > 0 else 0.0

    def mean_batch_size(self) -> float:
        with self._lock:
            total = sum(k * v for k, v in self.batch_hist.items())
            n = sum(self.batch_hist.values())
        return total / n if n else 0.0

    def snapshot(self) -> Dict[str, float]:
        """Flat float dict — the shape ``TrainLogger.write_dict`` (and
        the bench JSON artifact) want."""
        lat = self.latency_ms()
        with self._lock:
            out = {
                "serving_requests": float(self.requests),
                "serving_requests_high": float(
                    self.requests_by_class["high"]),
                "serving_requests_low": float(
                    self.requests_by_class["low"]),
                "serving_rejected": float(self.rejected),
                "serving_shed": float(self.sheds),
                "serving_shed_high": float(self.sheds_by_class["high"]),
                "serving_shed_low": float(self.sheds_by_class["low"]),
                "serving_responses": float(self.responses),
                "serving_errors": float(self.errors),
                "serving_timeouts": float(self.timeouts),
                "serving_batches": float(self.batches),
                "serving_padded_slots": float(self.padded_slots),
                "serving_compiles": float(self.compiles),
                "serving_queue_depth_peak": float(self.queue_depth_peak),
                "serving_swaps": float(self.swaps),
                "serving_rollbacks": float(self.rollbacks),
                "serving_isolated_retries": float(self.isolated_retries),
                "serving_breaker_fastfails": float(
                    self.breaker_fastfails),
                "serving_sharded_requests": float(self.sharded_requests),
                "serving_warm_requests": float(self.warm_requests),
                "serving_cold_stream_requests": float(
                    self.cold_stream_requests),
                "serving_encoder_hits": float(self.encoder_hits),
                "serving_encoder_misses": float(self.encoder_misses),
                "serving_encoder_cache_hit_rate": (
                    self.encoder_hits
                    / (self.encoder_hits + self.encoder_misses)
                    if (self.encoder_hits + self.encoder_misses)
                    else 0.0),
                "serving_early_exit_iters_saved": float(
                    self.early_exit_iters_saved),
                "serving_staged_bytes": float(self.staged_bytes),
                "serving_returned_bytes": float(self.returned_bytes),
                "serving_contbatch_admits": float(self.contbatch_admits),
                "serving_contbatch_retires": float(
                    self.contbatch_retires),
                "serving_contbatch_steps": float(self.contbatch_steps),
                "serving_contbatch_mean_occupancy": (
                    self.contbatch_occupancy_sum / self.contbatch_steps
                    if self.contbatch_steps else 0.0),
                "serving_contbatch_freed_iters": float(
                    self.contbatch_freed_iters),
                "serving_contbatch_retargets": float(
                    self.contbatch_retargets),
            }
            for iters, n in self.quality_hist.items():
                out[f"serving_quality_iters_{iters}"] = float(n)
            gauges = dict(self._gauge_sources)
        for name, fn in gauges.items():
            try:
                out[f"serving_{name}"] = float(fn())
            except Exception:
                out[f"serving_{name}"] = 0.0
        out["serving_latency_p50_ms"] = lat["p50"]
        out["serving_latency_p95_ms"] = lat["p95"]
        out["serving_latency_p99_ms"] = lat["p99"]
        out["serving_latency_mean_ms"] = lat["mean"]
        out["serving_throughput_rps"] = self.throughput()
        out["serving_mean_batch_size"] = self.mean_batch_size()
        return out

    def batch_histogram(self) -> Dict[int, int]:
        with self._lock:
            return dict(self.batch_hist)

    def quality_histogram(self) -> Dict[int, int]:
        """``{iters_level: responses served at it}`` — the brownout
        SLO readout (full-quality count vs the degraded ladder's)."""
        with self._lock:
            return dict(self.quality_hist)

    def attach_registry(self, registry) -> None:
        """Re-register this bag's live values as typed instruments on
        a :class:`~raft_tpu.observability.registry.MetricsRegistry` —
        callable-backed gauges reading the SAME counters ``snapshot()``
        reads, so the two expositions can never drift and this class's
        public surface (``snapshot``/``report``) is unchanged. Dynamic
        families (quality histogram, engine-wired gauge sources) become
        labeled gauges instead of dynamic names, so the registry's
        instrument set stays pinnable."""
        g = registry.gauge
        for name, attr, help_ in (
                ("serving_requests", "requests", "accepted submits"),
                ("serving_rejected", "rejected",
                 "rejections (sheds + closed-engine refusals)"),
                ("serving_shed", "sheds", "BacklogFull load-sheds"),
                ("serving_responses", "responses",
                 "futures resolved with a result"),
                ("serving_errors", "errors",
                 "futures resolved with an exception"),
                ("serving_timeouts", "timeouts",
                 "queue-deadline expiries"),
                ("serving_batches", "batches", "dispatched batches"),
                ("serving_padded_slots", "padded_slots",
                 "tail-padding waste (slots)"),
                ("serving_compiles", "compiles",
                 "fresh XLA compiles on the serve path"),
                ("serving_queue_depth_peak", "queue_depth_peak",
                 "peak backlog depth"),
                ("serving_swaps", "swaps", "hot reloads served live"),
                ("serving_rollbacks", "rollbacks",
                 "canary-failed reloads rolled back"),
                ("serving_isolated_retries", "isolated_retries",
                 "batch-failure singles that served"),
                ("serving_breaker_fastfails", "breaker_fastfails",
                 "requests failed fast while breaker OPEN"),
                ("serving_sharded_requests", "sharded_requests",
                 "submits routed to the spatially-sharded path"),
                ("serving_warm_requests", "warm_requests",
                 "warm stream pairs"),
                ("serving_cold_stream_requests", "cold_stream_requests",
                 "cold stream pairs"),
                ("serving_encoder_hits", "encoder_hits",
                 "encoder fmap cache hits"),
                ("serving_encoder_misses", "encoder_misses",
                 "encoder fmap cache misses (primes)"),
                ("serving_early_exit_iters_saved",
                 "early_exit_iters_saved",
                 "refine iterations skipped by convergence early exit"),
                ("serving_staged_bytes", "staged_bytes",
                 "bytes memcpy'd into the staging arena"),
                ("serving_returned_bytes", "returned_bytes",
                 "bytes returned through resolved futures"),
                ("serving_contbatch_admits", "contbatch_admits",
                 "requests admitted into continuous slot tables"),
                ("serving_contbatch_retires", "contbatch_retires",
                 "continuous slots retired at convergence/budget"),
                ("serving_contbatch_steps", "contbatch_steps",
                 "continuous step_dispatch launches"),
                ("serving_contbatch_freed_iters",
                 "contbatch_freed_iters",
                 "slot iterations freed by early retirement"),
                ("serving_contbatch_retargets", "contbatch_retargets",
                 "in-flight slots re-targeted on brownout rung moves")):
            g(name, help=help_,
              fn=(lambda a=attr: float(getattr(self, a))))
        g("serving_requests_by_class",
          help="accepted submits per priority class",
          labelnames=("class",),
          fn=lambda: {(c,): float(n)
                      for c, n in self.requests_by_class.items()})
        g("serving_shed_by_class",
          help="load-sheds per priority class", labelnames=("class",),
          fn=lambda: {(c,): float(n)
                      for c, n in self.sheds_by_class.items()})
        g("serving_quality_iters",
          help="responses served per GRU iteration level",
          labelnames=("iters",),
          fn=lambda: {(str(k),): float(v)
                      for k, v in self.quality_histogram().items()})
        g("serving_batch_size",
          help="dispatched batches per real-request count",
          labelnames=("size",),
          fn=lambda: {(str(k),): float(v)
                      for k, v in self.batch_histogram().items()})
        g("serving_latency_ms",
          help="rolling-window latency percentiles",
          labelnames=("quantile",),
          fn=lambda: {(q,): v for q, v in self.latency_ms().items()})
        g("serving_throughput_rps",
          help="responses per second of serving wall time",
          fn=self.throughput)
        g("serving_mean_batch_size",
          help="mean real requests per dispatched batch",
          fn=self.mean_batch_size)
        g("serving_contbatch_mean_occupancy",
          help="mean live slots per continuous step",
          fn=lambda: (self.contbatch_occupancy_sum
                      / self.contbatch_steps
                      if self.contbatch_steps else 0.0))
        g("serving_encoder_cache_hit_rate",
          help="encoder fmap cache hit rate",
          fn=lambda: (self.encoder_hits
                      / (self.encoder_hits + self.encoder_misses)
                      if (self.encoder_hits + self.encoder_misses)
                      else 0.0))

        def _gauges():
            with self._lock:
                sources = dict(self._gauge_sources)
            out = {}
            for name, fn in sources.items():
                try:
                    out[(name,)] = float(fn())
                except Exception:
                    out[(name,)] = 0.0
            return out

        g("serving_gauge",
          help="engine-wired live gauges (queue depth, inflight "
               "batches, breaker trips, health code, brownout level)",
          labelnames=("name",), fn=_gauges)

    def write_to(self, train_logger, step: Optional[int] = None) -> None:
        """Stream the snapshot through the existing scalar sinks
        (``scalars.jsonl`` + TensorBoard)."""
        train_logger.write_dict(self.snapshot(), step=step)

    def report(self) -> str:
        lat = self.latency_ms()
        hist = ", ".join(f"{k}:{v}" for k, v in
                         sorted(self.batch_histogram().items()))
        qhist = ", ".join(f"{k}:{v}" for k, v in
                          sorted(self.quality_histogram().items(),
                                 reverse=True))
        quality = (f" | quality hist {{{qhist}}}, early-exit saved "
                   f"{self.early_exit_iters_saved} iters"
                   if qhist or self.early_exit_iters_saved else "")
        return (f"requests {self.requests} "
                f"(hi {self.requests_by_class['high']} / "
                f"lo {self.requests_by_class['low']}, "
                f"rejected {self.rejected}, shed {self.sheds}) "
                f"responses {self.responses} errors {self.errors} "
                f"timeouts {self.timeouts} | "
                f"{self.throughput():.2f} req/s, mean batch "
                f"{self.mean_batch_size():.2f} | latency ms p50 "
                f"{lat['p50']:.1f} p95 {lat['p95']:.1f} p99 "
                f"{lat['p99']:.1f} | batch hist {{{hist}}} | padded "
                f"slots {self.padded_slots}, compiles {self.compiles}, "
                f"queue peak {self.queue_depth_peak} | swaps "
                f"{self.swaps}, rollbacks {self.rollbacks}, isolated "
                f"retries {self.isolated_retries}, breaker fastfails "
                f"{self.breaker_fastfails} | staged "
                f"{self.staged_bytes / 1e6:.2f} MB, returned "
                f"{self.returned_bytes / 1e6:.2f} MB{quality}")
