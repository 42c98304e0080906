"""Latency/throughput-focused inference serving for RAFT.

The ROADMAP north star serves heavy traffic from millions of users;
traffic like that arrives as single frame pairs, and BENCH_r05 puts the
cost of serving them one at a time at ~3x (31.5 pairs/s at batch 1 vs
99.0 at batch 128 per chip). This package closes that batch-1 gap at the
queue level, reusing :class:`raft_tpu.evaluate.FlowPredictor` for the
forward itself:

* :mod:`~raft_tpu.serving.batcher` — thread-safe shape-bucketed dynamic
  batcher (close on max-size or deadline, two priority classes per
  bucket, backlog cap with LOW-first shedding).
* :mod:`~raft_tpu.serving.engine` — warmup (per-bucket pre-compile +
  persistent XLA cache), pipelined async dispatch with donated input
  buffers, the ``submit() -> Future`` client API, circuit breaker +
  batch error isolation + health states + atomic model swap; uint8
  wire format (dtype-preserving host path through a zero-copy staging
  arena, dual-dtype warmup, bit-identical outputs) and the opt-in
  ``low_res`` 1/8-grid response.
* :mod:`~raft_tpu.serving.health` — engine health states, the dispatch
  :class:`~raft_tpu.serving.health.CircuitBreaker`, and the
  :class:`~raft_tpu.serving.health.EngineUnhealthy` fail-fast error.
* :mod:`~raft_tpu.serving.brownout` — graceful brownout under
  overload: the :class:`~raft_tpu.serving.brownout.BrownoutController`
  steps LOW traffic down a pre-warmed GRU-iteration quality ladder
  (degraded answers before dropped ones) and back up with hysteresis;
  zero fresh compiles, HIGH traffic never degraded.
* :mod:`~raft_tpu.serving.reload` — hot checkpoint reload: watch the
  trainer's commit-gated checkpoints, canary-validate a standby model
  on golden pairs (zero-compile via the shared executable cache), swap
  atomically or roll back and pin the bad step.
* :mod:`~raft_tpu.serving.metrics` — p50/p95/p99 latency, batch-size
  histogram, queue depth, throughput, XLA compile-count probe, plus
  robustness gauges (health state, swaps/rollbacks/breaker trips).
* :mod:`~raft_tpu.serving.loadgen` — CPU-runnable concurrent load
  generator with bit-exact response checking and per-replica
  attribution (drives ``bench.py serving`` and
  ``scripts/serve_drill.py``).
* :mod:`~raft_tpu.serving.fleet` — N engines behind one
  ``submit()/health()`` surface: rendezvous-hashed bucket routing (each
  replica warms only its buckets), health-gated balancing with
  response-level failover, fleet-wide rolling hot reload
  (canary-one-then-wave, whole-fleet rollback on drift), and
  fleet-aggregated metrics.
* :mod:`~raft_tpu.serving.netproto` / :mod:`~raft_tpu.serving.worker`
  / :mod:`~raft_tpu.serving.gateway` / :mod:`~raft_tpu.serving
  .supervisor` — the multi-process tier: replica engines in separate
  OS processes behind a length-prefixed local-socket protocol (the
  uint8 wire bytes network-fed into each worker's staging arena, with
  absolute deadlines propagated and enforced at every hop), heartbeat-
  lease membership over the coordination KV (file-store fallback),
  rendezvous routing over live lease-holders with the fleet's
  failover-not-timeout retry contract, and supervised respawn with
  exponential backoff + a crash-loop breaker. The transport is
  hardened for long-lived fleets: TCP keepalive, a bounded idle pool
  with age eviction, and one transparent reconnect when a pooled
  socket proves dead before any bytes are written.
* :mod:`~raft_tpu.serving.autoscaler` — metrics-driven capacity: a
  clock-injectable control loop reads the gateway's registry gauges
  (queue depth, slot occupancy, SLO violation ratio) and converges
  the fleet between ``min_workers``/``max_workers`` with two-watermark
  hysteresis, dwell and directional cooldowns. Scale-up spawns through
  the supervisor (unroutable until the lease proves warmup, brownout
  covering the gap); scale-down drains the least-loaded worker
  gracefully (finish in-flight, remove lease, exit 0 — a departure,
  not a crash).
* :mod:`~raft_tpu.serving.session` — stateful streaming sessions
  (``open_stream``): warm-start ``flow_init`` from the previous pair's
  flow at reduced ``warm_iters``, plus encoder feature-map reuse (one
  fnet pass per warm frame instead of two). The fleet adds sticky
  rendezvous pinning with state-drop + cold-restart failover
  (:class:`~raft_tpu.serving.fleet.FleetStreamSession`).
"""

from raft_tpu.serving.autoscaler import Autoscaler, AutoscalerConfig
from raft_tpu.serving.batcher import (PRIORITIES, PRIORITY_HIGH,
                                      PRIORITY_LOW, BacklogFull,
                                      QueuedRequest, RequestTimedOut,
                                      ShapeBucketBatcher)
from raft_tpu.serving.brownout import BrownoutController
from raft_tpu.serving.engine import (WIRE_F32, WIRE_U8, ServingConfig,
                                     ServingEngine, make_engine, request_wire,
                                     upsample_flow, wire_cast)
from raft_tpu.serving.fleet import (BucketRouter, FleetMetrics,
                                    FleetReloadConfig, FleetReloader,
                                    FleetStreamSession, ServingFleet,
                                    make_fleet)
from raft_tpu.serving.gateway import (GatewayConfig, GatewayMetrics,
                                      ServingGateway, SocketTransport,
                                      WorkerConnectionError)
from raft_tpu.serving.health import (CircuitBreaker, EngineUnhealthy,
                                     HEALTH_CODES, ROUTABLE, STALE,
                                     is_routable)
from raft_tpu.serving.metrics import (CompileWatch, ServingMetrics,
                                      xla_compile_count)
from raft_tpu.serving.netproto import (CoordKVLeaseStore, FileLeaseStore,
                                       Lease, ProtocolError,
                                       default_lease_store, owners_key)
from raft_tpu.serving.reload import (CanaryResult, HotReloader,
                                     ReloadConfig, ReloadSnapshot,
                                     load_step_variables)
from raft_tpu.serving.session import StreamSession
from raft_tpu.serving.supervisor import WorkerSpec, WorkerSupervisor
from raft_tpu.serving.worker import (WorkerConfig, WorkerServer,
                                     spawn_worker)

__all__ = [
    "Autoscaler",
    "AutoscalerConfig",
    "BacklogFull",
    "BrownoutController",
    "BucketRouter",
    "CanaryResult",
    "CircuitBreaker",
    "CompileWatch",
    "CoordKVLeaseStore",
    "EngineUnhealthy",
    "FileLeaseStore",
    "FleetMetrics",
    "FleetReloadConfig",
    "FleetReloader",
    "FleetStreamSession",
    "GatewayConfig",
    "GatewayMetrics",
    "HEALTH_CODES",
    "HotReloader",
    "Lease",
    "ProtocolError",
    "PRIORITIES",
    "PRIORITY_HIGH",
    "PRIORITY_LOW",
    "QueuedRequest",
    "ROUTABLE",
    "ReloadConfig",
    "ReloadSnapshot",
    "RequestTimedOut",
    "STALE",
    "ServingConfig",
    "ServingEngine",
    "ServingFleet",
    "ServingGateway",
    "ServingMetrics",
    "ShapeBucketBatcher",
    "SocketTransport",
    "StreamSession",
    "WIRE_F32",
    "WIRE_U8",
    "WorkerConfig",
    "WorkerConnectionError",
    "WorkerServer",
    "WorkerSpec",
    "WorkerSupervisor",
    "default_lease_store",
    "is_routable",
    "load_step_variables",
    "make_engine",
    "make_fleet",
    "owners_key",
    "request_wire",
    "spawn_worker",
    "upsample_flow",
    "wire_cast",
    "xla_compile_count",
]
