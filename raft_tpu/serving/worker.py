"""Replica worker process: one :class:`~raft_tpu.serving.engine
.ServingEngine` behind a local socket, with a heartbeat lease.

The multi-process serving tier's fault-isolation unit. Each worker is
its own OS process (its own Python heap, its own XLA client) so a
crash, deadlock, or OOM takes out exactly one replica — the failure
mode the in-process :class:`~raft_tpu.serving.fleet.ServingFleet` can
only simulate. The gateway never holds a reference into a worker; the
entire contract is:

* **The socket** — length-prefixed frames (:mod:`netproto`): a
  ``submit`` frame carries the request's wire bytes (the SAME uint8
  1-byte/channel payload :func:`~raft_tpu.serving.engine.request_wire`
  produces — ``np.frombuffer`` views of the received body feed the
  engine's staging arena with zero copies) plus ``priority``,
  ``iters``, ``trace_id`` and the absolute monotonic ``deadline``. The
  worker re-enforces the deadline at its hop: an already-expired
  request is answered ``timeout`` without ever touching the engine,
  and an accepted one carries the deadline into
  ``ServingEngine.submit(deadline_s=...)`` so the in-engine queue gate
  honors the client's remaining budget too.

* **The idempotency cache** — every submit frame may carry a
  ``request_id`` (gateway-minted or edge-propagated). The worker keeps
  a bounded LRU of key → in-flight-entry-or-completed-reply
  (:class:`DedupCache`): a duplicate delivery *attaches* to the
  in-flight computation (one engine compute, two bit-identical
  replies) and a retry after the reply bytes were lost *replays* the
  cached reply verbatim. This is what makes the gateway's
  retry-after-send safe — and it is deliberately process-local: a
  worker death loses the cache, and the retried key recomputes
  honestly on the respawn (determinism makes that recompute
  bit-identical anyway).

* **The SDC sentinel** — with ``self_check_interval_s`` set, a
  background thread periodically runs a golden frame pair through the
  engine (HIGH priority, a warmed bucket shape — zero fresh compiles
  by construction) and compares against the post-warmup reference:
  non-finite output, EPE drift beyond ``self_check_max_epe``, or any
  fresh compile flips the lease to ``QUARANTINED`` — non-routable,
  cooperative (the process keeps heartbeating), and recycled by the
  supervisor as a directed replacement, never a crash.

* **The lease** — a :class:`~raft_tpu.serving.netproto.Lease`
  republished every ``heartbeat_interval_s`` with the worker's
  address, engine health state, bucket config, served checkpoint step
  (from the reloader's serializable
  :class:`~raft_tpu.serving.reload.ReloadSnapshot`, or the statically
  configured ``step``) and post-warmup compile count. The heartbeat
  thread starts BEFORE warmup (publishing ``warming``) so the
  supervisor sees a fresh lease while executables compile — a slow
  warmup must read as "alive, not routable", never as a death.

Fault injection (:class:`~raft_tpu.resilience.FaultInjector`
``RAFT_FAULT_WORKER_*`` knobs) hooks four seams: kill the process on
the Nth received request (``os._exit`` mid-request — after acceptance,
before any reply: the exact window the gateway's post-acceptance retry
covers), stall the heartbeat once so the lease expires under a live
process, drop a connection after serving instead of replying, and
blackhole every request for one partition window while the heartbeat
stays fresh (alive to membership, dead to traffic — only the
gateway's per-hop stall deadline can catch it).

``python -m raft_tpu.serving.worker --spec spec.json`` runs one worker
until SIGTERM; :func:`spawn_worker` is the supervisor-side launcher
(plain ``subprocess.Popen`` with the parent's environment —
``JAX_PLATFORMS`` and the fault-injection env vars inherit).

**One worker process per chip.** A TPU chip belongs to one process at a
time: the first process that touches JAX holds it, and another that
needs it then fails or hangs. So a chip host runs exactly one worker
process per chip, and the processes that only route — the supervisor,
the gateway and the edge — must never initialise a backend (they do not
import jax at all; ``tests/test_chip_smoke.py`` checks it). Known gap,
recorded and not yet fixed: nothing here pins a worker to a device, so
on a four-chip host every ``spawn_worker`` child (and every in-process
``ServingFleet`` replica) lands on chip 0; ROADMAP Design 2 decides that
fabric's fate on measurements.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import dataclasses
import json
import logging
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from raft_tpu import resilience
from raft_tpu.serving import health as health_mod
from raft_tpu.serving import netproto
from raft_tpu.serving.batcher import PRIORITY_HIGH, RequestTimedOut
from raft_tpu.serving.metrics import CompileWatch
from raft_tpu.serving.netproto import (Lease, ProtocolError, read_message,
                                       write_message)

logger = logging.getLogger(__name__)

#: Exit code of an injected mid-request kill (distinguishable from a
#: clean exit in supervisor logs).
KILLED_BY_INJECTION = 17


def _is_loopback(host: str) -> bool:
    """Whether ``host`` names the loopback interface. An empty string
    and ``0.0.0.0`` are wildcard binds — reachable on every interface,
    so NOT loopback for the advertise-refusal rule."""
    if not host:
        return False
    if host in ("localhost", "::1"):
        return True
    return host.startswith("127.")


@dataclasses.dataclass
class WorkerConfig:
    """One worker process's spec — everything needed to build its
    engine and join the membership plane. JSON-roundtrippable
    (:meth:`to_dict` / :meth:`from_dict`) because it crosses the
    supervisor→worker process boundary as a spec file."""

    worker_id: str
    lease_dir: str
    host: str = "127.0.0.1"
    port: int = 0                   # 0 = ephemeral; published via lease
    # Multi-host bind: ``bind_host`` is the interface the listener
    # binds (falls back to ``host``); ``advertise_host`` is what the
    # lease publishes for the gateway to dial. They differ exactly when
    # the bound interface is not the dialable one (``0.0.0.0``
    # wildcard, NAT, container bridge). A non-loopback bind WITHOUT an
    # explicit advertise_host is refused at start: the listener would
    # be reachable off-box while its lease advertises an address other
    # hosts cannot resolve to it — routable-to-nowhere by construction.
    # Loopback defaults keep the single-host posture unchanged.
    bind_host: str = ""
    advertise_host: str = ""
    heartbeat_interval_s: float = 0.5
    buckets: Tuple[Tuple[int, int], ...] = ()
    max_batch: int = 4
    max_wait_ms: float = 3.0
    queue_timeout_ms: int = 10_000
    model_path: str = "random"
    small: bool = True
    iters: int = 2
    step: Optional[int] = None      # static served step (no reloader)
    persistent_cache: object = False
    # Per-connection read deadline: a client that stalls mid-frame (or
    # never sends one) is dropped after this many seconds instead of
    # pinning a connection thread forever. 0 disables. The default is
    # far above the gateway pool's idle-age cutoff, so a pooled
    # keep-alive connection always ages out of the pool before the
    # worker reaps it.
    conn_read_timeout_s: float = 120.0
    # Bound on how long a drain waits for in-flight work before
    # stopping anyway (a wedged request must not leak the process).
    drain_timeout_s: float = 30.0
    # Engine brownout knobs (see ServingConfig): the worker's overload
    # valve while the autoscaler's new capacity warms up.
    iters_ladder: Tuple[int, ...] = ()
    brownout_high_water: int = 0
    brownout_low_water: int = 0
    brownout_dwell_ms: float = 250.0
    # Idempotency cache capacity (entries): bounded LRU of request_id →
    # in-flight computation / completed reply bytes. 0 disables dedup
    # (every delivery computes). Process-local by design: a restart
    # loses the cache and recomputes honestly.
    dedup_cache_size: int = 256
    # SDC sentinel: seconds between golden-pair self-checks (0 =
    # disabled) and the EPE drift band a check may move within before
    # the worker quarantines itself.
    self_check_interval_s: float = 0.0
    self_check_max_epe: float = 5.0

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["buckets"] = [list(b) for b in self.buckets]
        d["iters_ladder"] = [int(v) for v in self.iters_ladder]
        return d

    @staticmethod
    def from_dict(d: Dict[str, object]) -> "WorkerConfig":
        d = dict(d)
        d["buckets"] = tuple(tuple(b) for b in d.get("buckets", ()))
        d["iters_ladder"] = tuple(
            int(v) for v in d.get("iters_ladder", ()))
        known = {f.name for f in dataclasses.fields(WorkerConfig)}
        return WorkerConfig(**{k: v for k, v in d.items() if k in known})


class _DedupEntry:
    """One idempotency-cache slot: in-flight until ``done`` is set,
    then an immutable completed reply (header dict + body bytes).
    Waiters hold a direct reference, so an entry keeps working even
    after LRU eviction removed it from the cache's map."""

    __slots__ = ("done", "header", "body", "cacheable")

    def __init__(self):
        self.done = threading.Event()
        self.header: Optional[dict] = None
        self.body: bytes = b""
        self.cacheable = False


class DedupCache:
    """Bounded LRU of idempotency key → in-flight / completed reply.

    The exactly-once-*effect* mechanism of the reliability layer: the
    first delivery of a key becomes the *owner* (it computes), every
    concurrent duplicate *attaches* (waits on the owner's entry and
    replies with the same bytes), and a later duplicate of a completed
    ``ok`` reply *replays* the cached bytes verbatim. Non-``ok``
    outcomes (timeouts, typed errors) complete their waiters but are
    NOT retained — a later retry of that key deserves a fresh compute,
    not a replayed failure.

    Strictly process-local and deliberately so: the cache survives
    nothing across process death. A respawned worker recomputes a
    retried key from scratch — determinism (bit-exact per bucket
    executable) makes that recompute indistinguishable from a replay,
    which is why dedup here is an optimization with honest fallback,
    never a correctness requirement.

    Thread-safe; counters are the audit trail the drill asserts on.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _DedupEntry]" = \
            collections.OrderedDict()
        self.inserts = 0            # keys that became owners
        self.hits_inflight = 0      # duplicates attached to a compute
        self.replays = 0            # completed replies served from cache
        self.evictions = 0          # LRU evictions under churn

    def begin(self, key: str) -> Tuple[_DedupEntry, bool]:
        """Look up ``key``; returns ``(entry, owner)``. ``owner=True``
        means the caller must compute and then call :meth:`finish`;
        otherwise the caller waits on ``entry.done`` and replies with
        the entry's bytes."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                if e.done.is_set():
                    self.replays += 1
                else:
                    self.hits_inflight += 1
                return e, False
            e = _DedupEntry()
            self._entries[key] = e
            self.inserts += 1
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
            return e, True

    def finish(self, key: str, entry: _DedupEntry, header: dict,
               body: bytes, cacheable: bool) -> None:
        """Complete an owned entry: store the reply, wake every waiter,
        and drop non-cacheable (non-``ok``) outcomes from the map so a
        later retry recomputes."""
        entry.header = dict(header)
        entry.body = bytes(body)
        entry.cacheable = cacheable
        with self._lock:
            if not cacheable and self._entries.get(key) is entry:
                self._entries.pop(key, None)
        entry.done.set()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"size": len(self._entries),
                    "inserts": self.inserts,
                    "hits_inflight": self.hits_inflight,
                    "replays": self.replays,
                    "evictions": self.evictions}


class _SinkConn:
    """Write-discarding stand-in for a socket: the injected duplicate
    delivery runs the REAL serve path but its reply has no transport
    to ride (the at-least-once replay it simulates was an extra frame,
    not an extra client)."""

    def sendall(self, data) -> None:
        pass

    def close(self) -> None:
        pass


class WorkerServer:
    """The socket front-end + heartbeat publisher around one engine.

    Usable in-process (tests and the gateway-overhead bench run real
    sockets without real processes) or as the body of the worker
    ``main``. The engine is injected so tests control its predictor;
    ``reloader`` (optional) supplies the served checkpoint step via
    its serializable snapshot.
    """

    def __init__(self, engine, config: WorkerConfig,
                 lease_store=None, reloader=None, on_drained=None):
        self.engine = engine
        self.config = config
        self.store = (lease_store if lease_store is not None
                      else netproto.default_lease_store(config.lease_dir))
        self.reloader = reloader
        # Invoked (once) after a drain directive finished: in-flight
        # work done, engine closed, lease removed. The worker ``main``
        # hooks its stop event here so a drained process exits 0.
        self.on_drained = on_drained
        self.addr: Optional[Tuple[str, int]] = None
        self._listener: Optional[socket.socket] = None
        self._stop = threading.Event()
        self._threads: list = []
        self._conns_lock = threading.Lock()
        self._conns: set = set()
        self._recv_lock = threading.Lock()
        self._recv_seq = 0          # requests RECEIVED, 1-based
        self._serving = False
        self._hb_seq = 0
        self._compile_watch: Optional[CompileWatch] = None
        # Drain lifecycle: _draining flips once (under _inflight_cv),
        # the drain thread waits for _inflight to hit zero, and
        # drained is set after the full stop sequence completed.
        self._inflight_cv = threading.Condition()
        self._inflight = 0
        self._draining = False
        self.drained = threading.Event()
        self.slow_client_drops = 0  # connections reaped by read deadline
        self._partition_until = 0.0  # injected blackhole window end
        # Idempotent dispatch (None = disabled): request_id → reply.
        self.dedup: Optional[DedupCache] = (
            DedupCache(config.dedup_cache_size)
            if config.dedup_cache_size > 0 else None)
        self.computes = 0           # wire submits that reached the engine
        self.dup_deliveries = 0     # injected duplicate frames served
        # SDC sentinel / quarantine lifecycle.
        self._quarantined = False
        self.quarantine_reason = ""
        self._self_checks = 0
        self._sentinel_ref: Optional[np.ndarray] = None

    # -- lifecycle -------------------------------------------------------

    def start(self, warmup: bool = True) -> "WorkerServer":
        """Bind the listener, start heartbeating (``warming``), warm
        the engine, then open for traffic. Ordering matters: the lease
        must be fresh DURING warmup (slow compile != death) but the
        state stays unroutable until the engine is actually ready —
        the supervisor's rejoin gate reads exactly this sequence."""
        bind_host = self.config.bind_host or self.config.host
        advertise = self.config.advertise_host
        if not _is_loopback(bind_host) and not advertise:
            raise ValueError(
                f"worker {self.config.worker_id!r}: non-loopback "
                f"bind_host {bind_host!r} requires an explicit "
                "advertise_host — the lease must publish an address "
                "other hosts can actually dial")
        ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        ls.bind((bind_host, self.config.port))
        ls.listen(64)
        self._listener = ls
        bound_host, bound_port = ls.getsockname()[:2]
        # The lease advertises the dialable address, not the bound one:
        # a 0.0.0.0 wildcard bind is meaningful to bind(), never to
        # connect().
        self.addr = (advertise or bound_host, bound_port)
        hb = threading.Thread(target=self._heartbeat_loop,
                              name=f"{self.config.worker_id}-heartbeat",
                              daemon=True)
        hb.start()
        self._threads.append(hb)
        if warmup:
            self.engine.start(warmup=True)
        else:
            self.engine.start(warmup=False)
        # Post-warmup baseline: every compile from here on is a
        # contract violation, published per heartbeat so the drill can
        # assert zero-post-warmup-compiles ACROSS process boundaries.
        self._compile_watch = CompileWatch().__enter__()
        self._serving = True
        self._publish_lease()       # don't wait an interval to go live
        acc = threading.Thread(target=self._accept_loop,
                               name=f"{self.config.worker_id}-accept",
                               daemon=True)
        acc.start()
        self._threads.append(acc)
        if self.config.self_check_interval_s > 0 and self.config.buckets:
            sen = threading.Thread(
                target=self._sentinel_loop,
                name=f"{self.config.worker_id}-sdc-sentinel",
                daemon=True)
            sen.start()
            self._threads.append(sen)
        return self

    def stop(self, remove_lease: bool = True) -> None:
        self._stop.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        self.engine.close()
        if remove_lease:
            self.store.remove(self.config.worker_id)

    # -- drain lifecycle -------------------------------------------------

    def drain(self, reason: str = "") -> bool:
        """Begin the graceful decommission sequence (idempotent;
        returns False when a drain was already running).

        The lease flips to ``draining`` immediately — the gateway stops
        routing here at its next membership refresh, and any submit
        that still lands is answered with a typed ``WorkerDraining``
        error the failover contract walks past. A background thread
        waits for in-flight work to finish (bounded by
        ``drain_timeout_s``), runs the normal :meth:`stop` sequence
        (lease removed), then fires ``on_drained`` — which in the
        process entry point means a clean exit 0."""
        with self._inflight_cv:
            if self._draining:
                return False
            self._draining = True
        logger.info("drain directive accepted%s",
                    f" ({reason})" if reason else "")
        t = threading.Thread(target=self._drain_loop,
                             name=f"{self.config.worker_id}-drain",
                             daemon=True)
        t.start()
        self._threads.append(t)
        return True

    @property
    def inflight(self) -> int:
        with self._inflight_cv:
            return self._inflight

    def _drain_loop(self) -> None:
        self._publish_lease()       # go DRAINING now, not next beat
        deadline = time.monotonic() + self.config.drain_timeout_s
        with self._inflight_cv:
            while (self._inflight > 0
                   and time.monotonic() < deadline):
                self._inflight_cv.wait(timeout=0.05)
            leaked = self._inflight
        if leaked:
            logger.warning(
                "drain timeout: %d request(s) still in flight after "
                "%.1fs; stopping anyway", leaked,
                self.config.drain_timeout_s)
        self.stop(remove_lease=True)
        self.drained.set()
        cb = self.on_drained
        if cb is not None:
            try:
                cb()
            except Exception:
                logger.exception("on_drained callback failed")

    # -- membership ------------------------------------------------------

    def _served_step(self) -> Optional[int]:
        if self.reloader is not None:
            return self.reloader.snapshot().current_step
        return self.config.step

    def _lease_state(self) -> str:
        if self._draining:
            # The drain overrides the engine's self-report: routing
            # must stop even while the engine still looks READY.
            return health_mod.DRAINING
        if self._quarantined:
            # SDC sentinel verdict overrides the engine too: the
            # engine still *runs* — it just can't be trusted. The
            # supervisor reads this state and recycles the process as
            # a directed replacement (no crash accounting).
            return health_mod.QUARANTINED
        if not self._serving:
            return "warming"
        try:
            return self.engine.health_state()
        except Exception:
            return "warming"

    def _publish_lease(self) -> None:
        self._hb_seq += 1
        extra: Dict[str, object] = {}
        if self._compile_watch is not None:
            extra["post_warmup_compiles"] = self._compile_watch.so_far
        if self.dedup is not None:
            # The reliability layer's audit trail, published per beat
            # so the drill can assert one-compute / replay / hedge-
            # loser accounting ACROSS process boundaries.
            dd = self.dedup.stats()
            dd["computes"] = self.computes
            dd["dup_deliveries"] = self.dup_deliveries
            extra["dedup"] = dd
        extra["self_checks"] = self._self_checks
        if self._quarantined:
            extra["quarantine_reason"] = self.quarantine_reason
        try:
            h = self.engine.health()
            # The autoscaler's occupancy signal and its drain-target
            # tiebreaker: queued + in-flight work at the last beat.
            extra["load"] = (float(h.get("queue_depth", 0))
                             + float(h.get("inflight_batches", 0)))
            bstats = h.get("brownout")
            if isinstance(bstats, dict):
                extra["brownout_transitions"] = \
                    int(bstats.get("transitions", 0))
                extra["brownout_level"] = int(bstats.get("level", 0))
        except Exception:
            pass                    # stub engines carry no load signal
        lease = Lease(
            worker_id=self.config.worker_id,
            addr=tuple(self.addr) if self.addr else ("", 0),
            state=self._lease_state(),
            step=self._served_step(),
            buckets=tuple(tuple(b) for b in self.config.buckets),
            pid=os.getpid(),
            seq=self._hb_seq,
            t_heartbeat=time.time(),
            extra=extra)
        try:
            self.store.publish(lease)
        except Exception:
            logger.exception("lease publish failed (will retry)")

    def _heartbeat_loop(self) -> None:
        while not self._stop.is_set():
            inj = resilience.active_injector()
            if inj is not None:
                stall = inj.take_heartbeat_stall()
                if stall > 0:
                    logger.warning("injected heartbeat stall: %.1fs",
                                   stall)
                    # A wedged publisher, not a dead process: the
                    # process keeps serving while its lease expires.
                    if self._stop.wait(stall):
                        return
            self._publish_lease()
            if self._stop.wait(self.config.heartbeat_interval_s):
                return

    # -- the socket protocol ---------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return              # listener closed = shutdown
            with self._conns_lock:
                self._conns.add(conn)
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name=f"{self.config.worker_id}-conn",
                                 daemon=True)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        if self.config.conn_read_timeout_s:
            # Slow-client defense: a peer that stalls mid-frame (or
            # opens a connection and never speaks) is reaped after
            # this deadline instead of pinning this thread forever.
            # The gateway pool's idle-age eviction sits well below it,
            # so healthy pooled connections never trip the reaper.
            try:
                conn.settimeout(self.config.conn_read_timeout_s)
            except OSError:
                pass
        try:
            while not self._stop.is_set():
                msg = read_message(conn)
                if msg is None:
                    return          # peer closed cleanly
                if not self._handle(conn, *msg):
                    return          # injected drop: connection is gone
        except socket.timeout:
            self.slow_client_drops += 1
            logger.warning(
                "dropping slow/wedged client connection (no complete "
                "frame within %.1fs)", self.config.conn_read_timeout_s)
        except (ProtocolError, OSError):
            pass                    # torn peer: drop the connection
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _handle(self, conn: socket.socket, header: dict,
                body: bytearray) -> bool:
        """Serve one frame; False = the connection was dropped."""
        op = header.get("op")
        if op == netproto.OP_PING:
            write_message(conn, {"status": "ok",
                                 "state": self._lease_state(),
                                 "step": self._served_step()})
            return True
        if op == netproto.OP_DRAIN:
            # Acknowledge BEFORE the drain starts tearing things down,
            # so the directive's sender gets a definite answer on the
            # same connection it asked on.
            write_message(conn, {"status": "ok",
                                 "draining": True,
                                 "worker": self.config.worker_id,
                                 "inflight": self.inflight})
            self.drain(reason=str(header.get("reason", "")))
            return True
        if op != netproto.OP_SUBMIT:
            write_message(conn, {"status": "error",
                                 "error_type": "ProtocolError",
                                 "error": f"unknown op {op!r}"})
            return True
        with self._recv_lock:
            self._recv_seq += 1
            seq = self._recv_seq
        inj = resilience.active_injector()
        if inj is not None and inj.kills_worker_request(seq):
            # Mid-request SIGKILL-equivalent: the request was accepted
            # (bytes read off the socket) but no reply will ever come —
            # the gateway must retry it on the next owner. os._exit
            # skips atexit/finally exactly like a real kill.
            logger.error("injected kill on request %d", seq)
            os._exit(KILLED_BY_INJECTION)
        if inj is not None:
            window = inj.take_worker_partition()
            if window > 0:
                self._partition_until = time.monotonic() + window
                logger.warning("injected partition: blackholing "
                               "requests for %.1fs", window)
        if self._partition_until > time.monotonic():
            # Accept-then-blackhole: the bytes were read, no reply will
            # ever be written, and the heartbeat thread keeps the lease
            # looking healthy — only the gateway's per-hop stall
            # deadline can detect this worker and fail the request
            # over. Hold silently for the window, then drop the conn.
            while (self._partition_until > time.monotonic()
                   and not self._stop.is_set()):
                time.sleep(0.05)
            return False
        if self._quarantined:
            # Raced the quarantine announcement (the gateway routes on
            # its last membership refresh): a typed post-acceptance
            # error the failover contract walks past — never serve a
            # result the SDC sentinel just declared untrustworthy.
            write_message(conn, {"status": "error",
                                 "error_type": "WorkerQuarantined",
                                 "error": f"worker "
                                          f"{self.config.worker_id} is "
                                          "quarantined "
                                          f"({self.quarantine_reason}); "
                                          "route elsewhere"})
            return True
        with self._inflight_cv:
            draining = self._draining
            if not draining:
                self._inflight += 1
        if draining:
            # Raced the drain announcement: a typed post-acceptance
            # error the gateway's failover contract walks past.
            write_message(conn, {"status": "error",
                                 "error_type": "WorkerDraining",
                                 "error": f"worker "
                                          f"{self.config.worker_id} is "
                                          "draining; route elsewhere"})
            return True
        if inj is not None and inj.duplicates_worker_request(seq):
            # At-least-once transport replaying a frame it already
            # delivered: run the SAME bytes through the real serve
            # path concurrently. Both passes share one request_id, so
            # the dedup cache must collapse them to one engine compute;
            # the duplicate's reply rides a sink (the replayed frame
            # had no second client attached).
            logger.warning("injected duplicate delivery of request %d",
                           seq)
            self.dup_deliveries += 1
            dup = threading.Thread(
                target=self._serve_duplicate,
                args=(dict(header), body),
                name=f"{self.config.worker_id}-dup", daemon=True)
            dup.start()
        try:
            return self._serve_submit(conn, header, body, seq, inj)
        finally:
            with self._inflight_cv:
                self._inflight -= 1
                self._inflight_cv.notify_all()

    def _serve_submit(self, conn: socket.socket, header: dict,
                      body: bytearray, seq: int, inj) -> bool:
        key = header.get("request_id")
        entry = None
        if key is not None and self.dedup is not None:
            entry, owner = self.dedup.begin(str(key))
            if not owner:
                # Duplicate delivery: attach to the in-flight compute
                # or replay the completed reply — never recompute.
                return self._reply_from_entry(conn, entry, header)
        reply_header, reply_body, cacheable = \
            self._compute_reply(header, body)
        if entry is not None:
            # Fill the cache BEFORE any reply byte moves: a reply lost
            # on the wire (drop injector below, SIGKILL upstream) must
            # already be replayable when the same key is retried.
            self.dedup.finish(str(key), entry, reply_header,
                              reply_body, cacheable)
        if (reply_header.get("status") == "ok" and inj is not None
                and inj.maybe_drop_worker_socket()):
            # Post-acceptance, post-serve drop: the reply bytes are
            # the only casualty. The gateway sees a dead connection
            # after acceptance and retries the SAME key — served from
            # the cache fill above with zero extra computes.
            logger.warning("injected socket drop (request %d)", seq)
            try:
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
            return False
        write_message(conn, reply_header, reply_body)
        return True

    def _reply_from_entry(self, conn, entry: _DedupEntry,
                          header: dict) -> bool:
        """Answer a duplicate delivery from the idempotency cache:
        wait (deadline-bounded) for the owner's compute if it is still
        in flight, then reply with the owner's exact bytes plus a
        ``deduped`` marker in the header (the body is bit-identical —
        the marker is audit, not payload)."""
        deadline = header.get("deadline")
        remaining = (None if deadline is None
                     else max(deadline - time.monotonic(), 0.001))
        if not entry.done.wait(timeout=remaining):
            write_message(conn, {"status": "timeout",
                                 "error": "deadline expired awaiting "
                                          "the in-flight duplicate"})
            return True
        reply = dict(entry.header)
        reply["deduped"] = True
        write_message(conn, reply, entry.body)
        return True

    def _serve_duplicate(self, header: dict, body: bytearray) -> None:
        """Body of the injected duplicate-delivery thread: the same
        frame through the real serve path (inflight-accounted), reply
        discarded into a sink."""
        with self._inflight_cv:
            if self._draining:
                return
            self._inflight += 1
        try:
            self._serve_submit(_SinkConn(), header, body, -1, None)
        except Exception:
            logger.exception("injected duplicate delivery failed")
        finally:
            with self._inflight_cv:
                self._inflight -= 1
                self._inflight_cv.notify_all()

    def _compute_reply(self, header: dict, body: bytearray
                       ) -> Tuple[dict, bytes, bool]:
        """One real compute: deadline admission → engine submit →
        typed reply. Returns ``(header, body, cacheable)`` —
        ``cacheable`` only for ``ok`` replies; failures complete any
        attached duplicates but are not retained for replay (a retry
        of a failed key deserves a fresh compute)."""
        deadline = header.get("deadline")
        if deadline is not None and time.monotonic() >= deadline:
            # Expired before we touched the engine: the budget was
            # spent upstream (queues, retries). Answer fast — serving
            # it would hand back a too-late result the client already
            # gave up on.
            return ({"status": "timeout",
                     "error": "deadline expired at worker admission"},
                    b"", False)
        try:
            fut = self._submit_from_wire(header, body)
            remaining = (None if deadline is None
                         else max(deadline - time.monotonic(), 0.001))
            flow = fut.result(timeout=remaining)
        except RequestTimedOut as e:
            return {"status": "timeout", "error": str(e)}, b"", False
        except (concurrent.futures.TimeoutError, TimeoutError):
            # fut.result() outlived the wire deadline.
            return ({"status": "timeout",
                     "error": "deadline expired in flight"}, b"", False)
        except Exception as e:     # engine-side failure: typed reply
            return ({"status": "error",
                     "error_type": type(e).__name__,
                     "error": str(e)}, b"", False)
        flow = np.ascontiguousarray(flow, dtype=np.float32)
        return ({"status": "ok",
                 "shape": list(flow.shape),
                 "dtype": "float32",
                 "worker": self.config.worker_id},
                flow.tobytes(), True)

    def _submit_from_wire(self, header: dict, body: bytearray):
        """Reconstruct the frame pair as zero-copy views of the
        received body and enqueue it. The body holds image1 then
        image2 back to back in the wire dtype (uint8 when both frames
        qualified — the PR 12/13 1-byte/channel path — else float32);
        ``np.frombuffer`` views go straight into the engine's staging
        arena without a dtype round-trip or a copy."""
        shape = tuple(int(v) for v in header["shape"])
        dtype = np.dtype(header.get("dtype", "float32"))
        split = int(header["split"])
        n = int(np.prod(shape))
        im1 = np.frombuffer(body, dtype=dtype, count=n,
                            offset=0).reshape(shape)
        im2 = np.frombuffer(body, dtype=dtype, count=n,
                            offset=split).reshape(shape)
        self.computes += 1          # the one-compute audit counter
        return self.engine.submit(
            im1, im2,
            priority=header.get("priority", PRIORITY_HIGH),
            iters=header.get("iters"),
            trace_id=header.get("trace_id"),
            deadline_s=header.get("deadline"))

    # -- SDC sentinel ----------------------------------------------------

    def _golden_pair(self) -> Tuple[np.ndarray, np.ndarray]:
        """A deterministic frame pair at the first configured bucket
        shape — exactly a warmed executable's shape, so the self-check
        can never justify a fresh compile."""
        h, w = (int(v) for v in self.config.buckets[0])
        rng = np.random.RandomState(0)
        im1 = rng.randint(0, 256, size=(h, w, 3)).astype(np.uint8)
        im2 = rng.randint(0, 256, size=(h, w, 3)).astype(np.uint8)
        return im1, im2

    def _self_check_flow(self, im1: np.ndarray,
                         im2: np.ndarray) -> np.ndarray:
        """One golden-pair inference at HIGH priority (the brownout
        ladder never cheapens HIGH, so the reference stays bit-exact
        even while the overload valve is engaged)."""
        fut = self.engine.submit(
            im1, im2, priority=PRIORITY_HIGH,
            trace_id=f"sdc-{self.config.worker_id}-{self._self_checks}")
        timeout = max(30.0, 10 * self.config.self_check_interval_s)
        return np.asarray(fut.result(timeout=timeout), dtype=np.float32)

    def _quarantine(self, reason: str) -> None:
        logger.error("SDC sentinel failed: %s — quarantining worker %s",
                     reason, self.config.worker_id)
        self.quarantine_reason = reason
        self._quarantined = True
        self._publish_lease()       # go QUARANTINED now, not next beat

    def _sentinel_loop(self) -> None:
        """Periodic silent-data-corruption self-check: golden pair →
        finite + EPE drift band vs the post-warmup reference + zero
        fresh compiles (the HotReloader canary's acceptance gates,
        pointed at the *hardware/runtime* instead of a new model). Any
        failure is terminal for this process: flip the lease to
        QUARANTINED and let the supervisor recycle us."""
        im1, im2 = self._golden_pair()
        try:
            self._sentinel_ref = self._self_check_flow(im1, im2)
        except Exception as e:
            # Can't even establish a reference post-warmup: that is
            # itself a failed self-check.
            self._quarantine(f"reference inference failed: {e}")
            return
        if not np.all(np.isfinite(self._sentinel_ref)):
            self._quarantine("non-finite reference flow")
            return
        while not self._stop.wait(self.config.self_check_interval_s):
            if self._quarantined or self._draining:
                return
            self._self_checks += 1
            seq = self._self_checks
            base = (self._compile_watch.so_far
                    if self._compile_watch is not None else 0)
            try:
                flow = self._self_check_flow(im1, im2)
            except Exception as e:
                self._quarantine(f"self-check {seq} failed: {e}")
                return
            inj = resilience.active_injector()
            if inj is not None and inj.corrupts_self_check(seq):
                # Injected SDC: flip bits in the computed answer
                # before the comparison — the corruption is in the
                # output, the detection must be the sentinel's.
                logger.warning("injected SDC on self-check %d", seq)
                flow = flow + np.float32(1e6)
            compiles = ((self._compile_watch.so_far
                         if self._compile_watch is not None else 0)
                        - base)
            if not np.all(np.isfinite(flow)):
                self._quarantine(f"self-check {seq}: non-finite flow")
                return
            epe = float(np.mean(np.sqrt(np.sum(
                (flow - self._sentinel_ref) ** 2, axis=-1))))
            if epe > self.config.self_check_max_epe:
                self._quarantine(
                    f"self-check {seq}: EPE drift {epe:.3f} > "
                    f"{self.config.self_check_max_epe}")
                return
            if compiles > 0:
                self._quarantine(
                    f"self-check {seq}: {compiles} fresh compile(s) "
                    "on a warmed bucket shape")
                return


# -- process entry points -----------------------------------------------

def spawn_worker(spec: Dict[str, object],
                 env: Optional[Dict[str, str]] = None
                 ) -> subprocess.Popen:
    """Launch one worker process from a :class:`WorkerConfig` dict.

    The spec is written to ``<lease_dir>/<worker_id>.spec.json`` and
    the child runs ``python -m raft_tpu.serving.worker --spec <path>``
    with the parent's environment (``JAX_PLATFORMS`` — CPU in tests,
    TPU in production — and any ``RAFT_FAULT_*`` knobs inherit; pass
    ``env`` to override). stdout/stderr land in
    ``<lease_dir>/<worker_id>.log`` for post-mortems."""
    cfg = WorkerConfig.from_dict(spec)
    os.makedirs(cfg.lease_dir, exist_ok=True)
    spec_path = os.path.join(cfg.lease_dir, f"{cfg.worker_id}.spec.json")
    with open(spec_path, "w") as f:
        json.dump(cfg.to_dict(), f)
    child_env = dict(os.environ if env is None else env)
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    child_env["PYTHONPATH"] = (
        repo_root + os.pathsep + child_env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    log_path = os.path.join(cfg.lease_dir, f"{cfg.worker_id}.log")
    log_f = open(log_path, "ab")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "raft_tpu.serving.worker",
             "--spec", spec_path],
            env=child_env, stdout=log_f, stderr=subprocess.STDOUT)
    finally:
        log_f.close()               # the child holds its own fd


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--spec", required=True,
                   help="path to a WorkerConfig JSON spec")
    args = p.parse_args(argv)
    with open(args.spec) as f:
        cfg = WorkerConfig.from_dict(json.load(f))
    # Env-driven fault injection scopes to this process like the PR-3
    # checkpoint knobs: the supervisor exports RAFT_FAULT_WORKER_* and
    # each worker resolves its own injector.
    resilience.set_injector(resilience.FaultInjector.from_env())

    from raft_tpu.evaluate import load_predictor
    from raft_tpu.serving.engine import ServingConfig, ServingEngine

    predictor = load_predictor(cfg.model_path, small=cfg.small,
                               iters=cfg.iters)
    engine = ServingEngine(predictor, ServingConfig(
        max_batch=cfg.max_batch,
        max_wait_ms=cfg.max_wait_ms,
        buckets=tuple(tuple(b) for b in cfg.buckets),
        queue_timeout_ms=cfg.queue_timeout_ms,
        replica_id=cfg.worker_id,
        persistent_cache=cfg.persistent_cache,
        iters_ladder=cfg.iters_ladder,
        brownout_high_water=cfg.brownout_high_water,
        brownout_low_water=cfg.brownout_low_water,
        brownout_dwell_ms=cfg.brownout_dwell_ms))
    stop = threading.Event()
    # A drain directive ends the process the same way SIGTERM does —
    # except the server already finished in-flight work, closed the
    # engine and removed its lease before firing this. Exit code 0 is
    # the drain contract the supervisor keys on (directed departure,
    # not a crash).
    server = WorkerServer(engine, cfg, on_drained=stop.set)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    server.start(warmup=True)
    logger.info("worker %s serving on %s (pid %d)",
                cfg.worker_id, server.addr, os.getpid())
    try:
        while not stop.is_set():
            stop.wait(0.5)
    finally:
        if not server.drained.is_set():
            server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
