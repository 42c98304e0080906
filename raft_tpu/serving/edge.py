"""Public HTTP/1.1 front door over the serving gateway.

A robustness layer first, a protocol adapter second: the one tier that
must absorb hostile, malformed, slow, and overwhelming traffic without
any of it reaching the engine's zero-compile hot path. Stdlib-only
(``asyncio.start_server`` + hand-rolled request parsing — the same
dependency posture as :mod:`~raft_tpu.serving.netproto`), fronting
:meth:`~raft_tpu.serving.gateway.ServingGateway.submit`.

**The wire contract.** ``POST /v1/flow`` with a binary body that is
``image1`` bytes immediately followed by ``image2`` bytes (C-order,
equal shapes), described by headers:

* ``X-Shape: H,W,C`` — per-image shape (both images).
* ``X-Dtype: uint8|float32`` — per-image dtype (default ``uint8``).
* ``X-Priority: high|low`` — scheduling class (default ``high``).
* ``X-Iters: N`` — optional refinement-iteration override.
* ``X-Deadline-Ms: N`` — the client's remaining budget. Converted
  ONCE to the absolute monotonic deadline :mod:`netproto` already
  carries, then enforced at every hop (edge admission, gateway queue,
  worker admission, engine queue gate). ``N <= 0`` → immediate 504.
* ``X-Client-Id`` — quota key (falls back to the peer address).
* ``X-Request-Id`` — optional client-supplied idempotency key
  (``[A-Za-z0-9._-]``, at most 128 chars; anything else is a 400).
  Threaded verbatim onto the gateway's wire-level idempotency key, so
  a client retrying a 5xx under the same id dedupes at the worker
  instead of recomputing. Absent, the edge mints one. Echoed on every
  ``/v1/flow`` response — success or error — alongside ``X-Trace-Id``.

A 200 carries the float32 ``(H, W, 2)`` flow as
``application/octet-stream`` with its own ``X-Shape``/``X-Dtype`` and
the ``X-Trace-Id`` of the gateway trace it rode. Every error is a JSON
body ``{"error": <class>, "message": ...}`` with ``Connection: close``:

========================  ======  =====================================
status                    class   when
========================  ======  =====================================
400 ``malformed``                 unparseable request line/headers,
                                  bad shape/dtype/length arithmetic
404 ``not_found``                 unknown target
413 ``payload_too_large``         body over ``max_body_bytes``
429 ``over_quota``                per-client token bucket empty
                                  (``Retry-After`` from the refill)
429 ``backlog_full``              the engine's admission backlog shed
503 ``admission_full``            global concurrency cap reached
503 ``overload_shed``             gateway pressure gauges over water
503 ``engine_unhealthy``          no routable worker / typed failure
503 ``draining``                  shutdown in progress
504 ``deadline_expired``          budget spent before dispatch
504 ``timeout``                   budget spent after dispatch
500 ``internal``                  anything else
========================  ======  =====================================

**Admission order.** Quota → concurrency → pressure-shed → deadline,
all decided from the request HEAD — an over-quota, overloaded, or
expired request is answered before a byte of image data is staged and
without ever reaching ``ServingGateway.submit``. The pressure signals
are the gateway's own registry gauges (``gateway_queue_depth``,
``gateway_fleet_occupancy``) — exactly what the autoscaler reads, so
the shed threshold and the scale-up threshold argue over one number.

**Abuse hardening.** Bounded header (``max_header_bytes``) and body
(``max_body_bytes``) sizes; a read deadline reaps slowloris clients
(mirroring ``WorkerServer.conn_read_timeout_s`` on the binary
protocol) and a write deadline reaps clients that stop reading their
response; a client that disconnects mid-response costs one counter
tick and nothing else — the gateway future resolves into the void.
Every rejection class is counted on the PR-14 registry
(``edge_errors{class=...}``), and each proxied request runs under an
``edge_request`` root span sharing the gateway-minted ``trace_id``.

**Coordinated shutdown.** :meth:`EdgeServer.shutdown` drains in order:
``/readyz`` flips unready → (grace for LB probes) → listener closes →
in-flight edge requests finish (bounded) → gateway closes → the worker
fleet drains (via the supervisor's
:meth:`~raft_tpu.serving.supervisor.WorkerSupervisor.drain_fleet`).
:meth:`EdgeServer.install_sigterm_handler` wires the whole sequence to
SIGTERM; the ordering is recorded in ``shutdown_events`` so drills and
tests assert it rather than trust it.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import math
import signal
import socket
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from raft_tpu import resilience
from raft_tpu.observability import tracer as tracing
from raft_tpu.serving.batcher import (BacklogFull, PRIORITY_HIGH,
                                      RequestTimedOut)
from raft_tpu.serving.health import EngineUnhealthy

logger = logging.getLogger(__name__)

_CRLF = b"\r\n"
_HEAD_END = b"\r\n\r\n"
_DTYPES = ("uint8", "float32")
_PRIORITIES = ("high", "low")

# Client-supplied idempotency keys ride the wire protocol and land in
# worker-side cache maps and trace args: a bounded, conservative
# charset keeps a hostile header from becoming a log/trace injection
# or an unbounded-allocation vector.
_REQUEST_ID_MAX = 128
_REQUEST_ID_CHARS = frozenset(
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
    "0123456789._-")


def _parse_request_id(headers: Dict[str, str]) -> Optional[str]:
    """Validate an optional ``X-Request-Id``; malformed → 400 per the
    classification, absent → ``None`` (the edge mints one)."""
    raw = headers.get("x-request-id", "").strip()
    if not raw:
        return None
    if len(raw) > _REQUEST_ID_MAX:
        raise _Reject(400, "malformed",
                      f"X-Request-Id exceeds {_REQUEST_ID_MAX} chars")
    if not set(raw) <= _REQUEST_ID_CHARS:
        raise _Reject(400, "malformed",
                      "X-Request-Id may contain only [A-Za-z0-9._-]")
    return raw

_STATUS_TEXT = {
    200: "OK", 400: "Bad Request", 404: "Not Found",
    413: "Payload Too Large", 429: "Too Many Requests",
    431: "Request Header Fields Too Large", 500: "Internal Server Error",
    503: "Service Unavailable", 504: "Gateway Timeout",
}


class _Reject(Exception):
    """An admission/parse rejection: carries the response verbatim."""

    def __init__(self, status: int, err_class: str, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.status = status
        self.err_class = err_class
        self.retry_after_s = retry_after_s


def classify_error(exc: BaseException) -> Tuple[int, str]:
    """The typed classification mapping every gateway outcome to an HTTP
    status + error class. ``RequestTimedOut`` is the spent budget
    (504), ``EngineUnhealthy`` the fleet saying no (503), and
    ``BacklogFull`` — whether raised directly or surfaced as the
    gateway's typed post-acceptance error string — is pushback the
    client should retry (429)."""
    if isinstance(exc, RequestTimedOut):
        return 504, "timeout"
    if isinstance(exc, BacklogFull):
        return 429, "backlog_full"
    if isinstance(exc, EngineUnhealthy):
        return 503, "engine_unhealthy"
    if "BacklogFull" in str(exc):
        return 429, "backlog_full"
    return 500, "internal"


class TokenBucket:
    """One client's quota: ``rate`` tokens/s refill up to ``burst``.

    Clock-injectable (monotonic). :meth:`acquire` returns
    ``(granted, retry_after_s)`` — on refusal ``retry_after_s`` is the
    exact refill time until one whole token exists, which is what the
    429's ``Retry-After`` advertises."""

    def __init__(self, rate: float, burst: float,
                 clock: Callable[[], float] = time.monotonic):
        self.rate = float(rate)
        self.burst = float(burst)
        self._clock = clock
        self.tokens = self.burst
        self._t_last = clock()

    def acquire(self, n: float = 1.0) -> Tuple[bool, float]:
        now = self._clock()
        self.tokens = min(self.burst,
                          self.tokens + (now - self._t_last) * self.rate)
        self._t_last = now
        if self.tokens >= n:
            self.tokens -= n
            return True, 0.0
        return False, (n - self.tokens) / self.rate


@dataclasses.dataclass(frozen=True)
class EdgeConfig:
    """Knobs for one :class:`EdgeServer`.

    Attributes:
      host / port: listener bind address. Loopback + ephemeral by
        default (tests); a public deployment binds an interface
        address. ``port=0`` publishes the bound port via ``addr``.
      max_concurrent: global in-flight request cap (the admission
        semaphore); the cap'th+1 concurrent proxied request is
        answered 503 ``admission_full`` instead of queueing — the
        gateway owns the queue, the edge only sheds.
      quota_rps / quota_burst: per-client token-bucket quota
        (``quota_rps`` tokens/s refill up to ``quota_burst``).
        ``quota_rps=0`` disables quotas.
      client_key_header: header naming the quota key; absent, the
        peer's IP is the key.
      shed_queue_depth: gateway queue depth at/above which proxied
        requests shed 503 (0 disables).
      shed_occupancy: fleet mean occupancy at/above which proxied
        requests shed 503 (0 disables).
      max_header_bytes / max_body_bytes: frame bounds; over-size heads
        are 431, over-size bodies 413.
      header_read_timeout_s: deadline for a complete request HEAD —
        the slowloris reaper (mirrors
        ``WorkerServer.conn_read_timeout_s``).
      body_read_timeout_s: deadline for the declared body bytes.
      write_timeout_s: deadline for draining a response to the client.
      default_deadline_ms: budget stamped on requests that carry no
        ``X-Deadline-Ms`` (0 → defer to the gateway's
        ``queue_timeout_ms``).
      drain_grace_s: seconds ``/readyz`` reports unready BEFORE the
        listener closes during shutdown — the window a load balancer
        needs to stop sending traffic to a door about to shut.
      drain_timeout_s: bound on waiting for in-flight edge requests
        during shutdown.
    """

    host: str = "127.0.0.1"
    port: int = 0
    max_concurrent: int = 64
    quota_rps: float = 0.0
    quota_burst: float = 10.0
    client_key_header: str = "x-client-id"
    shed_queue_depth: int = 0
    shed_occupancy: float = 0.0
    max_header_bytes: int = 16384
    max_body_bytes: int = 1 << 26
    header_read_timeout_s: float = 10.0
    body_read_timeout_s: float = 30.0
    write_timeout_s: float = 30.0
    default_deadline_ms: int = 0
    drain_grace_s: float = 0.0
    drain_timeout_s: float = 30.0


class EdgeServer:
    """The asyncio HTTP/1.1 listener in front of one gateway.

    ``gateway`` needs the :class:`~raft_tpu.serving.gateway
    .ServingGateway` surface: ``submit(...)`` → future, ``registry``
    (pressure gauges + edge counters), ``live_workers()`` (readiness
    rollup) and ``close()``. ``clock`` is the monotonic domain shared
    with the gateway (deadlines); ``drain_workers`` is the optional
    final shutdown leg (typically
    ``lambda: supervisor.drain_fleet(transport)``).

    Run it on an existing event loop (``await edge.start()`` /
    ``await edge.shutdown()``) or from synchronous code via
    :meth:`start_in_thread` / :meth:`shutdown_sync`, which own a
    daemon event-loop thread."""

    def __init__(self, gateway, config: Optional[EdgeConfig] = None,
                 registry=None, clock: Callable[[], float] = time.monotonic,
                 drain_workers: Optional[Callable[[], object]] = None):
        self.gateway = gateway
        self.config = config or EdgeConfig()
        self.registry = (registry if registry is not None
                         else gateway.registry)
        self._clock = clock
        self._drain_workers = drain_workers
        self._tracer = tracing.current()
        self.addr: Optional[Tuple[str, int]] = None
        self._server: Optional[asyncio.AbstractServer] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._inflight = 0          # proxied requests in flight
        #: Open client connections, so the drain can close the ones
        #: still lingering at its deadline.
        self._conns: set = set()
        self._buckets: Dict[str, TokenBucket] = {}
        self._draining = False
        self._closed = False
        self._shutdown_started = False
        #: Ordered record of the coordinated-shutdown legs — drills
        #: assert the sequence instead of trusting it.
        self.shutdown_events: List[str] = []
        self.slow_client_drops = 0  # connections reaped by a deadline
        self.client_aborts = 0      # peers gone mid-request/-response
        r = self.registry
        self._c_requests = r.counter(
            "edge_requests", help="HTTP requests parsed at the edge")
        self._c_responses = r.counter(
            "edge_responses", help="HTTP responses written, by status",
            labelnames=("status",))
        self._c_errors = r.counter(
            "edge_errors", help="edge rejections/failures, by class",
            labelnames=("class",))
        r.gauge("edge_inflight",
                help="proxied requests currently in flight at the edge",
                fn=lambda: float(self._inflight))
        r.gauge("edge_ready",
                help="1 while /readyz would answer 200",
                fn=lambda: 1.0 if self._ready() else 0.0)

    # -- lifecycle -------------------------------------------------------

    async def start(self) -> "EdgeServer":
        if self._server is not None:
            raise RuntimeError("edge already started")
        self._loop = asyncio.get_running_loop()
        self._server = await asyncio.start_server(
            self._serve_conn, self.config.host, self.config.port,
            limit=max(self.config.max_header_bytes * 2, 1 << 16))
        self.addr = self._server.sockets[0].getsockname()[:2]
        logger.info("edge listening on %s:%d", *self.addr)
        return self

    async def shutdown(self, drain_timeout_s: Optional[float] = None
                       ) -> None:
        """The coordinated drain: unready → (grace) → stop accepting →
        in-flight edge requests finish (bounded) → gateway closes →
        workers drain. Idempotent."""
        if self._shutdown_started:
            return
        self._shutdown_started = True
        self._draining = True       # /readyz now answers 503
        self._event("unready")
        if self.config.drain_grace_s:
            await asyncio.sleep(self.config.drain_grace_s)
        # Close the listener only. ``wait_closed()`` also waits for every
        # open connection (Python >= 3.12.1), so awaiting it here would
        # let one wedged request hold the drain past its deadline.
        if self._server is not None:
            self._server.close()
        self._event("listener_closed")
        bound = (self.config.drain_timeout_s
                 if drain_timeout_s is None else drain_timeout_s)
        deadline = self._clock() + bound
        while self._inflight > 0 and self._clock() < deadline:
            await asyncio.sleep(0.02)
        if self._inflight:
            logger.warning("edge drain deadline hit with %d request(s) "
                           "still in flight", self._inflight)
        self._event("edge_drained")
        self._closed = True
        for writer in list(self._conns):
            writer.close()
        try:
            self.gateway.close()
        except Exception:
            logger.exception("gateway close failed during edge drain")
        self._event("gateway_closed")
        if self._drain_workers is not None:
            loop = asyncio.get_running_loop()
            try:
                await loop.run_in_executor(None, self._drain_workers)
            except Exception:
                logger.exception("worker drain failed during shutdown")
            self._event("workers_drained")

    def _event(self, name: str) -> None:
        self.shutdown_events.append(name)
        logger.info("edge shutdown: %s", name)

    # -- sync wrappers (drills, bench, tests) ----------------------------

    def start_in_thread(self) -> "EdgeServer":
        """Run the edge on a private daemon event-loop thread; returns
        once the listener is bound (``self.addr`` valid)."""
        if self._thread is not None:
            raise RuntimeError("edge thread already started")
        started = threading.Event()
        failure: List[BaseException] = []

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            try:
                loop.run_until_complete(self.start())
            except BaseException as e:   # surface bind errors
                failure.append(e)
                started.set()
                return
            started.set()
            loop.run_forever()
            loop.close()

        self._thread = threading.Thread(target=run, name="edge-loop",
                                        daemon=True)
        self._thread.start()
        started.wait()
        if failure:
            raise failure[0]
        return self

    def shutdown_sync(self, timeout: float = 60.0) -> None:
        """Run :meth:`shutdown` from synchronous code (the loop thread
        keeps spinning until the drain finished, then stops)."""
        if self._loop is None:
            return
        fut = asyncio.run_coroutine_threadsafe(self.shutdown(),
                                               self._loop)
        try:
            fut.result(timeout)
        finally:
            if self._thread is not None:
                self._loop.call_soon_threadsafe(self._loop.stop)
                self._thread.join(timeout=5.0)
                self._thread = None

    def install_sigterm_handler(self) -> None:
        """Wire the coordinated drain to SIGTERM (main thread only —
        the handler hands off to a worker thread so the signal frame
        returns immediately)."""
        def _on_term(signum, frame):
            logger.info("SIGTERM: starting coordinated edge drain")
            threading.Thread(target=self.shutdown_sync,
                             name="edge-sigterm-drain",
                             daemon=True).start()
        signal.signal(signal.SIGTERM, _on_term)

    # -- readiness -------------------------------------------------------

    def _ready(self) -> bool:
        """Fleet-rollup readiness: accepting AND at least one routable
        worker. Unready the instant a drain starts — before the
        listener closes — so load balancers stop sending."""
        if self._draining or self._closed:
            return False
        try:
            return bool(self.gateway.live_workers())
        except Exception:
            return False

    def _read_gauge(self, name: str, agg=max) -> float:
        """The autoscaler's gauge-read contract verbatim: missing
        instrument or a torn collect reads 0.0."""
        inst = self.registry.instruments().get(name)
        if inst is None:
            return 0.0
        try:
            values = inst.collect()
        except Exception:
            return 0.0
        if not values:
            return 0.0
        return float(agg(values.values()))

    # -- the connection loop ---------------------------------------------

    async def _serve_conn(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        self._conns.add(writer)
        try:
            while not self._closed:
                keep_alive = await self._serve_one(reader, writer)
                if not keep_alive:
                    break
        except (ConnectionError, asyncio.IncompleteReadError):
            self.client_aborts += 1
            self._c_errors.inc(**{"class": "client_abort"})
        except Exception:
            logger.exception("edge connection handler failed")
        finally:
            self._conns.discard(writer)
            try:
                writer.close()
            except Exception:
                pass

    async def _serve_one(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> bool:
        """Read + answer one request; returns whether to keep the
        connection. Every early exit writes exactly one response (or
        reaps the connection silently for slowloris peers)."""
        try:
            head = await asyncio.wait_for(
                reader.readuntil(_HEAD_END),
                self.config.header_read_timeout_s or None)
        except asyncio.TimeoutError:
            # Slowloris: a peer that cannot produce one complete HEAD
            # within the deadline is reaped, not waited on.
            self.slow_client_drops += 1
            self._c_errors.inc(**{"class": "slowloris"})
            return False
        except asyncio.IncompleteReadError as e:
            if e.partial:
                self.client_aborts += 1
                self._c_errors.inc(**{"class": "client_abort"})
            return False            # clean EOF between requests
        except asyncio.LimitOverrunError:
            await self._respond_error(writer, _Reject(
                431, "header_too_large",
                f"request head exceeds {self.config.max_header_bytes} "
                "bytes"))
            return False
        except ConnectionError:
            self.client_aborts += 1
            self._c_errors.inc(**{"class": "client_abort"})
            return False
        if len(head) > self.config.max_header_bytes:
            await self._respond_error(writer, _Reject(
                431, "header_too_large",
                f"request head exceeds {self.config.max_header_bytes} "
                "bytes"))
            return False
        self._c_requests.inc()
        try:
            method, target, headers = _parse_head(head)
        except _Reject as rej:
            await self._respond_error(writer, rej)
            return False
        if method == "GET" and target == "/healthz":
            await self._respond_json(writer, 200, {"status": "alive"})
            return True
        if method == "GET" and target == "/readyz":
            ready = self._ready()
            await self._respond_json(
                writer, 200 if ready else 503,
                {"status": "ready" if ready else "unready",
                 "draining": self._draining,
                 "workers_live": self._read_gauge(
                     "gateway_workers_live")})
            return True
        if not (method == "POST" and target == "/v1/flow"):
            await self._respond_error(writer, _Reject(
                404, "not_found", f"no route for {method} {target}"))
            return False
        try:
            request_id = _parse_request_id(headers)
        except _Reject as rej:
            await self._respond_error(writer, rej)
            return False
        # Minted here when the client supplied none, so EVERY /v1/flow
        # response — success or rejection — can echo the key the wire
        # request will carry (a client retrying on it dedupes at the
        # worker).
        request_id = request_id or uuid.uuid4().hex
        try:
            return await self._serve_flow(reader, writer, headers,
                                          request_id)
        except _Reject as rej:
            self._c_errors.inc(**{"class": rej.err_class})
            await self._respond_error(writer, rej, counted=True,
                                      request_id=request_id)
            return False

    # -- the proxied request ---------------------------------------------

    def _admit(self, headers: Dict[str, str], peer: str
               ) -> Optional[float]:
        """The pre-body admission gauntlet: quota → concurrency →
        pressure → deadline, decided from the HEAD alone. Returns the
        absolute monotonic deadline (or ``None``); raises
        :class:`_Reject` with the documented status otherwise —
        ``ServingGateway.submit`` is never reached."""
        if self._draining or self._closed:
            raise _Reject(503, "draining",
                          "edge is draining; not accepting work")
        cfg = self.config
        if cfg.quota_rps > 0:
            key = headers.get(cfg.client_key_header, "").strip() or peer
            bucket = self._buckets.get(key)
            if bucket is None:
                bucket = self._buckets[key] = TokenBucket(
                    cfg.quota_rps, cfg.quota_burst, self._clock)
            ok, retry_after = bucket.acquire()
            if not ok:
                raise _Reject(
                    429, "over_quota",
                    f"client {key!r} over quota "
                    f"({cfg.quota_rps:g} req/s, burst "
                    f"{cfg.quota_burst:g}); retry after "
                    f"{retry_after:.3f}s", retry_after_s=retry_after)
        if self._inflight >= cfg.max_concurrent:
            raise _Reject(503, "admission_full",
                          f"{cfg.max_concurrent} requests already in "
                          "flight", retry_after_s=1.0)
        if cfg.shed_queue_depth > 0:
            depth = self._read_gauge("gateway_queue_depth")
            if depth >= cfg.shed_queue_depth:
                raise _Reject(503, "overload_shed",
                              f"gateway queue depth {depth:g} at/over "
                              f"shed watermark {cfg.shed_queue_depth}",
                              retry_after_s=1.0)
        if cfg.shed_occupancy > 0:
            occ = self._read_gauge("gateway_fleet_occupancy")
            if occ >= cfg.shed_occupancy:
                raise _Reject(503, "overload_shed",
                              f"fleet occupancy {occ:g} at/over shed "
                              f"watermark {cfg.shed_occupancy:g}",
                              retry_after_s=1.0)
        raw_ms = headers.get("x-deadline-ms", "").strip()
        if raw_ms:
            try:
                budget_ms = int(raw_ms)
            except ValueError:
                raise _Reject(400, "malformed",
                              f"unparseable X-Deadline-Ms: {raw_ms!r}")
            if budget_ms <= 0:
                # The client's own header says the budget is spent:
                # answering 504 now is cheaper (and more honest) than
                # dispatching work whose answer must arrive late.
                raise _Reject(504, "deadline_expired",
                              f"X-Deadline-Ms {budget_ms} already "
                              "spent")
        else:
            budget_ms = self.config.default_deadline_ms
            if budget_ms <= 0:
                return None         # gateway's queue_timeout_ms applies
        # THE conversion: header milliseconds → absolute monotonic
        # deadline, once, here. Everything downstream (gateway queue,
        # transport hops, worker admission, engine gate) compares
        # against this same number.
        return self._clock() + budget_ms / 1e3

    def _parse_flow_meta(self, headers: Dict[str, str]
                         ) -> Tuple[Tuple[int, int, int], str, str,
                                    Optional[int], int]:
        """Validate the flow-request metadata headers; malformed → 400
        before any body byte is read."""
        raw_shape = headers.get("x-shape", "")
        try:
            shape = tuple(int(v) for v in raw_shape.split(","))
        except ValueError:
            raise _Reject(400, "malformed",
                          f"unparseable X-Shape: {raw_shape!r}")
        if len(shape) != 3 or any(v <= 0 for v in shape):
            raise _Reject(400, "malformed",
                          f"X-Shape must be positive 'H,W,C', got "
                          f"{raw_shape!r}")
        dtype = headers.get("x-dtype", "uint8").strip().lower()
        if dtype not in _DTYPES:
            raise _Reject(400, "malformed",
                          f"X-Dtype must be one of {_DTYPES}, got "
                          f"{dtype!r}")
        priority = headers.get("x-priority", PRIORITY_HIGH).strip()
        if priority not in _PRIORITIES:
            raise _Reject(400, "malformed",
                          f"X-Priority must be one of {_PRIORITIES}, "
                          f"got {priority!r}")
        iters: Optional[int] = None
        raw_iters = headers.get("x-iters", "").strip()
        if raw_iters:
            try:
                iters = int(raw_iters)
            except ValueError:
                raise _Reject(400, "malformed",
                              f"unparseable X-Iters: {raw_iters!r}")
            if iters <= 0:
                raise _Reject(400, "malformed",
                              f"X-Iters must be positive, got {iters}")
        raw_len = headers.get("content-length", "")
        try:
            clen = int(raw_len)
        except ValueError:
            raise _Reject(400, "malformed",
                          f"missing/unparseable Content-Length: "
                          f"{raw_len!r}")
        if clen > self.config.max_body_bytes:
            raise _Reject(413, "payload_too_large",
                          f"body of {clen} bytes exceeds cap "
                          f"{self.config.max_body_bytes}")
        expect = 2 * int(np.prod(shape)) * np.dtype(dtype).itemsize
        if clen != expect:
            raise _Reject(400, "malformed",
                          f"Content-Length {clen} != 2 x {shape} "
                          f"{dtype} = {expect} bytes")
        return shape, dtype, priority, iters, clen

    async def _serve_flow(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter,
                          headers: Dict[str, str],
                          request_id: str) -> bool:
        peername = writer.get_extra_info("peername") or ("?", 0)
        deadline = self._admit(headers, str(peername[0]))
        shape, dtype, priority, iters, clen = \
            self._parse_flow_meta(headers)
        # Only now — with quota, capacity, pressure, deadline and frame
        # arithmetic all cleared — do image bytes get staged.
        try:
            body = await asyncio.wait_for(
                reader.readexactly(clen),
                self.config.body_read_timeout_s or None)
        except asyncio.TimeoutError:
            self.slow_client_drops += 1
            self._c_errors.inc(**{"class": "slowloris"})
            return False
        except (asyncio.IncompleteReadError, ConnectionError):
            self.client_aborts += 1
            self._c_errors.inc(**{"class": "client_abort"})
            return False
        half = clen // 2
        im1 = np.frombuffer(body, dtype=dtype, count=int(np.prod(shape)),
                            offset=0).reshape(shape)
        im2 = np.frombuffer(body, dtype=dtype, count=int(np.prod(shape)),
                            offset=half).reshape(shape)
        tr = self._tracer
        tid = tr.mint() if tr is not None else None
        if tr is not None:
            tr.begin_async("edge_request", tid,
                           args={"priority": priority,
                                 "shape": list(shape)})
        self._inflight += 1
        status, err_class = 200, ""
        try:
            try:
                fut = self.gateway.submit(im1, im2, priority=priority,
                                          iters=iters, trace_id=tid,
                                          deadline=deadline,
                                          request_id=request_id)
            except Exception as e:
                status, err_class = classify_error(e)
                await self._respond_error(writer, _Reject(
                    status, err_class, str(e)), request_id=request_id)
                return False
            wait = None
            if deadline is not None:
                # The gateway owns deadline enforcement; the extra
                # second only catches a wedged resolution path.
                wait = max(deadline - self._clock(), 0.0) + 1.0
            try:
                flow = await asyncio.wait_for(asyncio.wrap_future(fut),
                                              wait)
            except asyncio.TimeoutError:
                fut.cancel()
                status, err_class = 504, "timeout"
                await self._respond_error(writer, _Reject(
                    status, err_class,
                    "deadline expired awaiting the gateway"),
                    request_id=request_id)
                return False
            except Exception as e:
                status, err_class = classify_error(e)
                await self._respond_error(writer, _Reject(
                    status, err_class, str(e)), request_id=request_id)
                return False
            if reader.at_eof():
                # The client hung up while its answer was computed
                # (edge clients never half-close): count it and move
                # on — the result is already safely resolved, nothing
                # downstream is poisoned.
                self.client_aborts += 1
                self._c_errors.inc(**{"class": "client_abort"})
                return False
            out = np.ascontiguousarray(flow, dtype=np.float32)
            resp_headers = [
                ("Content-Type", "application/octet-stream"),
                ("X-Shape", ",".join(str(v) for v in out.shape)),
                ("X-Dtype", "float32"),
                ("X-Request-Id", request_id),
            ]
            if tid is not None:
                resp_headers.append(("X-Trace-Id", str(tid)))
            try:
                await self._write_response(writer, 200, resp_headers,
                                           out.tobytes())
            except (ConnectionError, asyncio.TimeoutError):
                # The client hung up (or stopped reading) while its
                # answer was in flight: one counter tick, nothing
                # poisoned — the gateway already resolved the future.
                self.client_aborts += 1
                self._c_errors.inc(**{"class": "client_abort"})
                return False
            self._c_responses.inc(status="200")
            return True
        finally:
            self._inflight -= 1
            if tr is not None:
                tr.end_async("edge_request", tid,
                             args={"status": status,
                                   "class": err_class or "ok"})

    # -- response writing ------------------------------------------------

    async def _write_response(self, writer: asyncio.StreamWriter,
                              status: int,
                              headers: List[Tuple[str, str]],
                              body: bytes) -> None:
        text = _STATUS_TEXT.get(status, "Unknown")
        out = [f"HTTP/1.1 {status} {text}".encode("ascii")]
        out.extend(f"{k}: {v}".encode("ascii") for k, v in headers)
        out.append(f"Content-Length: {len(body)}".encode("ascii"))
        writer.write(_CRLF.join(out) + _HEAD_END + body)
        await asyncio.wait_for(writer.drain(),
                               self.config.write_timeout_s or None)

    async def _respond_json(self, writer: asyncio.StreamWriter,
                            status: int, payload: dict,
                            extra_headers: Optional[
                                List[Tuple[str, str]]] = None) -> None:
        body = json.dumps(payload).encode("utf-8")
        headers = [("Content-Type", "application/json")]
        if extra_headers:
            headers.extend(extra_headers)
        try:
            await self._write_response(writer, status, headers, body)
        except (ConnectionError, asyncio.TimeoutError):
            self.client_aborts += 1
        self._c_responses.inc(status=str(status))

    async def _respond_error(self, writer: asyncio.StreamWriter,
                             rej: _Reject,
                             counted: bool = False,
                             request_id: Optional[str] = None) -> None:
        """One JSON error frame per the classification table; closes the
        connection (the caller returns False). ``counted`` marks
        rejections whose class counter the caller already ticked;
        ``request_id`` is echoed so a client can retry the same key."""
        if not counted:
            self._c_errors.inc(**{"class": rej.err_class})
        extra = [("Connection", "close")]
        if request_id is not None:
            extra.append(("X-Request-Id", request_id))
        if rej.retry_after_s is not None:
            extra.append(("Retry-After",
                          str(max(1, math.ceil(rej.retry_after_s)))))
            extra.append(("X-Retry-After-Ms",
                          str(int(rej.retry_after_s * 1000))))
        await self._respond_json(
            writer, rej.status,
            {"error": rej.err_class, "message": str(rej),
             "status": rej.status}, extra_headers=extra)


def _parse_head(head: bytes) -> Tuple[str, str, Dict[str, str]]:
    """Hand-rolled HTTP/1.1 HEAD parse → ``(method, target,
    lowercase-keyed headers)``; anything off-grammar is a 400."""
    try:
        text = head[:-len(_HEAD_END)].decode("latin-1")
    except UnicodeDecodeError:      # latin-1 never fails; belt+braces
        raise _Reject(400, "malformed", "undecodable request head")
    lines = text.split("\r\n")
    parts = lines[0].split(" ")
    if len(parts) != 3 or not parts[2].startswith("HTTP/1."):
        raise _Reject(400, "malformed",
                      f"bad request line: {lines[0]!r}")
    method, target = parts[0], parts[1]
    headers: Dict[str, str] = {}
    for line in lines[1:]:
        if not line:
            continue
        if ":" not in line:
            raise _Reject(400, "malformed",
                          f"bad header line: {line!r}")
        k, v = line.split(":", 1)
        headers[k.strip().lower()] = v.strip()
    return method, target, headers


# -- client helpers (tests, drills, bench) --------------------------------

class ClientAbortInjected(RuntimeError):
    """Raised by :func:`http_request` when the fault injector's
    ``RAFT_FAULT_EDGE_CLIENT_ABORT_NTH`` knob made THIS request hang
    up after sending — the caller knows no response is coming."""


@dataclasses.dataclass
class EdgeResponse:
    status: int
    headers: Dict[str, str]
    body: bytes

    def json(self) -> dict:
        return json.loads(self.body.decode("utf-8"))


_CLIENT_SEQ_LOCK = threading.Lock()


def http_request(addr: Tuple[str, int], method: str = "GET",
                 target: str = "/",
                 headers: Optional[Dict[str, str]] = None,
                 body: bytes = b"",
                 timeout: float = 30.0) -> Optional[EdgeResponse]:
    """Minimal synchronous HTTP/1.1 client for the edge (stdlib
    sockets; one request per call, ``Connection: close``).

    The process fault injector's edge knobs hook here — the injector
    plays the HOSTILE CLIENT on this protocol: an armed
    ``RAFT_FAULT_EDGE_SLOWLORIS_S`` turns this call into a slowloris
    (the request trickles one byte per interval until the edge reaps
    the connection; returns ``None``), and
    ``RAFT_FAULT_EDGE_CLIENT_ABORT_NTH`` makes the Nth request sent
    under that injector hang up right after its bytes (raises
    :class:`ClientAbortInjected`). The send counter lives ON the
    injector instance, so installing a fresh injector restarts the
    count — the same budgets-persist-per-injector rule every other
    knob follows."""
    hdrs = dict(headers or {})
    hdrs.setdefault("Host", f"{addr[0]}:{addr[1]}")
    hdrs.setdefault("Connection", "close")
    if body or method == "POST":
        hdrs["Content-Length"] = str(len(body))
    lines = [f"{method} {target} HTTP/1.1"]
    lines.extend(f"{k}: {v}" for k, v in hdrs.items())
    raw = ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1") + body
    inj = resilience.active_injector()
    sock = socket.create_connection(tuple(addr), timeout=timeout)
    try:
        interval = inj.take_edge_slowloris() if inj is not None else 0.0
        if interval > 0:
            # The injected slowloris: never a complete HEAD. The edge's
            # header read deadline must reap us; a closed/reset socket
            # is the expected (and asserted) outcome.
            try:
                for i in range(len(raw)):
                    sock.sendall(raw[i:i + 1])
                    time.sleep(interval)
                sock.recv(1)
            except OSError:
                pass
            return None
        seq = 0
        if inj is not None:
            with _CLIENT_SEQ_LOCK:
                seq = getattr(inj, "_edge_send_seq", 0) + 1
                inj._edge_send_seq = seq
        sock.sendall(raw)
        if inj is not None and inj.aborts_edge_client(seq):
            raise ClientAbortInjected(
                f"injected client abort on request #{seq}")
        return _read_response(sock)
    finally:
        try:
            sock.close()
        except OSError:
            pass


def _read_response(sock: socket.socket) -> EdgeResponse:
    buf = bytearray()
    while _HEAD_END not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("peer closed before response head")
        buf += chunk
    head, rest = bytes(buf).split(_HEAD_END, 1)
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ", 2)[1])
    headers: Dict[str, str] = {}
    for line in lines[1:]:
        if ":" in line:
            k, v = line.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    clen = int(headers.get("content-length", "0"))
    body = bytearray(rest)
    while len(body) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("peer closed mid-body")
        body += chunk
    return EdgeResponse(status, headers, bytes(body[:clen]))


def submit_flow(addr: Tuple[str, int], image1: np.ndarray,
                image2: np.ndarray, priority: str = PRIORITY_HIGH,
                iters: Optional[int] = None,
                deadline_ms: Optional[int] = None,
                client_id: Optional[str] = None,
                request_id: Optional[str] = None,
                timeout: float = 60.0) -> Optional[EdgeResponse]:
    """Client-side encoding of the ``POST /v1/flow`` contract: two
    same-shape images, C-order bytes back to back. On 200 the decoded
    flow is at ``np.frombuffer(resp.body, np.float32).reshape(
    resp.headers['x-shape'])``."""
    a1 = np.ascontiguousarray(image1)
    a2 = np.ascontiguousarray(image2)
    if a1.shape != a2.shape or a1.dtype != a2.dtype:
        raise ValueError("image1/image2 must share shape and dtype")
    headers = {
        "X-Shape": ",".join(str(v) for v in a1.shape),
        "X-Dtype": str(a1.dtype),
        "X-Priority": priority,
    }
    if iters is not None:
        headers["X-Iters"] = str(iters)
    if deadline_ms is not None:
        headers["X-Deadline-Ms"] = str(deadline_ms)
    if client_id is not None:
        headers["X-Client-Id"] = client_id
    if request_id is not None:
        headers["X-Request-Id"] = request_id
    return http_request(addr, "POST", "/v1/flow", headers,
                        a1.tobytes() + a2.tobytes(), timeout=timeout)


def decode_flow(resp: EdgeResponse) -> np.ndarray:
    """Decode a 200 ``/v1/flow`` response body into its ``(H, W, 2)``
    float32 array."""
    shape = tuple(int(v) for v in resp.headers["x-shape"].split(","))
    return np.frombuffer(resp.body, dtype=np.float32).reshape(shape)
