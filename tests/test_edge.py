"""The HTTP edge: admission control, deadline propagation, abuse
hardening, the typed error classification, and coordinated graceful shutdown.

Admission and shutdown semantics run against a FAKE gateway (recorded
``submit`` calls are the never-reached-the-gateway needle) and, where
the contract spans both tiers, a real :class:`ServingGateway` in
manual-drive mode over the :class:`FakeTransport` from the gateway
tests — ``transport.sent == []`` is the strongest possible "no byte
was dispatched" assertion. The live-socket tests (slowloris reap,
client abort, multi-host bind) use real listeners on loopback; the
full HTTP-clients-over-a-worker-kill proof is the slow-marked
``serve_drill.py --drill edge`` runner at the bottom.
"""

import concurrent.futures
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.observability.registry import MetricsRegistry
from raft_tpu.serving import edge as edge_mod
from raft_tpu.serving.batcher import BacklogFull, RequestTimedOut
from raft_tpu.serving.edge import (ClientAbortInjected, EdgeConfig,
                                   EdgeServer, TokenBucket,
                                   classify_error, decode_flow,
                                   http_request, submit_flow)
from raft_tpu.serving.gateway import GatewayConfig, ServingGateway
from raft_tpu.serving.health import STALE, EngineUnhealthy
from raft_tpu.serving.netproto import FileLeaseStore, Lease

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FRAME = np.arange(8 * 12 * 3, dtype=np.uint8).reshape(8, 12, 3)


def _quiet_submit(addr):
    """submit_flow that tolerates the edge tearing the socket down
    mid-request (drain-deadline tests force exactly that)."""
    try:
        submit_flow(addr, FRAME, FRAME)
    except (ConnectionError, OSError):
        pass


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeTransport:
    """From the gateway tests: scripted per-hop callables, every hop
    recorded in ``sent``."""

    def __init__(self, script=()):
        self.script = list(script)
        self.sent = []

    def request(self, addr, header, body=b"", deadline=None,
                clock=time.monotonic):
        self.sent.append((tuple(addr), dict(header), bytes(body)))
        if not self.script:
            raise AssertionError("transport called more times than "
                                 "scripted")
        return self.script.pop(0)(addr, header, body)

    def close(self):
        pass


class FakeGateway:
    """The ``submit``/``registry``/``live_workers``/``close`` surface
    the edge needs; ``calls`` is the reached-the-gateway needle."""

    def __init__(self, registry=None):
        self.registry = registry or MetricsRegistry()
        self.calls = []
        self.closed = False
        self.resolve_with = "flow"   # "flow" | "hold" | an exception
        self.held = []               # unresolved futures under "hold"

    def submit(self, im1, im2, priority="high", iters=None,
               trace_id=None, deadline=None, request_id=None):
        self.calls.append({"shape": im1.shape, "priority": priority,
                           "iters": iters, "trace_id": trace_id,
                           "deadline": deadline,
                           "request_id": request_id})
        fut = concurrent.futures.Future()
        if self.resolve_with == "hold":
            self.held.append(fut)
        elif self.resolve_with == "flow":
            fut.set_result(
                np.zeros((*im1.shape[:2], 2), np.float32))
        else:
            fut.set_exception(self.resolve_with)
        return fut

    def live_workers(self):
        return [] if self.closed else ["w0"]

    def close(self):
        self.closed = True


def _edge(gw, clock=None, **cfg):
    cfg.setdefault("header_read_timeout_s", 5.0)
    cfg.setdefault("body_read_timeout_s", 5.0)
    server = EdgeServer(gw, EdgeConfig(**cfg),
                        clock=clock or time.monotonic)
    server.start_in_thread()
    return server


def _counter(registry, name, **labels):
    inst = registry.instruments().get(name)
    if inst is None:
        return 0.0
    key = tuple(labels[k] for k in inst.labelnames)
    return inst.collect().get(key, 0.0)


# -- token bucket --------------------------------------------------------

class TestTokenBucket:
    def test_burst_then_exact_refill_math(self):
        clock = FakeClock()
        b = TokenBucket(rate=2.0, burst=3.0, clock=clock)
        assert all(b.acquire()[0] for _ in range(3))
        ok, retry = b.acquire()
        assert not ok
        # Empty bucket, 2 tokens/s: one whole token in 0.5s.
        assert retry == pytest.approx(0.5)
        clock.advance(0.25)          # half a token back
        ok, retry = b.acquire()
        assert not ok
        assert retry == pytest.approx(0.25)
        clock.advance(0.25)
        ok, retry = b.acquire()
        assert ok and retry == 0.0

    def test_refill_caps_at_burst(self):
        clock = FakeClock()
        b = TokenBucket(rate=10.0, burst=2.0, clock=clock)
        clock.advance(100.0)
        assert b.acquire()[0] and b.acquire()[0]
        assert not b.acquire()[0]


# -- the error classification --------------------------------------------------

class TestErrorClasses:
    @pytest.mark.parametrize("exc,status,cls", [
        (RequestTimedOut("budget spent"), 504, "timeout"),
        (EngineUnhealthy("no fleet"), 503, "engine_unhealthy"),
        (BacklogFull("queue full"), 429, "backlog_full"),
        (RuntimeError("worker w0 error (BacklogFull): shed"),
         429, "backlog_full"),
        (RuntimeError("gateway closed"), 500, "internal"),
    ])
    def test_gateway_outcomes_map_to_documented_status(
            self, exc, status, cls):
        assert classify_error(exc) == (status, cls)

    def test_gateway_error_rides_the_error_table_to_the_client(self):
        gw = FakeGateway()
        gw.resolve_with = EngineUnhealthy("no live lease-holder")
        es = _edge(gw)
        try:
            resp = submit_flow(es.addr, FRAME, FRAME)
            assert resp.status == 503
            assert resp.json()["error"] == "engine_unhealthy"
            assert _counter(gw.registry, "edge_errors",
                            **{"class": "engine_unhealthy"}) == 1.0
        finally:
            es.shutdown_sync()


# -- admission control ---------------------------------------------------

class TestAdmission:
    def test_over_quota_429_with_retry_after_math(self):
        clock = FakeClock()
        gw = FakeGateway()
        es = _edge(gw, clock=clock, quota_rps=2.0, quota_burst=1.0)
        try:
            ok = submit_flow(es.addr, FRAME, FRAME, client_id="alice")
            assert ok.status == 200
            rej = submit_flow(es.addr, FRAME, FRAME, client_id="alice")
            assert rej.status == 429
            assert rej.json()["error"] == "over_quota"
            # Empty bucket at 2 tokens/s: one token in exactly 500ms.
            assert rej.headers["x-retry-after-ms"] == "500"
            assert int(rej.headers["retry-after"]) >= 1
            # The rejection never reached the gateway.
            assert len(gw.calls) == 1
            # A different client key has its own bucket.
            assert submit_flow(es.addr, FRAME, FRAME,
                               client_id="bob").status == 200
        finally:
            es.shutdown_sync()

    def test_quota_falls_back_to_peer_address_key(self):
        clock = FakeClock()
        gw = FakeGateway()
        es = _edge(gw, clock=clock, quota_rps=1.0, quota_burst=1.0)
        try:
            assert submit_flow(es.addr, FRAME, FRAME).status == 200
            assert submit_flow(es.addr, FRAME, FRAME).status == 429
        finally:
            es.shutdown_sync()

    def test_pressure_shed_503_before_gateway(self):
        gw = FakeGateway()
        depth = [10.0]
        gw.registry.gauge("gateway_queue_depth", fn=lambda: depth[0])
        es = _edge(gw, shed_queue_depth=5)
        try:
            rej = submit_flow(es.addr, FRAME, FRAME)
            assert rej.status == 503
            assert rej.json()["error"] == "overload_shed"
            assert gw.calls == []
            assert _counter(gw.registry, "edge_errors",
                            **{"class": "overload_shed"}) == 1.0
            depth[0] = 0.0          # pressure gone: admits again
            assert submit_flow(es.addr, FRAME, FRAME).status == 200
        finally:
            es.shutdown_sync()

    def test_occupancy_shed_503(self):
        gw = FakeGateway()
        gw.registry.gauge("gateway_fleet_occupancy", fn=lambda: 9.0)
        es = _edge(gw, shed_occupancy=4.0)
        try:
            rej = submit_flow(es.addr, FRAME, FRAME)
            assert rej.status == 503
            assert rej.json()["error"] == "overload_shed"
            assert gw.calls == []
        finally:
            es.shutdown_sync()

    def test_concurrency_cap_503_admission_full(self):
        gw = FakeGateway()
        gw.resolve_with = "hold"
        es = _edge(gw, max_concurrent=1)
        try:
            first = threading.Thread(
                target=submit_flow, args=(es.addr, FRAME, FRAME),
                daemon=True)
            first.start()
            deadline = time.monotonic() + 5.0
            while not gw.held and time.monotonic() < deadline:
                time.sleep(0.01)
            assert gw.held, "first request never reached the gateway"
            rej = submit_flow(es.addr, FRAME, FRAME)
            assert rej.status == 503
            assert rej.json()["error"] == "admission_full"
            assert len(gw.calls) == 1
            gw.held[0].set_result(np.zeros((8, 12, 2), np.float32))
            first.join(timeout=5.0)
        finally:
            es.shutdown_sync()


# -- deadline propagation ------------------------------------------------

class TestDeadlines:
    def test_header_converted_once_to_absolute_monotonic(self):
        clock = FakeClock(t=1000.0)
        gw = FakeGateway()
        es = _edge(gw, clock=clock)
        try:
            resp = submit_flow(es.addr, FRAME, FRAME, deadline_ms=5000)
            assert resp.status == 200
            assert gw.calls[0]["deadline"] == pytest.approx(1005.0)
        finally:
            es.shutdown_sync()

    def test_no_header_defers_to_gateway_budget(self):
        gw = FakeGateway()
        es = _edge(gw)
        try:
            assert submit_flow(es.addr, FRAME, FRAME).status == 200
            assert gw.calls[0]["deadline"] is None
        finally:
            es.shutdown_sync()

    def test_expired_deadline_504_nothing_dispatched(self):
        """The acceptance needle: an expired request is answered 504
        WITHOUT reaching ``ServingGateway.submit`` — asserted on a
        REAL gateway via its transport (``sent == []``) and its
        request counter."""
        clock = FakeClock()
        transport = FakeTransport()
        tmp = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                           f"edge-leases-{os.getpid()}")
        store = FileLeaseStore(tmp)
        store.publish(Lease(worker_id="w0", addr=("127.0.0.1", 9000),
                            state="ready", t_heartbeat=time.time()))
        gw = ServingGateway(
            store, GatewayConfig(dispatch_threads=0,
                                 poll_interval_s=0.0),
            transport=transport, clock=clock)
        gw.refresh_membership()
        es = _edge(gw, clock=clock)
        try:
            rej = submit_flow(es.addr, FRAME, FRAME, deadline_ms=0)
            assert rej.status == 504
            assert rej.json()["error"] == "deadline_expired"
            rej = submit_flow(es.addr, FRAME, FRAME, deadline_ms=-50)
            assert rej.status == 504
            assert transport.sent == []
            assert gw.metrics.requests == 0
            assert _counter(gw.registry, "edge_errors",
                            **{"class": "deadline_expired"}) == 2.0
        finally:
            es.shutdown_sync()


# -- abuse hardening -----------------------------------------------------

class TestAbuse:
    def test_malformed_error_class(self):
        gw = FakeGateway()
        es = _edge(gw)
        try:
            cases = [
                # (headers, body, status) — shape/dtype/arithmetic
                ({"X-Shape": "nope"}, b"", 400),
                ({"X-Shape": "8,12,3", "X-Dtype": "float64"}, b"", 400),
                ({"X-Shape": "8,12,3", "X-Dtype": "uint8",
                  "X-Priority": "urgent"}, b"", 400),
                ({"X-Shape": "8,12,3", "X-Iters": "zero"}, b"", 400),
                # Content-Length disagrees with 2 x shape x dtype:
                ({"X-Shape": "8,12,3", "X-Dtype": "uint8"},
                 b"\x00" * 10, 400),
            ]
            for headers, body, status in cases:
                resp = http_request(es.addr, "POST", "/v1/flow",
                                    headers, body)
                assert resp.status == status, (headers, resp.status)
                assert resp.json()["error"] == "malformed"
            assert gw.calls == []
        finally:
            es.shutdown_sync()

    def test_bad_request_line_400_and_unknown_route_404(self):
        gw = FakeGateway()
        es = _edge(gw)
        try:
            s = socket.create_connection(es.addr, timeout=5.0)
            s.sendall(b"NONSENSE\r\n\r\n")
            resp = edge_mod._read_response(s)
            s.close()
            assert resp.status == 400
            assert resp.json()["error"] == "malformed"
            resp = http_request(es.addr, "GET", "/nope")
            assert resp.status == 404
            assert resp.json()["error"] == "not_found"
        finally:
            es.shutdown_sync()

    def test_oversize_body_413(self):
        gw = FakeGateway()
        es = _edge(gw, max_body_bytes=128)
        try:
            resp = submit_flow(es.addr, FRAME, FRAME)  # 576 bytes
            assert resp.status == 413
            assert resp.json()["error"] == "payload_too_large"
            assert gw.calls == []
        finally:
            es.shutdown_sync()

    def test_oversize_header_431(self):
        gw = FakeGateway()
        es = _edge(gw, max_header_bytes=256)
        try:
            resp = http_request(es.addr, "GET", "/healthz",
                                {"X-Pad": "x" * 1024})
            assert resp.status == 431
        finally:
            es.shutdown_sync()

    def test_slowloris_reaped_by_header_deadline(self):
        gw = FakeGateway()
        es = _edge(gw, header_read_timeout_s=0.2)
        try:
            s = socket.create_connection(es.addr, timeout=5.0)
            s.sendall(b"POST /v1/flow HT")   # never a complete HEAD
            s.settimeout(5.0)
            assert s.recv(16) == b""          # reaped: EOF, no bytes
            s.close()
            assert es.slow_client_drops == 1
            assert _counter(gw.registry, "edge_errors",
                            **{"class": "slowloris"}) == 1.0
            # The reap freed the slot; the door still serves.
            assert submit_flow(es.addr, FRAME, FRAME).status == 200
        finally:
            es.shutdown_sync()

    def test_injected_slowloris_knob_one_shot(self):
        inj = resilience.FaultInjector(edge_slowloris_s=0.01)
        assert inj.active
        assert inj.take_edge_slowloris() == 0.01
        assert inj.take_edge_slowloris() == 0.0

    def test_injected_client_abort_knob_nth_only(self):
        inj = resilience.FaultInjector(edge_client_abort_nth=3)
        assert inj.active
        assert [inj.aborts_edge_client(i) for i in (1, 2, 3, 4)] == \
            [False, False, True, False]

    def test_edge_knobs_parse_from_env(self, monkeypatch):
        monkeypatch.setenv("RAFT_FAULT_EDGE_SLOWLORIS_S", "0.25")
        monkeypatch.setenv("RAFT_FAULT_EDGE_CLIENT_ABORT_NTH", "7")
        inj = resilience.FaultInjector.from_env()
        assert inj.edge_slowloris_s == 0.25
        assert inj.edge_client_abort_nth == 7

    def test_client_abort_mid_response_does_not_poison_gateway(self):
        gw = FakeGateway()
        gw.resolve_with = "hold"
        es = _edge(gw)
        prev = resilience.set_injector(
            resilience.FaultInjector(edge_client_abort_nth=1))
        try:
            with pytest.raises(ClientAbortInjected):
                submit_flow(es.addr, FRAME, FRAME)
            deadline = time.monotonic() + 5.0
            while not gw.held and time.monotonic() < deadline:
                time.sleep(0.01)
            # Resolve the abandoned request AFTER its client left: the
            # edge's write fails into a counter, nothing else.
            gw.held[0].set_result(np.zeros((8, 12, 2), np.float32))
            deadline = time.monotonic() + 5.0
            while es.client_aborts == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert es.client_aborts >= 1
            # The gateway is not poisoned: next request round-trips.
            gw.resolve_with = "flow"
            resp = submit_flow(es.addr, FRAME, FRAME)
            assert resp.status == 200
            assert decode_flow(resp).shape == (8, 12, 2)
        finally:
            resilience.set_injector(prev)
            es.shutdown_sync()


# -- coordinated graceful shutdown ---------------------------------------

class TestShutdown:
    def test_ordering_edge_gateway_workers(self):
        gw = FakeGateway()
        drained = []
        es = EdgeServer(gw, EdgeConfig(),
                        drain_workers=lambda: drained.append(True))
        es.start_in_thread()
        es.shutdown_sync()
        assert es.shutdown_events == [
            "unready", "listener_closed", "edge_drained",
            "gateway_closed", "workers_drained"]
        assert gw.closed and drained == [True]

    def test_drain_bounded_by_deadline_on_fake_clock(self):
        """A wedged in-flight request cannot hold shutdown hostage:
        the drain wait is bounded by ``drain_timeout_s`` on the
        injected clock."""
        clock = FakeClock()
        gw = FakeGateway()
        gw.resolve_with = "hold"
        es = _edge(gw, clock=clock, drain_timeout_s=10.0)
        try:
            t = threading.Thread(target=_quiet_submit, args=(es.addr,),
                                 daemon=True)
            t.start()
            deadline = time.monotonic() + 5.0
            while not gw.held and time.monotonic() < deadline:
                time.sleep(0.01)
            assert gw.held
            done = threading.Event()
            shut = threading.Thread(
                target=lambda: (es.shutdown_sync(), done.set()),
                daemon=True)
            shut.start()
            deadline = time.monotonic() + 5.0
            while "listener_closed" not in es.shutdown_events \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            # In-flight request pending, clock frozen: drain holds.
            time.sleep(0.2)
            assert "edge_drained" not in es.shutdown_events
            clock.advance(11.0)      # past drain_timeout_s
            assert done.wait(5.0), "drain deadline did not release"
            assert es.shutdown_events[-2:] == ["edge_drained",
                                               "gateway_closed"]
            gw.held[0].set_result(np.zeros((8, 12, 2), np.float32))
        finally:
            if not es._closed:
                es.shutdown_sync()

    def test_readyz_flips_before_listener_closes(self):
        gw = FakeGateway()
        es = _edge(gw, drain_grace_s=0.6)
        assert http_request(es.addr, "GET", "/readyz").status == 200
        shut = threading.Thread(target=es.shutdown_sync, daemon=True)
        shut.start()
        deadline = time.monotonic() + 5.0
        while "unready" not in es.shutdown_events \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        # Inside the grace window: listener still open, readiness down,
        # liveness up, new work refused as draining.
        ready = http_request(es.addr, "GET", "/readyz")
        assert ready.status == 503
        assert ready.json()["draining"] is True
        assert http_request(es.addr, "GET", "/healthz").status == 200
        rej = submit_flow(es.addr, FRAME, FRAME)
        assert rej.status == 503
        assert rej.json()["error"] == "draining"
        shut.join(timeout=10.0)
        assert not shut.is_alive()
        assert es.shutdown_events.index("unready") \
            < es.shutdown_events.index("listener_closed")

    def test_readyz_503_when_no_routable_worker(self):
        gw = FakeGateway()
        gw.closed = True            # live_workers() -> []
        es = _edge(gw)
        try:
            assert http_request(es.addr, "GET", "/readyz").status == 503
            assert http_request(es.addr, "GET",
                                "/healthz").status == 200
        finally:
            es.shutdown_sync()


# -- lease addr routability (netproto satellite) -------------------------

class TestLeaseAddrRoutability:
    def test_missing_addr_parses_stale(self):
        lease = Lease.from_json('{"worker_id": "w", "state": "ready"}')
        assert lease.state == STALE
        assert not lease.has_routable_addr()
        assert lease.extra["unroutable_addr_state"] == "ready"

    def test_port_zero_addr_parses_stale(self):
        lease = Lease.from_json(
            '{"worker_id": "w", "addr": ["127.0.0.1", 0], '
            '"state": "ready"}')
        assert lease.state == STALE
        assert not lease.has_routable_addr()

    def test_real_addr_unchanged(self):
        lease = Lease.from_json(
            '{"worker_id": "w", "addr": ["10.0.0.2", 7001], '
            '"state": "ready"}')
        assert lease.state == "ready"
        assert lease.has_routable_addr()

    def test_gateway_never_routes_to_port_zero(self, tmp_path):
        store = FileLeaseStore(str(tmp_path / "leases"))
        store.publish(Lease(worker_id="w0", addr=("127.0.0.1", 0),
                            state="ready", t_heartbeat=time.time()))
        store.publish(Lease(worker_id="w1", addr=("127.0.0.1", 9001),
                            state="ready", t_heartbeat=time.time()))
        gw = ServingGateway(
            store, GatewayConfig(dispatch_threads=0,
                                 poll_interval_s=0.0),
            transport=FakeTransport())
        states = gw.refresh_membership()
        assert gw.live_workers() == ["w1"]
        assert states["w0"] == STALE


# -- multi-host bind -----------------------------------------------------

def _nonloopback_ip():
    """This host's primary non-loopback IP (no packets sent), or None."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("192.0.2.1", 1))     # TEST-NET: never routed
        ip = s.getsockname()[0]
    except OSError:
        return None
    finally:
        s.close()
    return None if ip.startswith("127.") else ip


class TestMultiHostBind:
    def _cfg(self, tmp_path, **kw):
        from raft_tpu.serving.worker import WorkerConfig
        return WorkerConfig(worker_id="w0", lease_dir=str(tmp_path),
                            heartbeat_interval_s=0.05, step=3, **kw)

    def test_nonloopback_bind_refused_without_advertise(self, tmp_path):
        from raft_tpu.serving.worker import WorkerServer
        from tests.test_gateway import _StubEngine
        server = WorkerServer(_StubEngine(),
                              self._cfg(tmp_path, bind_host="0.0.0.0"))
        with pytest.raises(ValueError, match="advertise_host"):
            server.start(warmup=False)

    def test_loopback_default_unchanged(self, tmp_path):
        from raft_tpu.serving.worker import WorkerServer
        from tests.test_gateway import _StubEngine
        server = WorkerServer(_StubEngine(), self._cfg(tmp_path))
        server.start(warmup=False)
        try:
            assert server.addr[0] == "127.0.0.1"
            lease = server.store.read_all()["w0"]
            assert lease.addr[0] == "127.0.0.1"
            assert lease.has_routable_addr()
        finally:
            server.stop()

    def test_wildcard_bind_advertises_and_routes(self, tmp_path):
        """The acceptance leg: a worker bound on a non-loopback
        interface (wildcard) advertises a dialable address and the
        gateway routes a real request to it."""
        from raft_tpu.serving.gateway import SocketTransport
        from raft_tpu.serving.worker import WorkerServer
        from tests.test_gateway import _StubEngine
        ip = _nonloopback_ip() or "127.0.0.1"
        server = WorkerServer(
            _StubEngine(),
            self._cfg(tmp_path, bind_host="0.0.0.0",
                      advertise_host=ip))
        server.start(warmup=False)
        try:
            # The pre-serving heartbeat may land a stale "warming"
            # lease just after start's own publish: wait out one beat.
            deadline = time.time() + 5.0
            while (server.store.read_all()["w0"].state != "ready"
                   and time.time() < deadline):
                time.sleep(0.05)
            lease = server.store.read_all()["w0"]
            assert lease.state == "ready"
            assert lease.addr == (ip, server.addr[1])
            assert lease.has_routable_addr()
            gw = ServingGateway(
                server.store,
                GatewayConfig(dispatch_threads=0, poll_interval_s=0.0),
                transport=SocketTransport())
            gw.refresh_membership()
            assert gw.live_workers() == ["w0"]
            # Manual-drive: pump the one queued request through.
            fut = gw.submit(FRAME, FRAME)
            assert gw._dispatch_next(timeout=1.0)
            out = fut.result(timeout=10.0)
            assert out.shape == (8, 12, 2)
            gw.close()
        finally:
            server.stop()


# -- the HTTP drill (slow tier) ------------------------------------------

@pytest.mark.slow
def test_edge_drill_subprocess():
    """The full front-door proof: concurrent HTTP clients through
    edge -> gateway -> worker processes surviving a SIGKILL and an
    injected slowloris with 0 dropped / 0 bit-incorrect / 0
    post-warmup compiles, then a SIGTERM draining edge -> gateway ->
    workers in order. Slow-marked — spawns real interpreters."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "serve_drill.py"),
         "--drill", "edge"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"drill failed:\n{proc.stdout}\n{proc.stderr}"
    assert "PASS drill_edge" in proc.stdout
