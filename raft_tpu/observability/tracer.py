"""Request-scoped tracing: monotonic-clock spans in a bounded ring.

The serving stack's counters (:mod:`raft_tpu.serving.metrics`) answer
"how many" and "how fast on average"; they cannot answer "where did
THIS request's 40 ms go". The :class:`Tracer` here records spans and
annotations into a bounded ring buffer and exports them as Chrome
trace-event JSON — the format Perfetto (https://ui.perfetto.dev) and
``chrome://tracing`` load natively — so one request's life renders as:

* an **async track per request** (``trace_id`` keyed): the root
  ``request`` span (submit → future resolution) with ``failover_hop`` /
  ``rebucket`` / ``retry_single`` annotations riding on it, plus the
  fleet's outer ``fleet_request`` span when routed through one;
* a **thread-track lane per worker**: the engine's dispatch/completion
  threads already carry descriptive names
  (``serving-<H>x<W>-dispatch`` / ``-complete``, ``serving-route``),
  which become Perfetto thread tracks holding the ``stack`` /
  ``dispatch`` / ``sync`` / ``unpad`` stage slices and the per-request
  ``queue`` wait slices;
* ``xla_compile`` slices fed by the existing JAX monitoring listener
  (:mod:`raft_tpu.serving.metrics`), module name attached when the
  event stream carries one.

Design constraints, both load-bearing:

* **Zero-cost when disabled.** Nothing here allocates, mints, or locks
  unless a tracer was explicitly enabled: producers hold a single
  ``self._tracer`` reference that is ``None`` in the default
  configuration, and every instrumentation site is behind one ``is not
  None`` test. No trace_id is minted per request and the latency path
  is bit-identical (asserted by tests/test_observability.py).
* **Bounded when enabled.** The ring holds ``capacity`` events and
  overwrites the oldest beyond that; the overwrite count is exposed as
  :attr:`Tracer.dropped` (and exported in the artifact), so a
  saturated tracer degrades to a recent-window view instead of
  unbounded memory growth. Recording is lock-free in CPython: the slot
  index comes from ``itertools.count`` (atomic, C-implemented) and the
  slot write is a single list item assignment.

Timestamps are ``time.perf_counter_ns`` microseconds relative to the
tracer's construction (:attr:`Tracer.t0_ns`, exported in the artifact)
— monotonic, immune to wall-clock steps, and directly usable as
Chrome's ``ts`` field.

The dataset pass, the predictor and the train loop do not record here
directly: their spans live in the always-on host timer
(:func:`raft_tpu.utils.profiling.host_timer`), which forwards each one
as a ``host``-category slice while a tracer is enabled.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

# Chrome trace-event phases used below:
#   X  complete slice (ts + dur) on a thread track
#   b / n / e  nestable async begin / instant / end, keyed by id —
#              one track per id, the per-request lane
#   M  metadata (thread names)
_ASYNC_CAT = "request"


class Tracer:
    """Bounded lock-free span recorder with Chrome trace-event export.

    One instance is shared process-wide (see :func:`enable` /
    :func:`current`): the engine, fleet, sessions, and the XLA compile
    listener all record into the same ring, so a single exported
    artifact holds the whole story.
    """

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: List[Optional[dict]] = [None] * self.capacity
        # itertools.count() is atomic under the GIL (C-implemented):
        # concurrent producers each get a unique slot without a lock.
        self._slots = itertools.count()
        self._ids = itertools.count(1)
        #: ``time.perf_counter_ns`` at construction: ``ts`` of an event
        #: is microseconds after it, so ``t0_ns + ts * 1e3`` puts the
        #: artifact on the clock of the host timer's spans and of the
        #: benchmark's device-trace anchors.
        self.t0_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        # tid -> thread name, filled lazily at record time. Plain dict
        # writes are atomic under the GIL; last-writer-wins is fine
        # (a tid's name never changes while it records).
        self._thread_names: Dict[int, str] = {}
        # (name, trace_id) -> open count, for the "every root span
        # closed" assertion. Guarded by a small lock — begin/end are
        # per-request (not per-event) so this is off the span hot path
        # frequency-wise, and correctness beats lock-freedom here.
        self._open: Dict[Tuple[str, int], int] = {}
        self._open_lock = threading.Lock()

    # -- clock ----------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since tracer construction (monotonic)."""
        return (time.perf_counter_ns() - self.t0_ns) / 1e3

    # -- identity -------------------------------------------------------

    def mint(self) -> int:
        """New process-unique trace id (one per request, at submit)."""
        return next(self._ids)

    # -- recording ------------------------------------------------------

    def _record(self, evt: dict) -> None:
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        evt["tid"] = tid
        i = next(self._slots)
        evt["_seq"] = i            # stripped at export; drop accounting
        self._ring[i % self.capacity] = evt

    def complete(self, name: str, dur_s: float,
                 trace_id: Optional[int] = None,
                 args: Optional[dict] = None,
                 end_ts_us: Optional[float] = None,
                 cat: str = "serving") -> None:
        """One finished slice of ``dur_s`` seconds ending now (or at
        ``end_ts_us``) on the calling thread's track. Used both for
        measured-in-place work and for retroactive slices (queue wait,
        compile durations) whose start predates the call."""
        end = self.now_us() if end_ts_us is None else end_ts_us
        dur = max(dur_s, 0.0) * 1e6
        evt = {"ph": "X", "name": name, "cat": cat,
               "ts": end - dur, "dur": dur}
        if trace_id is not None or args:
            a = dict(args) if args else {}
            if trace_id is not None:
                a["trace_id"] = trace_id
            evt["args"] = a
        self._record(evt)

    @contextmanager
    def span(self, name: str, trace_id: Optional[int] = None,
             args: Optional[dict] = None, cat: str = "serving"):
        """Measure the with-block as one complete slice."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.complete(name, (time.perf_counter_ns() - t0) / 1e9,
                          trace_id=trace_id, args=args, cat=cat)

    def begin_async(self, name: str, trace_id: int,
                    args: Optional[dict] = None) -> None:
        """Open one async span on the ``trace_id`` request track (the
        root ``request`` span, or a nested attempt). Must be closed by
        :meth:`end_async` with the same name + id."""
        with self._open_lock:
            key = (name, trace_id)
            self._open[key] = self._open.get(key, 0) + 1
        evt = {"ph": "b", "cat": _ASYNC_CAT, "name": name,
               "id": trace_id, "ts": self.now_us()}
        if args:
            evt["args"] = dict(args)
        self._record(evt)

    def end_async(self, name: str, trace_id: int,
                  args: Optional[dict] = None) -> None:
        with self._open_lock:
            key = (name, trace_id)
            n = self._open.get(key, 0) - 1
            if n > 0:
                self._open[key] = n
            else:
                self._open.pop(key, None)
        evt = {"ph": "e", "cat": _ASYNC_CAT, "name": name,
               "id": trace_id, "ts": self.now_us()}
        if args:
            evt["args"] = dict(args)
        self._record(evt)

    def async_instant(self, name: str, trace_id: int,
                      args: Optional[dict] = None) -> None:
        """Point annotation on the request's async track (failover
        hops, re-bucketing, isolation retries, warm-start notes)."""
        evt = {"ph": "n", "cat": _ASYNC_CAT, "name": name,
               "id": trace_id, "ts": self.now_us()}
        if args:
            evt["args"] = dict(args)
        self._record(evt)

    # -- reading / export -----------------------------------------------

    @property
    def recorded(self) -> int:
        """Events recorded so far (overwritten ones included): the
        highest sequence number stamped on a live event, plus one.
        itertools.count cannot be peeked, so this is derived from the
        ring contents — exact whenever the newest event is still in
        the ring (always, short of a concurrent writer mid-store)."""
        seqs = [e["_seq"] for e in list(self._ring)
                if e is not None and "_seq" in e]
        return max(seqs) + 1 if seqs else 0

    @property
    def dropped(self) -> int:
        """Events overwritten by ring wrap-around (0 until the ring
        fills). Exported in the artifact so a truncated capture says
        so."""
        return max(0, self.recorded - self.capacity)

    def open_flows(self) -> List[Tuple[str, int]]:
        """Async spans begun but not yet ended — empty once every
        accepted request's future has resolved."""
        with self._open_lock:
            return sorted(self._open)

    def events(self) -> List[dict]:
        """Snapshot of the ring's live events, oldest-first by ts
        (the internal ``_seq`` stamp stripped)."""
        evts = [{k: v for k, v in e.items() if k != "_seq"}
                for e in list(self._ring) if e is not None]
        evts.sort(key=lambda e: e.get("ts", 0.0))
        return evts

    def chrome_trace(self) -> dict:
        """The exported artifact: Chrome trace-event JSON (object
        form), loadable as-is in Perfetto / chrome://tracing."""
        events = []
        for tid, tname in sorted(self._thread_names.items()):
            events.append({"ph": "M", "name": "thread_name",
                           "pid": self._pid, "tid": tid,
                           "args": {"name": tname}})
        for e in self.events():
            evt = dict(e)
            evt["pid"] = self._pid
            events.append(evt)
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "open_flows": len(self.open_flows()),
                              "capacity": self.capacity,
                              "t0_ns": self.t0_ns}}

    def write(self, path: str) -> str:
        """Serialize :meth:`chrome_trace` to ``path``; returns it."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# -- process-wide tracer singleton --------------------------------------
#
# Producers capture current() ONCE at construction (engine/fleet
# __init__) into a `self._tracer` slot: the disabled path stays a
# single attribute test with no import, no call, no allocation.

_TRACER: Optional[Tracer] = None


def enable(capacity: int = 65536) -> Tracer:
    """Install (or return the already-installed) process tracer.
    Engines constructed AFTER this call record into it; enabling after
    construction does not retrofit running engines (their ``_tracer``
    slot was captured at init)."""
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer(capacity)
    return _TRACER


def disable() -> None:
    """Drop the process tracer (already-constructed engines keep the
    reference they captured; new ones see tracing off)."""
    global _TRACER
    _TRACER = None


def current() -> Optional[Tracer]:
    """The process tracer, or ``None`` when tracing is disabled."""
    return _TRACER
