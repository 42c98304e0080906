"""Host-side accounting of a run: where the wall time of a batch or a
step went, on the clock the device trace is anchored on.

The device's side is read from ``jax.profiler`` traces by the benchmark
(``benchmark/trace_reduce.py``; ``benchmark/tools/scope_summary.py``
prints the by-stage table). This module is the host's side:

* :class:`HostStageTimer` — accumulated wall time per named stage
  (pad / stack / dispatch / sync), for code whose cost the device
  tracer can't see, and — given a ring — the last spans themselves
  (start, duration, unit, parent). The serving engine threads a
  totals-only one through its dispatch loop; the dataset pass, the
  predictor and the train loop record into the process-wide one,
  :func:`host_timer`.
* :meth:`HostStageTimer.collector_spans` — for the length of a run the
  cyclic collector's passes are spans too (``gc.pass``, with the thread
  and generation; the many short young passes only as the totals
  ``gc.young``), so a stall inside ``block_until_ready`` can be told
  from device time.
* :meth:`HostStageTimer.close_root` — a unit's root span closes with
  its thread's cumulative CPU time: wall time without CPU time is a
  thread kept from running.
* :func:`unit_accounts` / :func:`slow_unit_lines` — the operator's
  reading of the ring at a flush: per unit what no stage covers, the
  collector's share, CPU beside wall, and a line for every unit that
  ran over three times the median.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import statistics
import struct
import threading
import time
from typing import Dict, List, Optional, Sequence

from raft_tpu.observability import tracer as _tracing

#: what a span leaves in the ring besides its name and args: id,
#: parent, unit, start_ns, dur_ns, nbytes
_ROW = struct.Struct("6q")


#: A collector pass of a young generation that took less than this
#: leaves no span, only the ``gc.young`` totals.
GC_SPAN_NS = 1_000_000

#: A unit that took over this many times the median one gets a line
#: of its own from :func:`slow_unit_lines`.
SLOW_FACTOR = 3.0

#: A closed span as :meth:`HostStageTimer.spans` gives it back.
SpanRecord = collections.namedtuple(
    "SpanRecord", "name id parent unit start_ns dur_ns nbytes args")


class Span:
    """One timed stretch of host work, open from its creation to
    :meth:`close` (``with timer.span(...)`` closes it). Times are
    absolute ``time.perf_counter_ns``, the clock the device trace is
    anchored on. ``unit`` (a non-negative integer) is the identifier
    all spans of one batch or one step share, ``parent`` the ``id`` of
    the span it was opened under: the one handed in, else the innermost
    span open on this thread when this one began (0 for a root);
    ``args`` holds small integers and may be filled while the span is
    open."""

    __slots__ = ("_timer", "_stack", "name", "id", "parent", "unit",
                 "start_ns", "dur_ns", "nbytes", "args")

    def __init__(self, timer, name, unit, nbytes, args, parent=None):
        self._timer = timer
        self.name = name
        self.nbytes = nbytes
        self.args = args
        self.dur_ns = None
        stack = self._stack = timer._stack()
        if parent is None and stack:
            parent = stack[-1]
        if parent is not None:
            self.parent = parent.id
            self.unit = parent.unit if unit is None else unit
        else:
            self.parent = 0
            self.unit = unit
        self.id = next(timer._ids)
        stack.append(self)
        self.start_ns = time.perf_counter_ns()

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def detach(self) -> "Span":
        """Take the open span off its thread's stack: it stays open,
        but a span opened after it is its child only if it is handed
        over as ``parent=`` or named by :meth:`HostStageTimer.under`.
        For a span that outlives the work around it: the dataset pass
        keeps two batches' roots open at once."""
        if self in self._stack:
            self._stack.remove(self)
        return self

    def close(self) -> None:
        """End the span now and record it. Closing twice records once."""
        if self.dur_ns is not None:
            return
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        # Usually the top of its thread's stack of open spans; a
        # detached span is not on it, and a generator's span can be
        # closed under a consumer's span, or by the garbage collector
        # on another thread.
        self.detach()
        self._timer._record(self)


class HostStageTimer:
    """Thread-safe accumulator of host-side wall time per named stage,
    and a bounded ring of the last spans.

    ``with timer.span("pad"): ...`` around each host-pipeline section
    (:meth:`stage` is the same call under its older name);
    :meth:`summary` returns ``{stage: {total_ms, count, mean_ms,
    total_bytes}}`` and :meth:`report` a one-line table. Stages may be
    entered concurrently from several threads (client threads pad while
    the dispatcher stacks) — times are summed, so on overlapping
    threads the totals measure *work*, not wall clock.

    Stages that move memory can also account bytes: pass ``nbytes`` to
    :meth:`span` when the amount is known up front (e.g. the staging
    arena memcpy), or call :meth:`add_bytes` when it is only known
    mid-stage (e.g. per-output device→host syncs). Byte totals turn the
    stage table into a bandwidth story — "stack" time divided by
    "stack" bytes is the host memcpy rate the wire format is cutting.

    ``ring``: how many closed spans to keep (:meth:`spans`, oldest
    first, as :class:`SpanRecord`; :attr:`dropped` counts overwrites).
    A span's parent is the span it was handed (``parent=``), else the
    innermost span open on the same thread, which a caller can name
    for the length of a call (:meth:`under`): so the predictor's spans
    fall under the batch the dataset pass called it for, though the
    pass has two batches' roots open. With a ring, each span of a unit
    is also forwarded to the process
    :class:`~raft_tpu.observability.Tracer` while one is enabled (as a
    slice of category ``host``); a span outside any unit
    is not (the serving engine's calls into the predictor: its own
    call sites write request-keyed slices, into the tracer the engine
    captured when it was built). ``ring=0`` keeps totals only: the
    serving engine's instance.

    Inside :meth:`collector_spans` a pass of the cyclic collector is a
    span too: ``gc.pass`` for a full pass (generation 2) or one of
    ``GC_SPAN_NS`` or more, with integer args ``generation``,
    ``collected``, ``uncollectable`` and ``main`` (1 on the thread that
    entered :meth:`collector_spans`, 0 on any other: a loader's thread
    that holds the interpreter while the loop's thread waits for it);
    its parent and unit are those of the innermost span open on the
    thread it ran on. Every other pass adds to the totals ``gc.young``
    (:meth:`summary`; :attr:`young_us`) and takes no slot of the ring.
    """

    def __init__(self, ring: int = 0):
        import itertools

        if ring < 0:
            raise ValueError(f"ring must be >= 0, got {ring}")
        self.ring = int(ring)
        self._lock = threading.Lock()
        self._total_ns: Dict[str, int] = collections.defaultdict(int)
        self._count: Dict[str, int] = collections.defaultdict(int)
        self._bytes: Dict[str, int] = collections.defaultdict(int)
        # The ring, as columns: a closed span leaves no Python object
        # behind. Keeping the Span objects (390 a Sintel batch) moved
        # the garbage collector's cadence, and with it when JAX lets go
        # of a batch's staging arrays: the large pass lost 8 % to page
        # faults (PERF.md, Findings of PR 27).
        self._names: List[Optional[str]] = [None] * self.ring
        self._args: List[Optional[dict]] = [None] * self.ring
        self._rows = bytearray(_ROW.size * self.ring)
        self._recorded = 0
        self._ids = itertools.count(1)
        self._open = threading.local()
        # The collector's passes (collector_spans). The callback runs
        # wherever a pass begins, which can be inside _record on the
        # thread that holds the lock: it takes no lock and leaves a
        # finished pass in a queue that the next lock holder folds in.
        self._gc_users = 0
        self._gc_main: Optional[int] = None
        self._gc_began_ns = 0
        self._gc_done: collections.deque = collections.deque()
        self._young_ns = 0
        self._young_count = 0

    def span(self, name: str, unit: Optional[int] = None, nbytes: int = 0,
             parent: Optional[Span] = None, **args: int) -> Span:
        """Open a span; close it with ``with`` or :meth:`Span.close`.
        ``parent``: the open span it belongs under, where that is not
        the innermost one open on this thread. ``unit`` defaults to the
        parent's."""
        return Span(self, name, unit, nbytes, args, parent)

    stage = span

    def _stack(self) -> List[Span]:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    @contextlib.contextmanager
    def under(self, span: Span):
        """For the length of the block ``span`` (open) is the innermost
        span of this thread: what code called inside opens without a
        ``parent=`` of its own falls under it. How a caller with
        several roots open says whose children a callee's spans are."""
        stack = self._stack()
        stack.append(span)
        try:
            yield span
        finally:
            for k in range(len(stack) - 1, -1, -1):
                if stack[k] is span:
                    del stack[k]
                    break

    def _record(self, span: Span) -> None:
        row = (span.name, span.id, span.parent, span.unit, span.start_ns,
               span.dur_ns, int(span.nbytes), span.args)
        with self._lock:
            self._fold_passes()
            self._put(*row)
        self._forward(*row)

    def _put(self, name, ident, parent, unit, start_ns, dur_ns, nbytes,
             args) -> None:
        """Under the lock: one closed span into the totals and the
        ring."""
        self._total_ns[name] += dur_ns
        self._count[name] += 1
        if nbytes:
            self._bytes[name] += nbytes
        if self.ring:
            slot = self._recorded % self.ring
            self._names[slot] = name
            self._args[slot] = args or None
            _ROW.pack_into(self._rows, _ROW.size * slot, ident, parent,
                           -1 if unit is None else unit, start_ns, dur_ns,
                           nbytes)
            self._recorded += 1

    def _forward(self, name, ident, parent, unit, start_ns, dur_ns, nbytes,
                 args) -> None:
        """A span of a unit to the process tracer, where one is on."""
        if self.ring and unit is not None:
            tracer = _tracing.current()
            if tracer is not None:
                tracer.complete(
                    name, dur_ns / 1e9, cat="host",
                    end_ts_us=(start_ns + dur_ns - tracer.t0_ns) / 1e3,
                    args={"unit": unit, "nbytes": nbytes, **args})

    # ------------------------------------------------- the collector

    @contextlib.contextmanager
    def collector_spans(self):
        """For the length of the block the cyclic collector's passes
        are recorded (class docstring). Blocks nest, also across
        ``train()`` and the dataset pass it validates with: one
        callback in ``gc.callbacks`` while any is open, none after the
        last is left, however it is left."""
        with self._lock:
            self._gc_users += 1
            if self._gc_users == 1:
                self._gc_main = threading.get_ident()
                gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            with self._lock:
                self._gc_users -= 1
                if not self._gc_users:
                    gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        now = time.perf_counter_ns()
        if phase == "start":
            self._gc_began_ns = now
            return
        began, self._gc_began_ns = self._gc_began_ns, 0
        if not began:
            # hooked between a pass's two callbacks: its start was not
            # seen
            return
        dur = now - began
        if info["generation"] < 2 and dur < GC_SPAN_NS:
            # passes do not nest and each holds the interpreter: no
            # other thread is in here
            self._young_ns += dur
            self._young_count += 1
            return
        stack = self._stack()
        parent, unit = (stack[-1].id, stack[-1].unit) if stack else (0, None)
        row = ("gc.pass", next(self._ids), parent, unit, began, dur, 0,
               {"generation": info["generation"],
                "collected": info["collected"],
                "uncollectable": info["uncollectable"],
                "main": int(threading.get_ident() == self._gc_main)})
        self._gc_done.append(row)
        self._forward(*row)

    def _fold_passes(self) -> None:
        """Under the lock: the passes finished since the last call
        into the ring, before whatever closes next."""
        done = self._gc_done
        while done:
            self._put(*done.popleft())

    @property
    def young_us(self) -> int:
        """Microseconds the passes that left no span have taken, in
        all, since the timer was made."""
        return self._young_ns // 1000

    def close_root(self, span: Span) -> None:
        """Close a unit's root span, on the thread that opened it, with
        what that thread has used so far: ``cpu_us`` (its user and
        system CPU time, ``time.thread_time_ns``) and ``young_us``
        (:attr:`young_us`). Both are cumulative: a reader takes the
        difference of two consecutive roots."""
        if span.dur_ns is None:
            span.args.update(cpu_us=time.thread_time_ns() // 1000,
                             young_us=self.young_us)
        span.close()

    def add_bytes(self, name: str, n: int) -> None:
        """Attribute ``n`` bytes to ``name`` outside a ``span()``
        block (or when the amount is only known mid-stage)."""
        with self._lock:
            self._bytes[name] += int(n)

    @property
    def dropped(self) -> int:
        """Spans the ring has overwritten."""
        with self._lock:
            self._fold_passes()
            return max(0, self._recorded - self.ring)

    @property
    def recorded(self) -> int:
        """Spans recorded so far, the overwritten ones among them."""
        with self._lock:
            return self._recorded + len(self._gc_done)

    def spans(self, newest: Optional[int] = None) -> List[SpanRecord]:
        """The ring's closed spans, oldest first (by when they closed:
        children before their parent); with ``newest``, no more than
        that many of its latest."""
        with self._lock:
            self._fold_passes()
            kept = min(self._recorded, self.ring)
            if newest is not None:
                kept = min(kept, max(int(newest), 0))
            slots = [(self._recorded - kept + k) % self.ring
                     for k in range(kept)]
            rows = [(self._names[i], self._args[i],
                     _ROW.unpack_from(self._rows, _ROW.size * i))
                    for i in slots]
        return [SpanRecord(name, ints[0], ints[1],
                           None if ints[2] < 0 else ints[2], ints[3],
                           ints[4], ints[5], dict(args or {}))
                for name, args, ints in rows]

    def summary(self, since: Optional[Dict[str, Dict[str, float]]] = None
                ) -> Dict[str, Dict[str, float]]:
        """Totals by stage, ``gc.young`` (the collector's passes that
        left no span) among them; with ``since`` (an earlier summary),
        what was added after it, stages with nothing new left out."""
        with self._lock:
            self._fold_passes()
            rows = {name: (tot / 1e6, float(self._count[name]),
                           float(self._bytes[name]))
                    for name, tot in self._total_ns.items()}
            if self._young_count:
                rows["gc.young"] = (self._young_ns / 1e6,
                                    float(self._young_count), 0.0)
        out = {}
        for name, (ms, count, nbytes) in rows.items():
            if since is not None and name in since:
                ms -= since[name]["total_ms"]
                count -= since[name]["count"]
                nbytes -= since[name]["total_bytes"]
                if not count:
                    continue
            out[name] = {"total_ms": ms, "count": count,
                         "mean_ms": ms / max(count, 1),
                         "total_bytes": nbytes}
        return out

    def report(self, since: Optional[Dict[str, Dict[str, float]]] = None
               ) -> str:
        rows = sorted(self.summary(since).items(),
                      key=lambda kv: -kv[1]["total_ms"])
        return " | ".join(
            f"{name}: {v['total_ms']:.1f}ms/{int(v['count'])} "
            f"({v['mean_ms']:.2f}ms avg"
            + (f", {v['total_bytes'] / 1e6:.2f}MB" if v["total_bytes"]
               else "")
            + ")" for name, v in rows) or "(empty)"


#: Spans the process-wide timer keeps: a Sintel pass batch of 128 is
#: ~390 spans, a train step 9, so the ring holds the last ~40 batches
#: or ~1800 steps.
HOST_RING = 16384

_HOST_TIMER = HostStageTimer(ring=HOST_RING)


def host_timer() -> HostStageTimer:
    """The process-wide timer the dataset pass, the predictor and the
    train loop record into, always on. Look it up at call time: a
    reader needs no handle on the predictor or the loop."""
    return _HOST_TIMER


# ------------------------------------------------- the operator's reading

def unit_accounts(spans: Sequence[SpanRecord], root: str) -> List[dict]:
    """What the thread of the ``root`` spans (``train.step``,
    ``pass.batch``) did, unit by unit, from ``spans`` (the ring, oldest
    first); one row a closed root:

    * ``unit``, ``id``; ``ms``: the unit's time, from the root's start,
      or from the close of the root before it where that one was still
      open then (the dataset pass keeps two open), to its close;
    * ``stages``: milliseconds by name of the roots' children that ran
      in that time, whichever unit's they are (and ``consume``, the
      consumer's time at the batch's yields); ``unattributed``: what is
      left of ``ms``;
    * ``passes``: ``(generation, main, ms)`` of every ``gc.pass`` span
      that overlaps that time, on any thread; ``collector_ms``: their
      overlap and the short passes (``young_us``) together;
    * ``cpu_ms``: the thread's CPU time
      (:meth:`HostStageTimer.close_root`) since the root before,
      ``None`` for a run's first root in ``spans``.
    """
    roots = [s for s in spans if s.name == root]
    ids = {r.id for r in roots}
    rows, begins, ends = [], [], []
    before = None
    for r in roots:
        end = r.start_ns + r.dur_ns
        follows = before is not None and r.unit == before.unit + 1
        begin = max(r.start_ns, ends[-1]) if follows else r.start_ns
        used = {k: (r.args[k] - before.args[k]) if follows
                and k in r.args and k in before.args else None
                for k in ("cpu_us", "young_us")}
        consumed = r.args.get("consume_us", 0) / 1e3
        rows.append({
            "unit": r.unit, "id": r.id, "ms": (end - begin) / 1e6,
            "stages": {"consume": consumed} if consumed else {},
            "passes": [], "collector_ms": (used["young_us"] or 0) / 1e3,
            "cpu_ms": None if used["cpu_us"] is None
            else used["cpu_us"] / 1e3})
        begins.append(begin)
        ends.append(end)
        before = r
    for s in spans:
        if s.name == "gc.pass":
            at = bisect.bisect_right(ends, s.start_ns)
            while at < len(rows) and begins[at] < s.start_ns + s.dur_ns:
                ms = (min(s.start_ns + s.dur_ns, ends[at])
                      - max(s.start_ns, begins[at])) / 1e6
                rows[at]["passes"].append(
                    (s.args.get("generation"), s.args.get("main"), ms))
                rows[at]["collector_ms"] += ms
                at += 1
        elif s.parent in ids:
            at = bisect.bisect_right(ends, s.start_ns)
            if at < len(rows) and begins[at] <= s.start_ns:
                stages = rows[at]["stages"]
                name = s.name.partition(".")[2] or s.name
                stages[name] = stages.get(name, 0.0) + s.dur_ns / 1e6
    for row in rows:
        row["unattributed"] = row["ms"] - sum(row["stages"].values())
    return rows


def slow_unit_lines(rows: Sequence[dict], what: str,
                    factor: float = SLOW_FACTOR) -> List[str]:
    """One line for each of ``rows`` (:func:`unit_accounts`) that took
    over ``factor`` times their median (of an even count, the lower of
    the two in the middle): its number, its time beside the median, the
    stage that grew most over that stage's median and by how much, the
    collector's passes that overlap it by thread and generation, and
    CPU time beside wall."""
    if not rows:
        return []
    median = statistics.median_low(r["ms"] for r in rows)
    usual: Dict[str, float] = {}
    for name in {n for r in rows for n in (*r["stages"], "unattributed")}:
        usual[name] = statistics.median_low(
            r["stages"].get(name, 0.0) if name != "unattributed"
            else r["unattributed"] for r in rows)
    lines = []
    for row in rows:
        if row["ms"] <= factor * median:
            continue
        grown = {name: (row["unattributed"] if name == "unattributed"
                        else row["stages"].get(name, 0.0)) - usual[name]
                 for name in usual}
        name = max(grown, key=grown.get)
        parts = [f"slow {what} {row['unit']}: {row['ms']:.1f} ms for a "
                 f"median of {median:.1f}",
                 f"{name} +{grown[name]:.1f} ms"]
        # one entry a generation and thread: a compiling step holds
        # dozens of long young passes
        kinds = sorted({p[:2] for p in row["passes"]})
        for generation, main in kinds:
            took = [ms for g, m, ms in row["passes"]
                    if (g, m) == (generation, main)]
            parts.append(
                (f"{len(took)} x " if len(took) > 1 else "")
                + f"gc.pass generation {generation} on "
                f"{'this' if main else 'another'} thread {sum(took):.1f} ms")
        if not kinds:
            parts.append("no gc.pass")
        if row["cpu_ms"] is not None:
            parts.append(f"cpu {row['cpu_ms']:.1f} ms")
        lines.append(" | ".join(parts))
    return lines
