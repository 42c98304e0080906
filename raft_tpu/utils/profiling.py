"""On-device profiling: trace capture + per-op time breakdown.

The reference's only observability hooks are the dormant
``MetricLogger.log_every`` timers (reference ``core/utils/misc.py:193-280``);
on TPU the native tracer is ``jax.profiler``. This module makes its output
actionable without TensorBoard:

* :func:`trace` — context manager around ``jax.profiler.trace`` with a
  fresh run directory per capture.
* :func:`op_breakdown` — parse the captured ``*.xplane.pb`` protobuf
  directly (the tensorboard-plugin converter stack is not required) and
  aggregate per-HLO-op self times from the device's "XLA Ops" timeline.
* :func:`print_breakdown` — the top-N table, normalized per step. When
  the trace carries per-op ``flops`` stats (TPU traces do; CPU traces
  usually don't) each row also gets an achieved-TFLOP/s and an MFU
  column, so "which op is the MFU wall" is answerable from the probe
  artifact alone instead of cross-referencing a roofline by hand.
* :func:`peak_tflops` — the MFU denominator: ``RAFT_PEAK_TFLOPS`` env
  override, else the TPU-v5e bf16 figure (197) on TPU backends, else
  unknown (CPU peak varies too much across hosts to guess).
* :func:`group_rows` / :func:`op_group_summary` — collapse the per-op
  rows into named op-pattern groups (e.g. every ``convc*``/``convf*``
  op of the motion encoder vs its fused Pallas custom-call) with summed
  time, FLOPs, achieved TFLOP/s and MFU per group — the "per-op MFU
  columns, but for a subsystem" view the kernel A/B probes print.
* :class:`HostStageTimer` — accumulated *host-side* wall time per named
  pipeline stage (pad / stack / dispatch / sync), for code whose cost
  the device tracer can't see, and — given a ring — the last spans
  themselves (start, duration, unit, parent). The serving engine
  threads a totals-only one through its dispatch loop; the dataset
  pass, the predictor and the train loop record into the process-wide
  one, :func:`host_timer`.

Typical use::

    with profiling.trace("/tmp/raft-trace") as t:
        for _ in range(3):
            state, metrics = step_fn(state, batch, rng)
        jax.block_until_ready(metrics)
    profiling.print_breakdown(t.logdir, steps=3)

Parsing needs the ``xplane_pb2`` proto, vendored by tensorflow; on hosts
without tensorflow :func:`op_breakdown` raises a clear error (the trace
itself can still be viewed in TensorBoard elsewhere).
"""

from __future__ import annotations

import collections
import contextlib
import glob
import importlib
import os
import os.path as osp
import struct
import time
from typing import Dict, List, Optional, Tuple

from raft_tpu.observability import tracer as _tracing

#: what a span leaves in the ring besides its name and args: id,
#: parent, unit, start_ns, dur_ns, nbytes
_ROW = struct.Struct("6q")


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
    """

    def __init__(self, ring: int = 0):
        import itertools
        import threading

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
        with self._lock:
            self._total_ns[span.name] += span.dur_ns
            self._count[span.name] += 1
            if span.nbytes:
                self._bytes[span.name] += int(span.nbytes)
            if self.ring:
                slot = self._recorded % self.ring
                self._names[slot] = span.name
                self._args[slot] = span.args or None
                _ROW.pack_into(
                    self._rows, _ROW.size * slot, span.id, span.parent,
                    -1 if span.unit is None else span.unit, span.start_ns,
                    span.dur_ns, int(span.nbytes))
                self._recorded += 1
        if self.ring and span.unit is not None:
            tracer = _tracing.current()
            if tracer is not None:
                tracer.complete(
                    span.name, span.dur_ns / 1e9, cat="host",
                    end_ts_us=(span.start_ns + span.dur_ns
                               - tracer.t0_ns) / 1e3,
                    args={"unit": span.unit, "nbytes": span.nbytes,
                          **span.args})

    def add_bytes(self, name: str, n: int) -> None:
        """Attribute ``n`` bytes to ``name`` outside a ``span()``
        block (or when the amount is only known mid-stage)."""
        with self._lock:
            self._bytes[name] += int(n)

    @property
    def dropped(self) -> int:
        """Spans the ring has overwritten."""
        with self._lock:
            return max(0, self._recorded - self.ring)

    def spans(self) -> List[SpanRecord]:
        """The ring's closed spans, oldest first (by when they closed:
        children before their parent)."""
        with self._lock:
            kept = min(self._recorded, self.ring)
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
        """Totals by stage; with ``since`` (an earlier summary), what
        was added after it, stages with nothing new left out."""
        with self._lock:
            rows = {name: (tot / 1e6, float(self._count[name]),
                           float(self._bytes[name]))
                    for name, tot in self._total_ns.items()}
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


class _Trace:
    def __init__(self, logdir: str):
        self.logdir = logdir


@contextlib.contextmanager
def trace(logdir: Optional[str] = None):
    """Capture a ``jax.profiler`` trace; yields an object with ``logdir``."""
    import jax

    if logdir is None:
        logdir = osp.join("/tmp", f"raft_tpu_trace_{int(time.time())}")
    os.makedirs(logdir, exist_ok=True)
    t = _Trace(logdir)
    with jax.profiler.trace(logdir):
        yield t


def _load_xspace(logdir: str):
    # The xplane proto moved across TF releases; try the known homes.
    XSpace, last_err = None, None
    for mod in ("tensorflow.core.profiler.protobuf.xplane_pb2",
                "tensorflow.tsl.profiler.protobuf.xplane_pb2"):
        try:
            XSpace = importlib.import_module(mod).XSpace
            break
        except ImportError as e:
            last_err = e
    if XSpace is None:  # pragma: no cover - depends on image
        raise ImportError(
            "parsing traces requires tensorflow's xplane_pb2 proto (tried "
            "tensorflow.core.profiler and tensorflow.tsl.profiler "
            f"locations); view the trace in TensorBoard instead "
            f"(logdir={logdir})") from last_err

    paths = sorted(glob.glob(
        osp.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    xs = XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def op_breakdown(logdir: str) -> List[Tuple[str, float, int]]:
    """Aggregate device-op self times from the latest trace in ``logdir``.

    Returns ``[(op_name, total_ms, count), ...]`` sorted by time. On TPU
    the ops live in each device plane's "XLA Ops" timeline; CPU traces put
    them on executor thread lines named ``tf_XLA...``. Exactly those two
    line kinds are considered and summed across ALL matching lines, so a
    multi-core/multi-device trace reports whole-trace op totals rather
    than one core's (the per-line totals are printed by
    :func:`print_breakdown` when more than one line contributed).
    """
    return _collect_ops(logdir)[0]


#: Published dense bf16 peak per chip, TFLOP/s, keyed by
#: ``jax.Device.device_kind``. Source: Google Cloud TPU documentation,
#: "TPU v5e" system architecture page (197 TFLOP/s bf16, 819 GB/s HBM).
#: A device kind that is not listed is an error, never a default.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
}


def peak_tflops() -> Optional[float]:
    """MFU denominator in TFLOP/s: ``RAFT_PEAK_TFLOPS`` env override
    (accepts any float; ``0``/empty = unknown), else the published bf16
    peak of the default device's ``device_kind`` from
    ``PEAK_BF16_TFLOPS``. ``None`` off-TPU (unknown; MFU columns are
    suppressed rather than guessed); a TPU whose kind is not in the
    table raises ``KeyError`` instead of borrowing another chip's
    peak."""
    raw = os.environ.get("RAFT_PEAK_TFLOPS", "")
    if raw:
        v = float(raw)
        return v if v > 0 else None
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no published peak for device kind {dev.device_kind!r}; add "
            f"it to raft_tpu.utils.profiling.PEAK_BF16_TFLOPS with its "
            f"source, or set RAFT_PEAK_TFLOPS")
    return PEAK_BF16_TFLOPS[dev.device_kind]


def _event_flops(plane, ev, stat_names) -> int:
    """FLOP count of one xplane event: the ``flops`` stat, read from the
    event's own stats first, then from its (shared) event metadata —
    traces have carried it in either place across TF releases."""
    for stats in (ev.stats, plane.event_metadata[ev.metadata_id].stats):
        for st in stats:
            if stat_names.get(st.metadata_id) != "flops":
                continue
            return int(st.int64_value or st.uint64_value
                       or st.double_value)
    return 0


def _collect_ops(logdir: str):
    """Shared collector:
    ``(rows, [(plane/line, total_ms), ...], {op: flops})``.

    ``rows`` keeps the historical ``[(name, total_ms, count), ...]``
    shape (:func:`op_breakdown`'s public contract); flops ride in the
    separate per-op dict, empty when the trace has no ``flops`` stats.
    """
    xs = _load_xspace(logdir)
    # Candidate op-level timelines: "XLA Ops" (TPU device planes) and CPU
    # executor threads ("tf_XLA..."). The TPU plane also has an
    # "XLA Modules" line whose whole-executable spans would double-count
    # every op — excluded. When BOTH device and host lines exist (a TPU
    # trace also records host executor activity for the same program),
    # only the device lines are summed: mixing them would double-count.
    device_lines, host_lines = [], []
    for plane in xs.planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                device_lines.append((plane, line))
            elif line.name.startswith("tf_XLA"):
                host_lines.append((plane, line))
    tot: collections.Counter = collections.Counter()
    cnt: collections.Counter = collections.Counter()
    flops: collections.Counter = collections.Counter()
    lines_used = []
    for plane, line in device_lines or host_lines:
        stat_names = {sid: meta.name
                      for sid, meta in plane.stat_metadata.items()}
        line_ps = 0
        for ev in line.events:
            name = plane.event_metadata[ev.metadata_id].name
            tot[name] += ev.duration_ps
            cnt[name] += 1
            flops[name] += _event_flops(plane, ev, stat_names)
            line_ps += ev.duration_ps
        if line_ps:
            lines_used.append((f"{plane.name}/{line.name}", line_ps / 1e9))
    rows = sorted(((k, ps / 1e9, cnt[k]) for k, ps in tot.items()),
                  key=lambda x: -x[1])
    return rows, lines_used, {k: v for k, v in flops.items() if v}


def group_rows(rows, flops, groups, steps: int = 1):
    """Collapse per-op ``rows`` (``op_breakdown`` shape) into named
    groups by substring match.

    ``groups`` maps a group name to a tuple of op-name substrings; an op
    belongs to the FIRST group (in dict order) with a matching pattern,
    so put the most specific patterns first. Pure function of the row
    data — unit-testable without a trace. Returns
    ``{group: {time_ms, ops, count, flops, tflops_per_s, mfu_pct}}``
    (``tflops_per_s``/``mfu_pct`` are ``None`` without flops stats /
    a known peak), plus an ``"(other)"`` group for unmatched time so the
    groups always sum to the whole program.
    """
    peak = peak_tflops() if flops else None
    out = {name: {"time_ms": 0.0, "ops": 0, "count": 0, "flops": 0}
           for name in groups}
    out["(other)"] = {"time_ms": 0.0, "ops": 0, "count": 0, "flops": 0}

    def bucket(op_name):
        for gname, pats in groups.items():
            if any(p in op_name for p in pats):
                return gname
        return "(other)"

    for name, ms, c in rows:
        g = out[bucket(name)]
        g["time_ms"] += ms / max(steps, 1)
        g["ops"] += 1
        g["count"] += c
        g["flops"] += flops.get(name, 0) // max(steps, 1)
    for g in out.values():
        if g["flops"] and g["time_ms"]:
            tf = g["flops"] / (g["time_ms"] * 1e-3) / 1e12
            g["tflops_per_s"] = tf
            g["mfu_pct"] = 100.0 * tf / peak if peak else None
        else:
            g["tflops_per_s"] = None
            g["mfu_pct"] = None
    return out


def op_group_summary(logdir: str, groups, steps: int = 1) -> dict:
    """Parse the latest trace in ``logdir`` and print + return the
    :func:`group_rows` table for ``groups`` — one line per group with
    summed time/step, op & event counts, and (when the trace has flops
    stats) achieved TFLOP/s and MFU."""
    rows, _, flops = _collect_ops(logdir)
    summary = group_rows(rows, flops, groups, steps=steps)
    for name, g in sorted(summary.items(),
                          key=lambda kv: -kv[1]["time_ms"]):
        if not g["count"]:
            continue
        line = (f"{g['time_ms']:9.3f} ms/step  {g['ops']:4d} ops "
                f"x{g['count']:6d}")
        if g["tflops_per_s"] is not None:
            line += f"  {g['tflops_per_s']:7.2f} TF/s"
            if g["mfu_pct"] is not None:
                line += f" {g['mfu_pct']:5.1f}% MFU"
        print(f"{line}  {name}")
    return summary


def print_breakdown(logdir: str, steps: int = 1, top: int = 20) -> None:
    """Print the top-``top`` ops, times divided by ``steps``.

    With per-op ``flops`` stats in the trace, each row gains the op's
    achieved TFLOP/s and — when :func:`peak_tflops` knows the chip — its
    MFU, plus a weighted whole-program MFU line. Both are *self-time*
    utilizations (flops / op self time / peak), so memory-bound ops
    honestly read near 0% rather than inheriting neighbors' compute.
    """
    rows, lines_used, flops = _collect_ops(logdir)
    total = sum(ms for _, ms, _ in rows)
    peak = peak_tflops() if flops else None
    print(f"total device op time: {total / max(steps, 1):.2f} ms/step "
          f"({len(rows)} distinct ops, {len(lines_used)} op timelines)")
    if flops and total:
        agg = sum(flops.values()) / (total * 1e-3) / 1e12
        line = f"achieved: {agg:.2f} TFLOP/s over device op time"
        if peak:
            line += f" = {100.0 * agg / peak:.1f}% MFU of {peak:g} peak"
        print(line)
    if len(lines_used) > 1:
        for name, ms in lines_used:
            print(f"  contributing line: {name} "
                  f"({ms / max(steps, 1):.2f} ms/step)")
    for name, ms, c in rows[:top]:
        cols = f"{ms / max(steps, 1):9.3f} ms/step  x{c:5d}"
        if name in flops and ms:
            tf = flops[name] / (ms * 1e-3) / 1e12
            cols += f"  {tf:7.2f} TF/s"
            if peak:
                cols += f" {100.0 * tf / peak:5.1f}% MFU"
        print(f"{cols}  {name[:90]}")
