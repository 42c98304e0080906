"""From a profiler trace to numbers: busy union, idle gaps, time by name.

The reduction works on a plain structure so that a recorded or synthetic
trace can feed it in a test::

    {"device_ops": {"<plane name>": [[name, start_ns, dur_ns], ...]},
     "host_spans": [[name, start_ns, dur_ns], ...]}

``load_xplane`` builds ``device_ops`` from an ``.xplane.pb`` file with
``jax.profiler.ProfileData`` alone: one entry for each device plane (its
"XLA Ops" line: one event for each operation the device executed, named
by its HLO instruction; a Pallas kernel's event carries the kernel's
``name``). The benchmark's own host spans are timed on the host's clock
and put on the trace's by ``host_to_trace_clock``, through marker
programs that the harness runs at both ends of the trace.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "bench_marker"


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    markers: List[float] = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                device_ops.setdefault(plane.name, []).extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events)
            elif line.name == MODULES_LINE and not markers:
                markers = [float(ev.start_ns) for ev in line.events
                           if MARKER in ev.name]
    return {"device_ops": device_ops, "markers": markers}


def host_to_trace_clock(host_spans: Iterable[Sequence],
                        anchors: Sequence[Sequence[float]],
                        markers: Sequence[float]) -> List[list]:
    """Host spans moved onto the trace's clock. ``anchors`` are the
    host's times just before and after each marker program ran (first
    and last of the trace), ``markers`` the same programs' starts in
    the trace. The first pair fixes the offset and the last the drift
    between the clocks, taken as linear in between."""
    if len(markers) < 2 or len(anchors) < 2:
        raise ValueError(f"the trace holds {len(markers)} marker programs "
                         f"for {len(anchors)} run; two are needed")
    h0, h1 = (sum(anchors[0]) / 2.0, sum(anchors[-1]) / 2.0)
    d0, d1 = min(markers), max(markers)
    rate = (d1 - d0) / (h1 - h0)

    def to_trace(t):
        return d0 + (t - h0) * rate

    return [[name, to_trace(start), to_trace(start + dur) - to_trace(start)]
            for name, start, dur in host_spans]


def clock_drift_us(anchors, markers) -> float:
    """How far the two clocks drift apart over the trace, in us."""
    h0, h1 = (sum(anchors[0]) / 2.0, sum(anchors[-1]) / 2.0)
    return ((max(markers) - min(markers)) - (h1 - h0)) / 1e3


def window_of(trace: dict) -> Optional[Tuple[float, float]]:
    """The measured window, from the harness's ``bench.window`` span."""
    for name, start, dur in trace["host_spans"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    return None


def clip(events: Iterable[Sequence], lo: float, hi: float) -> List[list]:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def busy_union(events: Iterable[Sequence]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals in which some event ran."""
    spans = sorted((start, start + dur) for _, start, dur in events)
    merged: List[List[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The complement of ``busy`` inside ``[lo, hi]``."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def op_name(text: str) -> str:
    """The trace names a device event by the whole HLO instruction
    (``%raft_step.10 = (bf16[128,...]) custom-call(...)``); the name is
    what stands before `` = ``, without the ``%``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(events: Iterable[Sequence]) -> List[list]:
    """Events with the time of the events nested inside them taken out
    (a ``while`` holds its body's operations), so that a sum over
    events counts no nanosecond twice. Events of one line nest or
    follow one another; they do not partly overlap."""
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = [[name, start, dur] for name, start, dur in ordered]
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(ordered):
        while stack and start >= ordered[stack[-1]][1] + ordered[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= dur
        stack.append(i)
    return out


def time_by_name(events: Iterable[Sequence]) -> Dict[str, float]:
    """Summed self time (ns) of events by operation name."""
    out: Dict[str, float] = {}
    for name, _, dur in self_times(events):
        key = op_name(name)
        out[key] = out.get(key, 0.0) + dur
    return out


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   host_spans: Iterable[Sequence],
                   busy: Sequence[Tuple[float, float]] = (),
                   unnamed: str = "outside_benchmark_spans"
                   ) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing. Each gap is split
    over the host spans (other than the window's own) that overlap it,
    and what no span covers goes to ``unnamed``. A span's idle time is
    further told apart by where the device's work of that span lies:
    ``<span>:before_device`` (the device has not yet started on this
    span's work), ``<span>:after_device`` (it has finished it),
    ``<span>:between_ops``, or the bare name where the device does
    nothing during the span."""
    spans = sorted((start, start + dur, name)
                   for name, start, dur in host_spans
                   if name != WINDOW_SPAN)
    out: Dict[str, float] = {}

    def label(name, s, e, lo, hi):
        inside = [(bs, be) for bs, be in busy if be > s and bs < e]
        if not inside:
            return name
        if hi <= inside[0][0]:
            return name + ":before_device"
        if lo >= inside[-1][1]:
            return name + ":after_device"
        return name + ":between_ops"

    for lo, hi in gaps:
        covered, at = 0.0, lo
        for s, e, name in spans:
            if e <= at or s >= hi:
                continue
            a, b = max(s, at), min(e, hi)
            if b > a:
                key = label(name, s, e, a, b)
                out[key] = out.get(key, 0.0) + (b - a)
                covered += b - a
                at = b
        if hi - lo - covered > 0:
            out[unnamed] = out.get(unnamed, 0.0) + (hi - lo - covered)
    return out


def reduce_trace(trace: dict) -> dict:
    """Everything the readers need, per device plane and averaged::

        window_s, busy_s (mean over device planes), ops (name -> s, summed
        over planes and divided by their number), gaps (label -> s, of the
        first device plane), n_devices
    """
    window = window_of(trace)
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = window
    planes = sorted(trace["device_ops"])
    if not planes:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    busy_ns, ops, gaps = [], {}, {}
    for i, plane in enumerate(planes):
        events = clip(trace["device_ops"][plane], lo, hi)
        busy = busy_union(events)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, ns in time_by_name(events).items():
            ops[name] = ops.get(name, 0.0) + ns
        if i == 0:
            gaps = attribute_gaps(idle_gaps(busy, lo, hi),
                                  trace["host_spans"], busy)
    n = len(planes)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / n / 1e9,
            "ops": {k: v / n / 1e9 for k, v in ops.items()},
            "gaps": {k: v / 1e9 for k, v in gaps.items()},
            "n_devices": n}


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
