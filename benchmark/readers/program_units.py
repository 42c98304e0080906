"""Readers that account for the whole of a step or a batch from the
program's own host spans (``readers/program_spans.py`` says what a span
is and reads the named stages): what no stage covers, the cyclic
collector's share, the thread's CPU time, and the worst unit beside the
median one.

They read, besides the spans' times: the ``gc.pass`` spans the timer
records while a run has its collector hook installed (any thread;
counted by overlap in time, not by parent) and, on each root
(``train.step``, ``pass.batch``) at its close, the cumulative integers
``cpu_us`` (the closing thread's CPU time) and ``young_us``
(collector passes too short for a span), and on a
``pass.batch`` ``consume_us`` (the time the pass spent suspended at
that batch's yields). A cumulative integer is read as the difference
between the last chosen root and the root before the first, where the
ring holds that one and it is the same run's (its ``unit`` is one
less); else between the last and the first, over one unit fewer.

The units are chosen as ``program_spans.stage_ms_per_unit`` chooses
them: the last ``n`` complete roots, one run's, whole in the ring. A
program without the hook or the integers gives ``None`` where a reader
needs them: the metric is then left out of the line.
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple

COLLECTOR = "gc.pass"


def window_roots(spans: Sequence, dropped: int, root: str, n: int
                 ) -> Optional[Tuple[list, Optional[object]]]:
    """The last ``n`` complete ``root`` spans of ``spans`` (the ring,
    oldest first) and the root that closed before them if it is the
    same run's, else ``None`` in its place. ``None`` altogether where
    ``stage_ms_per_unit`` gives ``None`` for want of units."""
    roots = [s for s in spans if s.name == root]
    chosen = [s for s in roots if s.args.get("complete")][-n:]
    if n < 1 or len(chosen) < n:
        return None
    if any(b.unit != a.unit + 1 for a, b in zip(chosen, chosen[1:])):
        return None
    ids = {s.id for s in chosen}
    if dropped and (spans[0].id in ids or spans[0].parent in ids):
        return None
    at = roots.index(chosen[0])
    before = roots[at - 1] if at else None
    if before is not None and before.unit != chosen[0].unit - 1:
        before = None
    return chosen, before


def _end(span) -> int:
    return span.start_ns + span.dur_ns


def _overlap(span, intervals) -> int:
    return sum(max(0, min(_end(span), hi) - max(span.start_ns, lo))
               for lo, hi in intervals)


def _merged(roots) -> List[Tuple[int, int]]:
    """The union of the roots' intervals (a pass's roots overlap)."""
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted((r.start_ns, _end(r)) for r in roots):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def cumulative_per_unit(chosen, before, arg: str) -> Optional[float]:
    """A cumulative integer's growth a unit (module docstring)."""
    if arg not in chosen[-1].args:
        return None
    first = before if before is not None and arg in before.args else None
    if first is not None:
        return (chosen[-1].args[arg] - first.args[arg]) / len(chosen)
    if len(chosen) < 2 or arg not in chosen[0].args:
        return None
    return (chosen[-1].args[arg] - chosen[0].args[arg]) / (len(chosen) - 1)


def unattributed_ms_per_unit(spans, dropped, root, n) -> Optional[float]:
    """Root time that no direct child covers, a unit (the collector's
    spans are not children: a pass between two stages stays in here)."""
    window = window_roots(spans, dropped, root, n)
    if window is None:
        return None
    chosen, _ = window
    ids = {s.id for s in chosen}
    covered = sum(s.dur_ns for s in spans
                  if s.parent in ids and s.name != COLLECTOR)
    return (sum(s.dur_ns for s in chosen) - covered) / n / 1e6


def collector_ms_per_unit(spans, dropped, root, n) -> Optional[float]:
    """The cyclic collector's time inside the chosen roots, a unit:
    the ``gc.pass`` spans of any thread by their overlap with the
    roots, and the short passes by ``young_us``."""
    window = window_roots(spans, dropped, root, n)
    if window is None:
        return None
    chosen, before = window
    young_us = cumulative_per_unit(chosen, before, "young_us")
    if young_us is None:
        return None
    intervals = _merged(chosen)
    passes = sum(_overlap(s, intervals) for s in spans
                 if s.name == COLLECTOR)
    return passes / n / 1e6 + young_us / 1e3


def arg_per_unit(spans, dropped, root, n, arg: str, cumulative: bool
                 ) -> Optional[float]:
    """A root's integer ``arg`` a unit, in its own unit of measure."""
    window = window_roots(spans, dropped, root, n)
    if window is None:
        return None
    chosen, before = window
    if cumulative:
        return cumulative_per_unit(chosen, before, arg)
    if any(arg not in s.args for s in chosen):
        return None
    return sum(s.args[arg] for s in chosen) / n


def unit_times_ns(chosen, before, closes: bool) -> List[int]:
    """Each chosen unit's time: its root's duration, or with ``closes``
    (roots that overlap) the time from the close before it to its own,
    the first one's from the close of ``before``, else from its own
    start."""
    if not closes:
        return [s.dur_ns for s in chosen]
    ends = [_end(before) if before is not None else chosen[0].start_ns]
    ends += [_end(s) for s in chosen]
    return [b - a for a, b in zip(ends, ends[1:])]


def worst_over_median(spans, dropped, root, n, closes: bool,
                      rank: int = 0) -> Optional[float]:
    """The longest unit of the window over its median unit; with
    ``rank`` 1 the second longest. In a traced run the longest is
    always the one the benchmark itself lengthens: the step its
    ``Trace.start`` falls into, a pass's first batch with its fetch
    exposed."""
    window = window_roots(spans, dropped, root, n)
    if window is None or n <= rank:
        return None
    times = sorted(unit_times_ns(*window, closes))
    median = statistics.median(times)
    return times[-1 - rank] / median if median > 0 else None


def uncovered_ms_per_unit(spans, dropped, root, n) -> Optional[float]:
    """Between the first chosen root's start and the last one's end,
    the time of the roots' thread that lies in no child span of any
    ``root`` (the batch before's collection and the batch after's
    fetching fall in there too) and in no consumer's time (the
    ``consume_us`` of every root that closed in there), a unit."""
    window = window_roots(spans, dropped, root, n)
    if window is None:
        return None
    chosen, _ = window
    if "consume_us" not in chosen[-1].args:
        return None
    lo, hi = chosen[0].start_ns, _end(chosen[-1])
    roots = [s for s in spans if s.name == root]
    ids = {s.id for s in roots}
    covered = sum(_overlap(s, [(lo, hi)]) for s in spans
                  if s.parent in ids and s.name != COLLECTOR)
    consumed = sum(s.args.get("consume_us", 0) * 1000 for s in roots
                   if lo <= _end(s) <= hi)
    return (hi - lo - covered - consumed) / n / 1e6


# ------------------------------------------------- readers of metric files

def _read(ctx, root: str, units: str, reader, *args):
    """``reader`` over the process timer's ring and the window's units:
    the last ``ctx["run"][units]`` (``batches`` or ``steps``)."""
    try:
        from raft_tpu.utils import profiling
    except ImportError:
        return None
    host_timer = getattr(profiling, "host_timer", None)
    if host_timer is None or not ctx["run"].get(units):
        return None
    timer = host_timer()
    return reader(timer.spans(), timer.dropped, root,
                  int(ctx["run"][units]), *args)


def unattributed_ms(ctx, root: str, units: str):
    return _read(ctx, root, units, unattributed_ms_per_unit)


def collector_ms(ctx, root: str, units: str):
    return _read(ctx, root, units, collector_ms_per_unit)


def root_arg(ctx, root: str, units: str, arg: str, cumulative: bool,
             per: float = 1.0):
    """``arg`` a unit, divided by ``per`` (1000: microseconds read as
    milliseconds)."""
    value = _read(ctx, root, units, arg_per_unit, arg, cumulative)
    return None if value is None else value / per


def worst(ctx, root: str, units: str, closes: bool, rank: int = 0):
    return _read(ctx, root, units, worst_over_median, closes, rank)


def uncovered_ms(ctx, root: str, units: str):
    return _read(ctx, root, units, uncovered_ms_per_unit)
