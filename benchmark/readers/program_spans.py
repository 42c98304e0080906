"""Readers of the program's own host spans: what the dataset pass, the
predictor and the train loop record, always on, into the process-wide
timer ``raft_tpu.utils.profiling.host_timer()``. Each span there has a
``name``, ``start_ns`` and ``dur_ns`` on ``time.perf_counter_ns``, a
``unit`` (the batch's or step's number), an ``id``, the ``parent``
span's ``id`` and small integer ``args``; a root span (``pass.batch``,
``train.step``) reads ``complete`` 1 once its batch's last flow was
yielded or its step's ``logger.push`` returned.

A program without that timer (the commit before it existed) gives
``None``: the metric is then left out of the line.
"""

from __future__ import annotations

from typing import Optional, Sequence


def stage_ms_per_unit(spans: Sequence, dropped: int, root: str,
                      stages: Sequence[str], n: int) -> Optional[float]:
    """Milliseconds of the ``stages`` spans per unit, over the last
    ``n`` complete ``root`` spans of ``spans`` (oldest first, as the
    timer's ring gives them). ``None`` where the ring holds fewer such
    units, where they are not one run's (their ``unit`` numbers do not
    follow one another), where it may have dropped a span of theirs
    (it has dropped some and its oldest span is already one of
    theirs), or where no span of ``stages`` lies under them."""
    chosen = [s for s in spans
              if s.name == root and s.args.get("complete")][-n:]
    if n < 1 or len(chosen) < n:
        return None
    if any(b.unit != a.unit + 1 for a, b in zip(chosen, chosen[1:])):
        return None
    ids = {s.id for s in chosen}
    if dropped and (spans[0].id in ids or spans[0].parent in ids):
        return None
    found = [s.dur_ns for s in spans
             if s.parent in ids and s.name in stages]
    if not found:
        return None
    return sum(found) / n / 1e6


def per_unit_ms(ctx, root: str, stages: Sequence[str], units: str):
    """``stage_ms_per_unit`` over the window's units: the last
    ``ctx["run"][units]`` (``batches`` or ``steps``) that completed."""
    try:
        from raft_tpu.utils import profiling
    except ImportError:
        return None
    host_timer = getattr(profiling, "host_timer", None)
    if host_timer is None or not ctx["run"].get(units):
        return None
    timer = host_timer()
    return stage_ms_per_unit(timer.spans(), timer.dropped, root, stages,
                             int(ctx["run"][units]))
