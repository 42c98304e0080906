"""Process-wide XLA compile counter.

:func:`xla_compile_count` / :class:`CompileWatch` count fresh backend
compiles from ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event stream (cache
*hits*, persistent or in-memory, don't emit it). One listener a process,
whoever asks first: the train loop and the dataset pass put the count's
delta on their spans, the serving tier (which exports these names from
:mod:`raft_tpu.serving.metrics`) proves its zero-compile contract with
it.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional

from raft_tpu.observability.tracer import current as _tracing_current

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_lock = threading.Lock()
_compile_count = 0
# Recent compile events (duration + module name when the monitoring
# stream carries one) for trace attribution; bounded so an unbounded
# compile storm can't grow host memory.
_compile_log: deque = deque(maxlen=256)
# Listener registration state. A DEDICATED lock, distinct from
# _compile_lock: the old code registered while holding _compile_lock —
# the same lock the listener callback takes — so a compile event
# delivered on another thread during registration (or a jax build that
# flushes buffered events to a new listener synchronously) would
# deadlock; and two engines starting concurrently before the lazy
# first call raced the check-then-register window on jax versions
# where the import itself dropped the module lock. Double-checked
# fast path + registration under _register_lock closes both: the flag
# flips only AFTER the one registration call, and re-entry returns on
# the first check. Double registration would double-count every
# compile forever (each listener fires per event).
_register_lock = threading.Lock()
_listener_on = False


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    global _compile_count
    if event != _COMPILE_EVENT:
        return
    # jax's monitoring stream does not promise kwargs; take a module
    # name under any of the keys observed across versions, else the
    # slice stays anonymous.
    module = str(kwargs.get("module_name")
                 or kwargs.get("fingerprint") or "")
    with _compile_lock:
        _compile_count += 1
        _compile_log.append((float(duration), module))
    tr = _tracing_current()
    if tr is not None:
        # Retroactive slice: the event fires when the compile ENDS, so
        # the slice is [now - duration, now] on the compiling thread's
        # lane, named by the XLA module when known.
        name = f"xla_compile:{module}" if module else "xla_compile"
        tr.complete(name, duration, cat="compile",
                    args={"module": module,
                          "duration_s": float(duration)})


def _ensure_listener() -> None:
    """Register the monitoring listener exactly once per process
    (lazily — the counter only measures deltas, so compiles before the
    first call to :func:`xla_compile_count` are irrelevant).
    Thread-safe under concurrent engine startup: see the
    ``_register_lock`` note above."""
    global _listener_on
    if _listener_on:               # fast path: flag set post-register
        return
    with _register_lock:
        if _listener_on:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration_event)
        _listener_on = True


def compile_events(n: int = 256) -> list:
    """The last ``n`` observed backend compiles as ``(duration_s,
    module_name)`` tuples (module name ``""`` when the jax version's
    monitoring stream doesn't carry one)."""
    with _compile_lock:
        return list(_compile_log)[-n:]


def xla_compile_count() -> int:
    """Process-wide count of fresh XLA backend compiles observed since
    the probe was first armed. Use deltas, not absolute values."""
    _ensure_listener()
    with _compile_lock:
        return _compile_count


class CompileWatch:
    """``with CompileWatch() as w: ...; w.compiles`` — fresh XLA backend
    compiles triggered inside the block (0 on cache hits, persistent
    cache included)."""

    def __enter__(self) -> "CompileWatch":
        self._c0 = xla_compile_count()
        self.compiles: Optional[int] = None
        return self

    def __exit__(self, *exc) -> None:
        self.compiles = xla_compile_count() - self._c0

    @property
    def so_far(self) -> int:
        return xla_compile_count() - self._c0
