"""Loud, uniform parsing of ``RAFT_*`` environment flags.

Every kernel/runtime toggle in this repo is an environment variable read at
trace time (``RAFT_CORR_TOUT``, ``RAFT_CORR_TILE``, ``RAFT_GRU_PALLAS``, ...).
Historically each call site hand-validated its own string, so a misspelled
value failed differently depending on which flag you fat-fingered — or worse,
was silently treated as the default.  This module centralises the parsing so
every flag fails loudly and identically:

* ``env_bool``  — '0'/'1' flags (``RAFT_CORR_TOUT``).
* ``env_enum``  — closed string sets (``RAFT_GRU_PALLAS`` in {'auto','0','1'}).
* ``env_int_choice`` — closed integer sets with an optional sentinel for
  "unset/auto" (``RAFT_CORR_TILE`` in {0, 128, 256}).
* ``forced_flag`` — scoped override/restore for A/B harnesses
  (``bench.py --gru/--motion ab``, ``scripts/serve_drill.py``) that
  force a trace-time flag for one arm and must put the environment back
  exactly — including deleting a variable that was unset — however the
  arm exits.

All helpers raise ``ValueError`` naming the variable, the offending value and
the accepted set, and all treat the empty string like an unset variable (shells
routinely export empties when composing env incantations).
"""

from __future__ import annotations

import contextlib
import os
from typing import Sequence


def _get(name: str) -> str | None:
    """Read ``name`` from the environment; empty string counts as unset."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    return raw


def env_bool(name: str, default: bool) -> bool:
    """Parse a '0'/'1' environment flag.

    Unset (or empty) returns ``default``.  Anything other than the literal
    strings '0' or '1' raises ``ValueError`` — boolean flags here deliberately
    do not accept 'true'/'yes'/'on' spellings, so a typo can never silently
    flip a kernel code path.
    """
    raw = _get(name)
    if raw is None:
        return default
    if raw not in ("0", "1"):
        raise ValueError(f"{name} must be '0' or '1', got {raw!r}")
    return raw == "1"


def env_enum(name: str, choices: Sequence[str], default: str) -> str:
    """Parse an environment flag restricted to a closed set of strings.

    Unset (or empty) returns ``default``; ``default`` must itself be a member
    of ``choices`` so call sites cannot introduce an unreachable spelling.
    """
    if default not in choices:
        raise ValueError(
            f"default {default!r} for {name} is not among choices {tuple(choices)}"
        )
    raw = _get(name)
    if raw is None:
        return default
    if raw not in choices:
        raise ValueError(
            f"{name} must be one of {tuple(choices)}, got {raw!r}"
        )
    return raw


def env_int_choice(
    name: str,
    choices: Sequence[int],
    default: int,
    *,
    hint: str = "",
) -> int:
    """Parse an integer flag restricted to a closed set.

    Unset (or empty) returns ``default``.  A value that does not parse as an
    integer, or parses but is not in ``choices``, raises ``ValueError``; the
    optional ``hint`` is appended to the message so call sites can explain the
    constraint (e.g. why larger correlation tiles are rejected).
    """
    raw = _get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        suffix = f" ({hint})" if hint else ""
        raise ValueError(
            f"{name} must be an integer, one of {tuple(choices)}, got {raw!r}{suffix}"
        ) from None
    if val not in choices:
        suffix = f" ({hint})" if hint else ""
        raise ValueError(
            f"{name} must be one of {tuple(choices)}, got {val}{suffix}"
        )
    return val


# Continuous (iteration-granular) serving batching. Read at ENGINE
# CONSTRUCTION time, not trace time: '1' turns the slot scheduler on
# for every configured stateless bucket when ServingConfig.continuous
# is left unset, '0' pins it off, 'auto' (default) defers to the
# config (and currently resolves off — the scheduler is opt-in until
# an on-TPU capture earns it a default; BASELINE.md round 9).
CONTBATCH_FLAG = "RAFT_CONTBATCH"


def resolve_contbatch() -> str:
    """Resolved ``RAFT_CONTBATCH`` mode, one of ``'auto'/'0'/'1'`` —
    the loud-parse gate for the continuous serving scheduler
    (:mod:`raft_tpu.serving.contbatch`); a misspelled value fails at
    engine construction, before any bucket warms."""
    return env_enum(CONTBATCH_FLAG, ("auto", "0", "1"), "auto")


# Fused one-launch scan-body kernel (motion encoder → SepConvGRU
# [+ flow head], ops/step_pallas.py). Read at TRACE time like the
# per-kernel flags it subsumes: 'auto' (default) fuses on TPU where
# the VMEM admission ladder admits the shape and otherwise falls back
# loudly to the two-launch chain / XLA path; '0' pins the fused step
# off (today's behavior, byte-identical); '1' forces it — interpret
# mode off-TPU (parity tooling), and on TPU raises if no tile admits
# instead of silently degrading a forced A/B arm.
STEP_FLAG = "RAFT_STEP_PALLAS"


def resolve_step_pallas() -> str:
    """Resolved ``RAFT_STEP_PALLAS`` mode, one of ``'auto'/'0'/'1'`` —
    the loud-parse gate for the fused scan-body kernel dispatch
    (:mod:`raft_tpu.ops.step_pallas`); read at trace time so the choice
    bakes into each compiled executable (the serving zero-compile
    contract)."""
    return env_enum(STEP_FLAG, ("auto", "0", "1"), "auto")


@contextlib.contextmanager
def forced_flag(name: str, value: str | None):
    """Set (or, with ``value=None``, unset) an environment flag for the
    duration of a ``with`` block and restore the previous state exactly
    on exit — the save/override/restore dance every A/B harness used to
    hand-roll around trace-time flags.  Restoration distinguishes
    "was unset" from "was empty/some value", so nesting and exceptions
    cannot leak one arm's forced value into the next.
    """
    prev = os.environ.get(name)
    try:
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev
