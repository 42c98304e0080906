"""Scoped-VMEM budgets shared by the Pallas kernels (corr, GRU, motion,
and the fused one-launch step kernel).

Mosaic holds a ``pallas_call`` to a *scoped* VMEM limit: 16 MiB unless
the call passes a larger one in its compiler params (a v5e core has 128
MiB). Exceeding it does not fail gracefully: the 512-query-tile corr
config died in Mosaic with a raw scoped-allocator OOM — ``17.41 MB vs
16 MB limit`` after a long compile (BASELINE.md "Query tile 512") —
with no indication of *which* buffers blew the budget.

This module gives kernels the shared pieces:

* ``BUDGET_BYTES`` — the conservative admission budget (13 MiB) under the
  default limit that ``corr_pallas.fused_eligible`` has used since round
  2; the 3 MiB gap is the measured headroom Mosaic's own temporaries
  need.
* ``SCAN_LIMIT_BYTES`` / ``scan_compiler_params()`` / ``scan_rows_parts``
  — the scan-body kernels' explicit limit, the compiler params that
  carry it, and their estimate, calibrated from the compiler's own
  reports.
* ``preflight(parts, where)`` — a loud pre-launch check: given the
  kernel's named buffer estimate, raise ``ValueError`` with the itemized
  breakdown *before* ``pallas_call`` hands the config to Mosaic, instead
  of after a multi-minute compile.
* ``log_fallback(flag, shape, parts)`` — the ``auto`` counterpart: when
  a kernel's dispatch *wants* the fused path on TPU but the admission
  table rejects the shape, emit one structured warning naming the flag,
  the shape, and the estimate-vs-budget numbers — a silent fall-back to
  the slow path is a perf bug that hides for months.

Estimates are static (shape arithmetic only) and intentionally
conservative — over-admitting reproduces the raw Mosaic OOM this module
exists to prevent, while under-admitting merely falls back to the XLA
path.  Interpret mode (CPU tests) has no VMEM, so wrappers skip the
preflight when ``interpret=True``.
"""

from __future__ import annotations

import logging
from typing import Mapping

_LOG = logging.getLogger(__name__)

#: Mosaic's default scoped-VMEM limit: what a ``pallas_call`` that passes
#: no compiler params is held to. The corr and MSDA kernels live under it
#: (the corr forward asks for its band scratch on top of it).
LIMIT_BYTES = 16 * 2 ** 20

#: Conservative admission budget under the default limit: leaves ~3 MiB
#: for Mosaic temporaries.
BUDGET_BYTES = 13 * 2 ** 20

#: The scan-body kernels (GRU, motion, fused step) keep a whole
#: multi-conv chain resident and need several times the default: they ask
#: Mosaic for this much explicitly (``scan_compiler_params``) and are
#: admitted against it. A v5e core has 128 MiB of VMEM; this leaves 28
#: MiB for whatever XLA keeps there around the call.
SCAN_LIMIT_BYTES = 100 * 2 ** 20

#: Bytes of scoped VMEM Mosaic takes per flattened assembly row
#: (``(TH + 2*halo) * W`` rows), by kernel and compute-dtype width, at the
#: canonical RAFT-large widths (hidden 128, x 256, 324 corr channels).
#: Read from ``used_scoped_memory_configs`` in the compiled text (jax
#: 0.9.0 / libtpu 0.0.34, v5e) of compiles under a 1 GiB limit, batch 2,
#: TH in {4, 8, 16}, at 55x128 (Sintel), 46x62 (chairs), 48x156 (KITTI)
#: and 135x240 (1080p). Mosaic's footprint is elastic — under a tighter
#: limit the same kernel compiles into less, by an amount that varies
#: with batch and surroundings — so the unlimited figure is the upper
#: bound and the only safe thing to admit against. It is linear in the
#: row span with no constant term, and bf16 costs most of what f32 does
#: (the v5e VPU computes the elementwise tail in f32 either way). The
#: motion and step figures were re-read when ``convf1`` and the flow
#: head's last conv stopped taking an MXU pass a tap: the 49 + 9 per-tap
#: temporaries had been a third of it (motion 24.3 / 27.5 before).
#: Largest observed, KiB/row: GRU 13.4 (bf16) / 19.3 (f32); motion
#: 16.0 / 21.5 (at W=62) — rounded up.
_ROW_BYTES = {
    "gru": {2: 14 * 1024, 4: 24 * 1024},
    "motion": {2: 17 * 1024, 4: 23 * 1024},
}

#: The fused step kernel's scoped VMEM by compute-dtype width:
#: ``(weights, bytes a column of the padded W for the rows the stages
#: keep, bytes a flattened row of the tile's TH x W)``. Its body streams:
#: a stage computes a grid step's TH rows into a VMEM span that also
#: holds the image rows its readers need above them, however tall the
#: tile, so the footprint is no longer proportional to one span. Read
#: the same way as ``_ROW_BYTES`` (1 GiB limit, batch 2, 'mgf'; 'mg'
#: reads 3-5 MiB less) at 55x128, 46x62, 48x156, 135x240 and 30x64, TH
#: in {4, 8, 16}: bf16 16.2 / 24.3 / 40.3 MiB at Sintel (75.2 MiB at TH
#: 16 when every tile recomputed its halo), 25.4 / 40.8 / 70.8 at
#: 1080p; f32 49.1 / 81.6 at Sintel TH 8 / 16, 82.4 at 1080p TH 8.
#: Fitted: bf16 5.6 MiB + 22.5 KiB x W + 16 KiB x TH x W, f32 11 MiB +
#: 45 KiB x W + 32.5 KiB x TH x W — each rounded up by about a tenth;
#: the estimate covers every probe by 4-25 %.
STEP_BYTES = {
    2: (6 * 2 ** 20, 24 * 1024, 18 * 1024),
    4: (12 * 2 ** 20, 48 * 1024, 36 * 1024),
}


def scan_compiler_params(dimension_semantics=None):
    """The TPU compiler params every scan-body ``pallas_call`` passes:
    the explicit scoped-VMEM limit their admission is sized from, and
    for a kernel whose grid steps depend on each other which axes may
    run in any order and which in order."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=SCAN_LIMIT_BYTES,
                                dimension_semantics=dimension_semantics)


def total_bytes(parts: Mapping[str, int]) -> int:
    """Sum a kernel's named buffer estimate (bytes per name)."""
    return sum(parts.values())


def fits(parts: Mapping[str, int], budget: int = BUDGET_BYTES) -> bool:
    """Whether the estimate fits the admission budget."""
    return total_bytes(parts) <= budget


def preflight(parts: Mapping[str, int], where: str,
              budget: int = BUDGET_BYTES) -> None:
    """Raise a clear ``ValueError`` if ``parts`` exceeds the admission
    budget — called by kernel wrappers immediately before ``pallas_call``
    so an oversized config fails in microseconds with an itemized
    breakdown instead of a raw Mosaic scoped-VMEM OOM after compile.

    ``where`` names the kernel/config for the message (e.g.
    ``"corr fused forward (tq=512)"``). ``budget`` is the default-limit
    budget unless the kernel passes its own (``SCAN_LIMIT_BYTES``).
    """
    total = total_bytes(parts)
    if total <= budget:
        return
    mb = 2 ** 20
    items = ", ".join(f"{k}={v / mb:.2f} MB"
                      for k, v in sorted(parts.items(),
                                         key=lambda kv: -kv[1]))
    raise ValueError(
        f"{where}: estimated VMEM {total / mb:.2f} MB exceeds the "
        f"{budget / mb:.0f} MB admission budget. Breakdown: {items}. "
        f"Shrink the tile or shard the input instead of letting Mosaic "
        f"hit a raw scoped-VMEM OOM (BASELINE.md 'Query tile 512')."
    )


def choose_rows(ladder, w: int, parts_fn,
                budget: int = SCAN_LIMIT_BYTES) -> int | None:
    """Generic row-tile admission ladder shared by the scan-body kernels.

    Walks ``ladder`` (descending TH candidates) and returns the first
    tile height that is sublane-aligned for the flattened ``(th*w, C)``
    view (``(th * w) % 8 == 0``) and whose ``parts_fn(th)`` estimate
    ``fits`` the scan-body budget; ``None`` if no rung admits (caller
    falls back to the XLA path via ``log_fallback``).  Larger tiles
    amortize weight-stationary reuse across more rows, so the ladder is
    ordered biggest-first and the *first* admitted rung wins.
    """
    for th in ladder:
        if (th * w) % 8:
            continue
        if fits(parts_fn(th), budget):
            return th
    return None


def scan_rows_parts(kind: str, rows: int, dtype_bytes: int,
                    channel_scale: float = 1.0) -> dict:
    """Named scoped-VMEM estimate of one scan-body launch whose working
    span is ``rows`` flattened assembly rows (tile + halos, times W).

    What Mosaic puts on its stack for these kernels is proportional to
    the assembly span and nothing else that varies at run time — block
    windows, weights and the live intermediates all scale with it. The
    earlier shape-arithmetic estimates ("phase-peak liveness")
    under-counted Mosaic 1.5x (GRU) to 4.7x (motion) and admitted tiles
    the compiler then refused, so the estimate is now Mosaic's own
    per-row figure from ``_ROW_BYTES``; ``tests/test_chip_compile.py``
    compiles the admitted tiles for the chip, compares the figure with
    what the compiler used, and is the judge of it. ``channel_scale`` stretches the figure
    for a GRU wider than the calibrated C=128/Cx=256."""
    return {"assembly_rows_live":
            int(rows * _ROW_BYTES[kind][dtype_bytes] * channel_scale)}


def log_fallback(flag: str, shape: str, parts: Mapping[str, int],
                 budget: int = SCAN_LIMIT_BYTES) -> None:
    """One loud structured line when ``<flag>=auto`` rejects a TPU launch
    and falls back to the XLA path — the estimate that failed admission,
    at the kernel's smallest tile, against the budget. Called at trace
    time (once per compiled shape, not per step)."""
    mb = 2 ** 20
    _LOG.warning(
        "%s=auto: falling back to the XLA path for shape %s — smallest-"
        "tile VMEM estimate %.2f MB exceeds the %.0f MB admission "
        "budget. Set %s=0 to silence, or use a narrower shape to admit "
        "the fused kernel.",
        flag, shape, total_bytes(parts) / mb, budget / mb, flag)
