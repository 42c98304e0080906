"""Fused on-demand windowed correlation — Pallas TPU kernel.

TPU-native equivalent of the reference's ``alt_cuda_corr`` CUDA extension
(reference ``alt_cuda_corr/correlation_kernel.cu:19-119`` forward,
``:122-256`` backward): compute, for every query pixel, the correlation of
its feature vector against bilinear samples of the target feature map in a
``(2r+1)^2`` window around the current flow estimate — without ever
materializing the ``(B, HW, HW)`` all-pairs volume in HBM.

Design (TPU-first, not a CUDA translation):

* The CUDA kernel walks a ``(2r+2)^2`` integer neighborhood per pixel and
  bilinear-*scatters* dot products into the output window. Scatters and
  per-pixel gathers are the wrong shape for TPU. Instead we use two facts:

  1. **Blockwise recompute**: for a tile of ``TQ`` query pixels, the rows of
     the all-pairs volume they need are MXU matmuls of the query tile
     against target-row chunks. Results live only in VMEM and are consumed
     immediately — the flash-attention memory pattern applied to the
     correlation volume (the quadratic object of this workload, SURVEY.md
     §5 "long-context equivalent").

  2. **Separable bilinear windows**: a bilinear sample at ``(cx+ox, cy+oy)``
     factors into 1-D "hat" weights ``max(0, 1-|y-(cy+oy)|)`` times
     ``max(0, 1-|x-(cx+ox)|)``. Each of the ``2r+1`` y-offsets keeps an
     accumulator of the target rows' correlation slices weighted by the
     y-side hat, summed in order of ``y``; a final x-side hat contraction
     emits the window. Pure multiply-accumulate on the VPU — no gather, no
     scatter. Rows/columns outside the image simply never contribute, which
     reproduces ``grid_sample(padding_mode='zeros')`` exactly (the
     semantics of ``raft_tpu.ops.sampling.bilinear_sampler``).

  3. **Diagonal y-sweep** (forward): row ``y`` meets y-offset ``off`` with
     weight ``hat(y - off - cy_n)``, which is zero for every query of the
     tile unless the diagonal ``d = y - off`` lies in ``[floor(min cy),
     ceil(max cy)]``. The kernel parks the band's chunk products in a VMEM
     scratch and folds diagonal by diagonal, ``_DIAG_BLOCK`` at a time, so
     that an accumulator is read and written once a block: of the ``(rows
     of the band) x (2r+1)`` pairs a dense sweep folds it visits ``(live
     diagonals) x (2r+1)`` (``sweep_stats`` counts both). The pairs left
     out have weight exactly 0 and the rest keep their order, so the
     result is bit-identical to the dense sweep's. A tile whose band is
     taller than the scratch or whose diagonals would fold no fewer pairs
     (flow spread over much of the image), a level too short to hold one
     block, and ``band="off"`` keep the dense sweep: every row of a chunk
     into every y-offset. A 2-D tile's window (item 4) always folds by
     diagonals: its level is parked whole.

  4. **2-D query tiles and column windows** (forward): a tile of 256
     queries is a ``TH x TW`` rectangle of the query grid
     (``choose_query_tile``: 8 x 32 on Sintel's 55 x 128 feature grid,
     from a count of the work a query over shapes alone; the raster tile
     of 256 consecutive queries where the count says so or the grid is
     under 256 queries, one or two rows). Its weights then reach a few
     rows *and* a few dozen columns of each level: a window of ``xw``
     columns, in blocks of 8 (the float32 sublane tile), from the block
     ``_column_window`` gives. The level is laid out once a pair, outside
     the refinement loop (``lookup_operands``, ``_block_columns``), so
     that a chunk's window is one contiguous slice: its products, sweeps
     and x-side contraction run over ``xw`` columns instead of the whole
     width (56 and 40 of 128 and 64 at Sintel). The columns left out
     carry weight exactly 0 and every window row still sums at most two
     nonzero terms a side, so the result is the raster launch's bit for
     bit. A tile whose columns one window does not hold reads the level
     a window at a time, the same code in a loop, each window's columns
     added once. Input and output blocks are the tile's rectangle of
     ``(B, H, W, ·)`` arrays, read and written in place; only the
     coordinates are permuted into tile order, each call. The backward
     keeps raster tiles.

  Everything is strictly 2-D inside the kernel (Mosaic's vector layout
  requirement) and laid out **query-minor**: the query-tile axis is the lane
  dimension, so the y-sweep's row chunks land on the sublane axis and the
  target width only needs 8-alignment (not 128), minimizing padding for
  narrow training crops.

Round-3 performance redesign (VERDICT r2 #2 — the kernel lost to the
materialized path at KITTI eval, 12.1 vs 18.1 pairs/s):

* **Dynamic y-band skipping.** The hat weight of query ``n`` is *exactly
  zero* for target rows outside ``[cy_n - r - 1, cy_n + r + 1]``, so each
  query tile only needs the rows in the band spanned by its own
  ``[min(cy), max(cy)]``. The kernel computes that band from the (already
  VMEM-resident) coordinates and runs a dynamic-bound ``fori_loop`` over
  row *chunks*, skipping both the MXU matmul and the VPU sweep for
  untouched chunks — numerics-exact, worst case (wild flow spread) equals
  the full sweep. RAFT's lookups are ``grid + flow`` with smooth flow, so
  a query tile typically touches ~``2(r+1) + tile_rows`` of the ``H2``
  target rows: 2 tile rows a raster tile at Sintel's width, 8 a 2-D one,
  whose column window then cuts the width instead.
* **All pyramid levels in ONE kernel launch.** The pooled feature levels
  are passed as separate VMEM-resident inputs and looped statically inside
  the kernel: one launch per lookup instead of four, and the query tile's
  features/coords are loaded once for all levels.
* **Scratch-ref accumulators.** The y-offset accumulators live in a VMEM
  scratch ref updated in place; the previous formulation concatenated
  ``2r+1`` fresh blocks per target row and added them into a carried array,
  doubling the sweep's VPU traffic.
* **Optional bf16 MXU operands** (``mxu_dtype='bfloat16'``): the
  correlation matmuls read ``f1``/``f2`` as bfloat16 with float32
  accumulation (``preferred_element_type``) — 4x MXU throughput, the same
  contract as the model's mixed-precision policy. All hat-weight
  arithmetic and accumulation stay float32. The *backward* matmuls also
  round the assembled f32 cotangent to bfloat16 (standard mixed-precision
  backprop; gradients carry bf16-rounding error the forward avoids —
  bounded in ``test_bf16_mxu_operands_close_to_f32``).

* Backward is the transpose of the same banded pipeline: the x-side
  adjoint is assembled once per (tile, level), then a dynamic-bound chunk
  loop assembles dL/d(corr chunk) in registers and feeds two MXU matmuls
  per chunk; ``fmap2`` gradients accumulate across query tiles in VMEM via
  output-block revisiting — no atomics, unlike the CUDA kernel's
  ``atomicAdd`` (``correlation_kernel.cu:229-238``). Coordinates get zero
  gradient, matching the CUDA extension (``coords_grad`` is allocated but
  never written, ``correlation_kernel.cu:307``) and the per-iteration
  ``coords1.detach()`` upstream (reference ``core/raft.py:124``).

VMEM envelope: a launch lives under Mosaic's scoped limit, 16 MiB by
default (``vmem.LIMIT_BYTES``), and is admitted when its named buffers
(``corr_vmem_parts``: the pooled target levels, Σ_l ``H2lp*W2lp x C``,
plus per-tile scratch) come to 13 MiB (``vmem.BUDGET_BYTES``) or less;
the rest is what Mosaic takes beyond the estimate (it double-buffers the
resident levels: 13.9 MB at Sintel over bfloat16 features, compiled for a
v5e at batch 128). The forward's band scratch rides on top: the launch
asks for the default limit plus the scratch and ``_BAND_HEADROOM``, and
is admitted against the budget plus the scratch, so what fitted without
it fits with it. The banded backward no longer needs its former ``(H2*W2p
x TQ)`` cotangent scratch. At stride-8 feature resolution this holds for
full Sintel and KITTI eval forward passes and for all reference training
crop sizes. Residency is set by the *input* dtype: bfloat16 feature maps
(the mixed-precision policy) halve the envelope; ``mxu_dtype`` alone only
changes the per-chunk cast, not what is staged.

Numerics: accumulation in float32 regardless of input or MXU dtype; parity
with the jnp reference ``raft_tpu.models.corr.windowed_correlation`` is
asserted in ``tests/test_corr_pallas.py``.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops import layout as klayout
from raft_tpu.ops import vmem
from raft_tpu.utils.envflags import env_bool, env_int_choice

# Rows per banded chunk: one MXU matmul + unrolled sweep per chunk. 8 keeps
# the dynamic-slice starts sublane-aligned for every 8-aligned level width.
_CHUNK = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _choose_tile(n: int) -> int:
    """Query-tile (lane-axis) size. The banded pipeline's per-tile VMEM is
    small (chunked matmuls, no full-level scratch), so the tile is sized
    for grid-overhead amortization; lane-dim blocks must stay
    128-divisible once the grid has more than one tile.
    ``RAFT_CORR_TILE`` overrides for measurement (trace-time read, like
    ``RAFT_CORR_BAND``), capped at 256: ``fused_eligible`` budgets the
    per-tile scratch at tq=256, and 512 measured a Mosaic scoped-VMEM
    stack OOM (17.4 MB vs the 16 MB limit) at Sintel resolution —
    larger tiles cannot be admitted without also shrinking the resident
    pyramid the kernel depends on."""
    tile = env_int_choice(
        "RAFT_CORR_TILE", (0, 128, 256), 0,
        hint="0/unset = auto; lane-dim blocks must be a multiple of 128 "
             "and larger tiles measured a Mosaic scoped-VMEM OOM")
    tile = tile or (256 if n >= 256 else 128)
    return min(tile, _round_up(n, 128))


def _mxu(mxu_dtype: str):
    return jnp.bfloat16 if mxu_dtype == "bfloat16" else jnp.float32


def _dot_precision(mdt):
    """Trace-time MXU pass-count lever (see sampling.corr_precision):
    ``RAFT_CORR_PRECISION=highest`` makes the kernel's f32 dots
    f32-faithful (multi-pass) instead of the TPU default bf16-operand
    passes. Gated to f32 operands: Mosaic rejects HIGHEST on bf16 dots
    (measured on-chip round 5 — MosaicError INTERNAL on every band
    mode), and multi-pass is meaningless for bf16 anyway."""
    if mdt != jnp.float32:
        return jax.lax.Precision.DEFAULT
    from raft_tpu.ops.sampling import corr_precision
    return corr_precision()


def _hat(dist: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(0.0, 1.0 - jnp.abs(dist))


def _x_iota(w2p: int, tq: int) -> jnp.ndarray:
    """(W2P, TQ) iota along the sublane (x-position) axis."""
    return jax.lax.broadcasted_iota(jnp.int32, (w2p, tq), 0).astype(
        jnp.float32)


def _band_chunks(cy, radius, h2l, nchunks):
    """Chunk-index range [c_lo, c_hi) of target rows whose hat weight can
    be nonzero for ANY query in the tile. Exact: row y contributes to
    query n iff |y - cy_n - off| < 1 for some |off| <= r."""
    lo = jnp.maximum(jnp.floor(jnp.min(cy)) - (radius + 1), 0.0)
    hi = jnp.minimum(jnp.ceil(jnp.max(cy)) + (radius + 1),
                     jnp.float32(h2l - 1))
    c_lo = jnp.minimum(lo.astype(jnp.int32) // _CHUNK, nchunks)
    c_hi = jnp.minimum(hi.astype(jnp.int32) // _CHUNK + 1, nchunks)
    return c_lo, c_hi


def _guarded(pred, body):
    @pl.when(pred)
    def _():
        body()


def _span_loop(band: str, lo, hi, most: int, body):
    """Run ``body(i)`` (effects-only: VMEM-ref stores, no carry) for ``i``
    in the traced range ``[lo, hi)`` of ``[0, most)``: a traced-bound
    ``fori_loop`` (``"dynamic"``), or a static trip count of ``most``
    with a ``pl.when`` on each step (``"static"``)."""
    if band == "dynamic":
        jax.lax.fori_loop(lo, hi, lambda i, c: (body(i), c)[1], 0)
        return
    jax.lax.fori_loop(0, most, lambda i, c: (_guarded(
        jnp.logical_and(i >= lo, i < hi), lambda: body(i)), c)[1], 0)


def _chunk_loop(band: str, cy, radius, h2l, nchunks, body):
    """Run ``body(yc)`` (effects-only: VMEM-ref stores, no carry) over the
    row chunks a query tile can touch, under one of three band modes:

    * ``"dynamic"`` — traced-bound ``fori_loop`` over exactly
      ``[c_lo, c_hi)``. Fewest iterations, but a dynamic-trip-count loop
      is the one construct of this kernel never yet compiled by Mosaic
      on real hardware (VERDICT r3 weak #2).
    * ``"static"`` — masked-static: a *static* trip count (``nchunks``,
      known at trace time) with a per-chunk ``@pl.when`` predicate.
      Skipped chunks still skip the MXU matmul and the VPU sweep, so
      ~all of the banded traffic win survives, using only constructs the
      round-2 kernel already proved on-chip (static loops + ``pl.when``).
    * ``"off"`` — unconditional full sweep (the round-2 kernel).
    """
    if band == "off":
        jax.lax.fori_loop(0, nchunks, lambda yc, c: (body(yc), c)[1], 0)
        return
    c_lo, c_hi = _band_chunks(cy, radius, h2l, nchunks)
    _span_loop(band, c_lo, c_hi, nchunks, body)


# Diagonals folded per step of the diagonal sweep: each y-offset
# accumulator is read and written once a step, so a step's partial sums
# stay in registers across its diagonals; a step past the last live
# diagonal folds dead ones. Of 1, 2, 3, 4 and 8, 2 was fastest at both
# Sintel launches on the v5e (PERF.md section 6, PR 30).
_DIAG_BLOCK = 2


def _live_diagonals(cy, radius, h2l):
    """Integer range ``[d_lo, d_hi]`` of the diagonals ``d = y - offset``
    that can carry a nonzero y-side weight for ANY query of the tile:
    ``hat(d - cy_n) > 0`` iff ``|d - cy_n| < 1``, so only
    ``floor(min cy) <= d <= ceil(max cy)`` (exact under the float32
    rounding of ``cy + offset``: rounding is monotone and the bounds are
    integers), cut to the diagonals that cross the level's rows at all."""
    lo = jnp.clip(jnp.floor(jnp.min(cy)), -radius, h2l + radius)
    hi = jnp.clip(jnp.ceil(jnp.max(cy)), -radius - 1, h2l - 1 + radius)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _column_window(cx, radius, w2pl, xb, nwb):
    """Column block ``jb0`` where a tile's window of ``nwb`` blocks of
    ``xb`` columns starts, and whether it holds every column whose x-side
    weight can be nonzero for ANY query of the tile: ``hat(x - cx_n -
    off) > 0`` only for ``floor(min cx) - r <= x <= ceil(max cx) + r``
    (exact under the float32 rounding of ``cx + off``, as for
    ``_live_diagonals``), cut to the level's columns. A NaN coordinate
    fits no window."""
    lo = jnp.clip(jnp.floor(jnp.min(cx)) - radius, 0.0, w2pl - 1.0)
    hi = jnp.clip(jnp.ceil(jnp.max(cx)) + radius, 0.0, w2pl - 1.0)
    jb0 = jnp.minimum(lo.astype(jnp.int32) // xb, w2pl // xb - nwb)
    fits = jnp.logical_and(
        lo >= (jb0 * xb).astype(jnp.float32),
        hi < ((jb0 + nwb) * xb).astype(jnp.float32))
    return jb0, fits


def _fwd_kernel(cx_ref, cy_ref, f1_ref, *refs, radius: int, scale: bool,
                levels: tuple, mxu_dtype: str, band: str,
                rescale: bool, tout: bool = False, band_rows: int = 0,
                windows: tuple | None = None):
    """refs = (f2_l0..f2_lN, out, t1_scratch[, band_scratch][,
    rows_scratch]); levels = ((h2l, h2lp, w2pl),…) with h2lp the
    CHUNK-padded row count (padded rows are zero features → zero
    contribution). ``band_rows``: rows of a level the band scratch holds
    (0: no scratch, dense sweep only). ``tout``: store the output block
    transposed — (TQ, L*win*win) instead of (L*win*win, TQ) — so the
    wrapper's swapaxes disappears (the b64 profile measured the XLA
    transpose copy at ~12 ms/step); one in-VMEM transpose per tile
    instead.

    ``windows``: a 2-D query tile's launch (``None``: a raster one). The
    f1 and out blocks are ``(1, TH, TW, ·)`` rectangles of the query
    grid, queries in raster order within the tile, and each level's
    ``(xb, xw)`` says how its columns are laid out (``_block_columns``)
    and how wide a window of them the tile reads: ``xw == w2pl`` reads
    the whole width; else the tile reads the ``xw`` columns from
    ``_column_window``'s block, and a tile they do not hold reads the
    level a window at a time. ``rows_scratch`` holds a windowed level's
    output rows, which its windows add up."""
    nl = len(levels)
    f2_refs, out_ref, t1_ref = refs[:nl], refs[nl], refs[nl + 1]
    band_ref = refs[nl + 2] if band_rows else None
    rows_ref = refs[-1]                  # a windowed launch's last scratch
    win = 2 * radius + 1
    mdt = _mxu(mxu_dtype)
    f1 = f1_ref[0]
    if windows is not None:                              # (TH, TW, C)
        f1 = f1.reshape(-1, f1.shape[-1])
    f1 = f1.astype(mdt)                                  # (TQ, C)
    tq, c = f1.shape
    # Transposed once a tile: a product against (TQ, C) would transpose
    # it again for every chunk (2.7 ms of a 36.3 ms Sintel call, v5e).
    f1t = f1.T                                           # (C, TQ)
    cx0 = cx_ref[0].astype(jnp.float32)                  # (1, TQ)
    cy0 = cy_ref[0].astype(jnp.float32)
    inv_sqrt_c = 1.0 / (c ** 0.5)

    def level_pass(l, cx, cy, xb, jb0, xw, first=None):
        """One level's window rows (a list of (1, TQ)) over ``xw``
        columns from column block ``jb0`` (``xb`` columns a block),
        leaving out those left of column ``first`` where it is given."""
        h2l, h2lp, w2pl = levels[l]
        nchunks = h2lp // _CHUNK
        nxb, nwb = w2pl // xb, xw // xb
        t1_ref[0:win * xw, :] = jnp.zeros((win * xw, tq), jnp.float32)

        # The closures below are traced where they are defined, inside
        # this pass: they read its variables as is.
        def chunk_corr(yc):
            # The query tile's slice of the all-pairs volume for one row
            # chunk: one MXU matmul, consumed by the sweep that follows.
            # Its rows are the window's column blocks in order, each
            # block's CHUNK rows of ``xb`` columns (row-major for a
            # level read whole: ``xb == xw == w2pl``).
            start = pl.multiple_of((yc * nxb + jb0) * (_CHUNK * xb),
                                   _CHUNK * xb)
            f2c = f2_refs[l][0, pl.ds(start, _CHUNK * xw), :]
            return jax.lax.dot_general(
                f2c.astype(mdt), f1t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_dot_precision(mdt))              # (CHUNK*XW, TQ)

        def dense_body(yc):
            # Dense sweep: every row of the chunk into all y-offsets. A
            # whole-width read's alone: a window folds by diagonals.
            corr = chunk_corr(yc)
            y0f = (yc * _CHUNK).astype(jnp.float32)
            for r_i in range(_CHUNK):
                row = corr[r_i * xw:(r_i + 1) * xw, :]
                for i in range(win):                     # y-offset index
                    wy = _hat(y0f + r_i - (cy + (i - radius)))  # (1, TQ)
                    t1_ref[i * xw:(i + 1) * xw, :] += wy * row

        def dense_sweep():
            _chunk_loop(band, cy, radius, h2l, nchunks, dense_body)

        # Chunks of this level the band scratch can hold at once; a
        # window's whole level (``_tiling`` windows no level it cannot).
        held = (h2lp if xw < w2pl else min(band_rows, h2lp)) // _CHUNK
        if xw == w2pl and (band == "off"
                           or held * _CHUNK < win + _DIAG_BLOCK):
            dense_sweep()
        else:
            c_lo, c_hi = _band_chunks(cy, radius, h2l, nchunks)
            d_lo, d_hi = _live_diagonals(cy, radius, h2l)
            if band == "off":               # a window's: skip nothing
                c_lo, c_hi = 0, nchunks
                d_lo, d_hi = -radius, h2l - 1 + radius
            nblk = (d_hi - d_lo + _DIAG_BLOCK) // _DIAG_BLOCK

            def diagonal_sweep():
                # Park the band's chunk products row-major (row, x), then
                # fold diagonal by diagonal: on diagonal d, y-offset
                # ``off`` meets the one row y = d + off. Every (row,
                # offset) pair left out has weight exactly 0 for the
                # whole tile and the rest keep their order of y, so each
                # accumulator sees the same nonzero terms in the same
                # order as the dense sweep: bit-identical. A row outside
                # the image, or a diagonal past d_hi, is folded with
                # weight 0 from a row that was computed (never from
                # stale scratch).
                if nwb == 1:
                    for k in range(held):
                        def park(k=k):
                            band_ref[k * _CHUNK * xw:(k + 1) * _CHUNK * xw,
                                     :] = chunk_corr(c_lo + k)
                        _guarded(c_lo + k < c_hi, park)
                else:
                    def park(yc):
                        corr = chunk_corr(yc)
                        base = (yc - c_lo) * (_CHUNK * xw)
                        for r_i in range(_CHUNK):
                            for j in range(nwb):
                                at = pl.multiple_of(
                                    base + r_i * xw + j * xb, 8)
                                src = (j * _CHUNK + r_i) * xb
                                band_ref[pl.ds(at, xb), :] = corr[
                                    src:src + xb, :]
                    _span_loop(band, c_lo, c_hi, nchunks, park)
                y_lo, y_hi = c_lo * _CHUNK, c_hi * _CHUNK - 1

                def block(jb):
                    d0 = d_lo + jb * _DIAG_BLOCK
                    for i in range(win):                 # y-offset index
                        off = i - radius
                        acc = t1_ref[i * xw:(i + 1) * xw, :]
                        for jj in range(_DIAG_BLOCK):
                            y = d0 + jj + off
                            live = jnp.logical_and(
                                d0 + jj <= d_hi,
                                jnp.logical_and(y >= 0, y < h2l))
                            wy = _hat(y.astype(jnp.float32) - (cy + off))
                            wy = wy * live.astype(jnp.float32)  # (1, TQ)
                            at = (jnp.clip(y, y_lo, y_hi) - y_lo) * xw
                            acc = acc + wy * band_ref[
                                pl.ds(pl.multiple_of(at, 8), xw), :]
                        t1_ref[i * xw:(i + 1) * xw, :] = acc

                # A window's level is parked whole: its diagonals, at
                # most h2l + 2r, never outrun the scratch.
                most = (held * _CHUNK if xw == w2pl else
                        h2l + 2 * radius + _DIAG_BLOCK - 1) // _DIAG_BLOCK
                _span_loop(band, 0, nblk, most, block)

            if xw < w2pl:
                # A window folds by diagonals whatever the flow: its band
                # always fits, and flow spread over the whole level costs
                # h2l + 2r rows' folds where a dense sweep folds h2lp.
                diagonal_sweep()
            else:
                # The dense sweep stays for a band of more chunks than the
                # scratch holds, and wherever it folds no more pairs than
                # the diagonals would (flow spread over much of the image;
                # diagonals that mostly miss a short level): the worst
                # case is the dense sweep's. An empty band is the dense
                # sweep's too: its chunk loop runs no chunk.
                diagonal = jnp.logical_and(
                    c_hi - c_lo <= held,
                    nblk * _DIAG_BLOCK < (c_hi - c_lo) * _CHUNK)
                _guarded(diagonal, diagonal_sweep)
                _guarded(jnp.logical_not(diagonal), dense_sweep)

        # x-side hat contraction → window rows in the reference order
        # (core/corr.py delta grid: first window axis moves x). Columns
        # outside the window carry weight exactly 0, and a window row
        # sums at most two nonzero terms: the same sum in any order.
        xi = _x_iota(xw, tq)
        if xw < w2pl:
            xi = xi + (jb0 * xb).astype(jnp.float32)
        if first is not None:
            keep = xi >= first.astype(jnp.float32)
        rows = []
        for a in range(win):                             # x-offset index
            vx = _hat(xi - (cx + (a - radius)))          # (XW, TQ)
            if first is not None:
                vx = jnp.where(keep, vx, 0.0)
            for b in range(win):                         # y-offset index
                t1_b = t1_ref[b * xw:(b + 1) * xw, :]
                rows.append(jnp.sum(t1_b * vx, axis=0, keepdims=True))
        return rows

    level_rows = []
    nrows = _round_up(win * win, 8)
    for l, (_, _, w2pl) in enumerate(levels):
        # rescale=False reproduces the fork drift that samples every
        # pooled level at UN-rescaled coords (core/corr.py:38-42) — the
        # semantics the sparse-keypoint family was trained with.
        lscale = (1.0 / 2 ** l) if rescale else 1.0
        cx = cx0 * lscale
        cy = cy0 * lscale
        xb, xw = windows[l] if windows is not None else (w2pl, w2pl)
        if xw == w2pl:
            level_rows += level_pass(l, cx, cy, xb, 0, w2pl)
            continue
        nwb, nxb = xw // xb, w2pl // xb
        jb0, fits = _column_window(cx, radius, w2pl, xb, nwb)
        # A tile whose columns the window does not hold reads them all,
        # a window at a time: the k-th of those that cover the level
        # (the last one moved back inside it, the columns the one before
        # read left out). Each output row gains at most two nonzero
        # terms over all windows: the same sum in any grouping.
        nwin = -(-nxb // nwb)
        slot = slice(l * nrows, (l + 1) * nrows)
        rows_ref[slot, :] = jnp.zeros((nrows, tq), jnp.float32)

        def window(k, l=l, cx=cx, cy=cy, xb=xb, xw=xw, nwb=nwb, nxb=nxb,
                   jb0=jb0, fits=fits, slot=slot):
            start = jnp.where(fits, jb0, jnp.minimum(k * nwb, nxb - nwb))
            first = jnp.where(fits, jb0, k * nwb) * xb
            rows = level_pass(l, cx, cy, xb, start, xw, first)
            rows.append(jnp.zeros((nrows - len(rows), tq), jnp.float32))
            rows_ref[slot, :] += jnp.concatenate(rows)

        _span_loop(band, 0, jnp.where(fits, 1, nwin), nwin, window)
        level_rows.append(rows_ref[slot, :][:win * win])

    # ONE aligned full-block store: per-level stores at row offset
    # l*win*win (81, 162, …) would be sublane-unaligned.
    out = jnp.concatenate(level_rows, axis=0)            # (L*win*win, TQ)
    if scale:
        out = out * inv_sqrt_c
    if windows is not None:
        # The tile's rectangle of the (B, H, W, L*win*win) output.
        out_ref[0] = out.T.astype(out_ref.dtype).reshape(out_ref.shape[1:])
        return
    # Consumer dtype + axis order emitted at the boundary (layout-contract
    # invariants 1-2, raft_tpu.ops.layout): bit-identical to casting the
    # float32 result outside the kernel, but saves the XLA-level
    # convert+copy at the custom-call boundary (measured ~2% of the b64
    # headline step as pure layout tax). ``tout`` → (TQ, L*win*win).
    klayout.boundary_store(out_ref, out, transpose=tout)


def _bwd_kernel(cx_ref, cy_ref, f1_ref, *refs, radius: int, scale: bool,
                levels: tuple, mxu_dtype: str, band: str,
                rescale: bool):
    """refs = (f2_l0.., g, df1, df2_l0.., u_scratch, df1_scratch). df2
    blocks are revisited across the query-tile grid axis: zeroed at tile
    0, then band-accumulated — no atomics. df1 accumulates in a VMEM
    scratch (not a loop carry) so the chunk body is effects-only and can
    sit under the masked-static mode's ``pl.when`` predicate."""
    nl = len(levels)
    f2_refs = refs[:nl]
    g_ref = refs[nl]
    df1_ref = refs[nl + 1]
    df2_refs = refs[nl + 2:nl + 2 + nl]
    u_ref = refs[nl + 2 + nl]
    df1_acc_ref = refs[nl + 3 + nl]
    win = 2 * radius + 1
    mdt = _mxu(mxu_dtype)
    f1 = f1_ref[0].astype(jnp.float32)                   # (TQ, C)
    tq, c = f1.shape
    f1m = f1.astype(mdt)
    cx0 = cx_ref[0].astype(jnp.float32)
    cy0 = cy_ref[0].astype(jnp.float32)
    t = pl.program_id(1)

    # ONE aligned full-block load; per-level row offsets (l*win*win) are
    # sublane-unaligned, so slice the loaded value instead of the ref.
    g_all = g_ref[0].astype(jnp.float32)                 # (L*win*win, TQ)
    if scale:
        g_all = g_all * (1.0 / (c ** 0.5))

    df1_acc_ref[...] = jnp.zeros((tq, c), jnp.float32)
    for l, (h2l, h2lp, w2pl) in enumerate(levels):
        lscale = (1.0 / 2 ** l) if rescale else 1.0
        cx = cx0 * lscale
        cy = cy0 * lscale
        nchunks = h2lp // _CHUNK
        g = g_all[l * win * win:(l + 1) * win * win, :]  # (win*win, TQ)

        # U_b[x, n] = sum_a g[a*win+b, n] * hat(x - cx_n - (a - r)) — the
        # x-side adjoint, shared across the y sweep.
        xi = _x_iota(w2pl, tq)
        for b in range(win):
            acc = jnp.zeros((w2pl, tq), jnp.float32)
            for a in range(win):
                vx = _hat(xi - (cx + (a - radius)))
                acc = acc + g[a * win + b:a * win + b + 1, :] * vx
            u_ref[b * w2pl:(b + 1) * w2pl, :] = acc

        @pl.when(t == 0)
        def _(l=l):
            df2_refs[l][0] = jnp.zeros_like(df2_refs[l][0])

        def body(yc, l=l, w2pl=w2pl, cy=cy):
            base = yc * (_CHUNK * w2pl)
            y0f = (yc * _CHUNK).astype(jnp.float32)
            # Assemble dL/d(corr chunk) from the adjoint with y-side hats.
            g2_rows = []
            for r_i in range(_CHUNK):
                g2y = jnp.zeros((w2pl, tq), jnp.float32)
                for b in range(win):
                    wy = _hat(y0f + r_i - (cy + (b - radius)))
                    g2y = g2y + wy * u_ref[b * w2pl:(b + 1) * w2pl, :]
                g2_rows.append(g2y)
            g2 = jnp.concatenate(g2_rows, axis=0)        # (CHUNK*W2PL, TQ)
            f2c = f2_refs[l][0, pl.ds(base, _CHUNK * w2pl), :]
            df1_acc_ref[...] += jax.lax.dot_general(
                g2.astype(mdt), f2c.astype(mdt), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_dot_precision(mdt))              # (TQ, C)
            contrib = jax.lax.dot_general(
                g2.astype(mdt), f1m, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_dot_precision(mdt))              # (CHUNK*W2PL, C)
            df2_refs[l][0, pl.ds(base, _CHUNK * w2pl), :] += contrib

        _chunk_loop(band, cy, radius, h2l, nchunks, body)
    df1_ref[0] = df1_acc_ref[...]


def _level_geometry(pyramid_shapes):
    """Per-level (h2l, h2lp, w2pl): width padded to sublane alignment,
    rows padded to the chunk size (both paddings are zero features →
    exactly zero contribution)."""
    levels = []
    for (h2, w2) in pyramid_shapes:
        w2p = _round_up(w2, 8)
        h2p = _round_up(h2, _CHUNK)
        levels.append((h2, h2p, w2p))
    return tuple(levels)


#: Columns a block of a windowed level's layout: the float32 sublane
#: tile, so that a window starts on a tile boundary of the accumulators.
_XBLOCK = 8

#: Flow spread across a query tile, in level-0 pixels, in x and in y, at
#: which ``choose_query_tile`` sizes the windows and counts the work.
_SPREAD = 4.0

#: Widths of the 2-D query tiles weighed against the raster one: each a
#: multiple of bfloat16's 16-row sublane tile, so that a (TH, TW, C)
#: block reads as (TQ, C) in place. 16 x 16 tiles measured slowest of
#: all on the Sintel grid on a v5e (PERF.md section 6), though the count
#: put them second: a band 16 rows tall holds the most chunks and
#: diagonals.
_TILE_WIDTHS = (32, 64, 128)


class _Tiling(NamedTuple):
    """A 2-D query tile of ``th`` rows by ``tw`` columns and, per level,
    ``(xb, xw)``: the column blocks its layout is cut into and the width
    of the window a tile reads (``xb == xw == w2pl``: the whole width)."""
    th: int
    tw: int
    windows: tuple


def _tiling(th, tw, levels, radius, rescale=True) -> _Tiling:
    """The windows of ``(th, tw)`` tiles: room for the columns a tile's
    weights reach at ``_SPREAD`` (``ceil(max cx) - floor(min cx) + 2r +
    1``) wherever its first column falls in a block. A level no wider
    than that, or too tall for the band scratch to hold whole at that
    width, is read whole."""
    held_rows = (_band_scratch_rows(levels, radius)
                 * max(w2pl for (_, _, w2pl) in levels))
    windows = []
    for l, (_, h2lp, w2pl) in enumerate(levels):
        s = 0.5 ** l if rescale else 1.0
        need = math.ceil((tw - 1 + _SPREAD) * s) + 2 * radius + 2
        xw = _round_up(need + _XBLOCK - 1, _XBLOCK)
        windowed = xw < w2pl and h2lp * xw <= held_rows
        windows.append((_XBLOCK, xw) if windowed else (w2pl, w2pl))
    return _Tiling(th, tw, tuple(windows))


def _query_work(h, w, levels, radius, c, rescale, tiling) -> float:
    """The forward's work a query on an ``h x w`` grid, counted from
    shapes at ``_SPREAD``: per level and tile, the columns read times the
    band's product rows (``c / 32`` a row: the MXU's passes over C, which
    made them ~8x a VPU row in PERF.md's split of a Sintel call), the
    x-side contraction's (2r+1)^2 multiply-reduces and the diagonal
    sweep's folds, over the tiles the padded grid holds."""
    win = 2 * radius + 1
    if tiling is None:
        tq = _choose_tile(h * w)
        tiles = -(-h * w // tq)
        rows = tq / w + (tq % w != 0)      # image rows a raster tile spans
        widths = [w2pl for (_, _, w2pl) in levels]
    else:
        tiles = -(-h // tiling.th) * -(-w // tiling.tw)
        rows = tiling.th
        widths = [xw for (_, xw) in tiling.windows]
    work = 0.0
    for l, ((_, h2lp, _), xw) in enumerate(zip(levels, widths)):
        s = 0.5 ** l if rescale else 1.0
        ext = math.ceil((rows - 1 + _SPREAD) * s)
        band = min(ext + 2 * radius + 4 + _CHUNK - 1, h2lp)
        diagonals = min(_round_up(ext + 2, _DIAG_BLOCK), band)
        work += xw * (band * c / 32 + win * win + diagonals * win)
    return tiles * work / (h * w)


def choose_query_tile(h: int, w: int, levels, radius: int, c: int,
                      rescale: bool = True) -> _Tiling | None:
    """The forward's query tile for an ``h x w`` grid over ``levels``
    (``_level_geometry``): the raster tile (``None``) or the 2-D tile of
    256 queries, shorter than the grid, whose ``_query_work`` counts
    least, the raster one on a tie. A grid of under 256 queries, or of
    one or two rows, keeps the raster tile."""
    if _choose_tile(h * w) != 256:
        return None
    best, least = None, _query_work(h, w, levels, radius, c, rescale, None)
    for tw in _TILE_WIDTHS:
        if 256 // tw >= h:
            continue
        tiling = _tiling(256 // tw, tw, levels, radius, rescale)
        work = _query_work(h, w, levels, radius, c, rescale, tiling)
        if work < least:
            best, least = tiling, work
    return best


def _pad_level(f2, h2p, w2p):
    b, h2, w2, c = f2.shape
    f2 = jnp.pad(f2, ((0, 0), (0, h2p - h2), (0, w2p - w2), (0, 0)))
    return f2.reshape(b, h2p * w2p, c)


def _block_columns(f2, h2p, w2p, xb):
    """A padded level ``(B, H2p*W2p, C)``, row-major, in the forward
    kernel's windowed layout: ``CHUNK``-row chunks in order, each chunk
    its column blocks of ``xb`` columns in order, each block its rows of
    ``xb`` columns. A window of column blocks is then one contiguous
    slice of a chunk; ``xb == w2p`` is the row-major layout itself."""
    if xb == w2p:
        return f2
    b, _, c = f2.shape
    f2 = f2.reshape(b, h2p // _CHUNK, _CHUNK, w2p // xb, xb, c)
    return f2.transpose(0, 1, 3, 2, 4, 5).reshape(b, h2p * w2p, c)


def _pallas_fwd(f1, f2s, cx, cy, radius, scale, interpret, levels, tq,
                mxu_dtype, band, rescale, out_dtype, tout=False,
                tiling=None):
    """A raster launch (``tiling`` None): f1 (B, Np, C); f2s per-level
    (B, H2lp*W2lp, C), row-major; cx/cy (B, 1, Np) at level-0 scale;
    Np % tq == 0. Returns (B, L*win*win, Np) — query-minor; transposed by
    the wrapper — or, with ``tout``, (B, Np, L*win*win) already in the
    consumer's order (kernel-side per-tile transpose; see
    RAFT_CORR_TOUT).

    A 2-D tile's launch (a ``_Tiling``; ``tq`` is then ``TH * TW``): f1
    (B, Hp, Wp, C), tiled by ``(TH, TW)`` rectangles; f2s in ``_block_columns``
    layout by the tiling's column blocks; cx/cy (B, 1, Np) in tile order (tile
    ``(i, j)`` holds queries ``i*tq*nw + j*tq ..``, raster within the
    tile). Returns (B, Hp, Wp, L*win*win)."""
    b, c = f1.shape[0], f1.shape[-1]
    win = 2 * radius + 1
    nl = len(levels)
    w2p_max = max(w2pl for (_, _, w2pl) in levels)
    if tiling is not None:
        tq = tiling.th * tiling.tw

    band_rows = _band_scratch_rows(levels, radius)
    kwargs = dict(radius=radius, scale=scale, levels=levels,
                  mxu_dtype=mxu_dtype, band=band, rescale=rescale,
                  tout=tout, band_rows=band_rows)
    scratch = [pltpu.VMEM((win * w2p_max, tq), jnp.float32)]
    if band_rows:
        scratch.append(pltpu.VMEM((band_rows * w2p_max, tq), jnp.float32))
    limit = (vmem.LIMIT_BYTES + _BAND_HEADROOM
             + _band_scratch_bytes(levels, radius, tq))
    if tiling is None:
        grid = (b, f1.shape[1] // tq)
        qmap = lambda bi, ti: (bi, 0, ti)
        f1_spec = pl.BlockSpec((1, tq, c), lambda bi, ti: (bi, ti, 0))
        f2map = lambda bi, ti: (bi, 0, 0)
        # Layout-contract invariant 3: output tiled over the query axis;
        # the consumer-major order pairs with the kernel's transposed
        # store.
        out_specs, out_shape = klayout.query_tiled_out(
            b, f1.shape[1], nl * win * win, tq, out_dtype,
            consumer_major=tout)
    else:
        _, hp, wp, _ = f1.shape
        th, tw = tiling.th, tiling.tw
        grid = (b, hp // th, wp // tw)
        nw = wp // tw
        qmap = lambda bi, i, j: (bi, 0, i * nw + j)
        f1_spec = pl.BlockSpec((1, th, tw, c), lambda bi, i, j: (bi, i, j, 0))
        f2map = lambda bi, i, j: (bi, 0, 0)
        out_specs = pl.BlockSpec((1, th, tw, nl * win * win),
                                 lambda bi, i, j: (bi, i, j, 0))
        out_shape = jax.ShapeDtypeStruct((b, hp, wp, nl * win * win),
                                         out_dtype)
        kwargs["windows"] = tiling.windows
        for (_, h2lp, w2pl), (_, xw) in zip(levels, tiling.windows):
            if xw < w2pl and h2lp * xw > band_rows * w2p_max:
                raise ValueError(f"a window of {xw} columns over {h2lp} "
                                 "rows outgrows the band scratch")
        nrows = nl * _round_up(win * win, 8)
        scratch.append(pltpu.VMEM((nrows, tq), jnp.float32))
        limit += nrows * tq * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kwargs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq), qmap),
            pl.BlockSpec((1, 1, tq), qmap),
            f1_spec,
        ] + [pl.BlockSpec((1, f2.shape[1], c), f2map) for f2 in f2s],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
        name=klayout.KERNEL_NAMES["corr_fwd"],
    )(cx, cy, f1, *f2s)


def _pallas_bwd(f1, f2s, cx, cy, g, radius, scale, interpret, levels, tq,
                mxu_dtype, band, rescale):
    b, np_, c = f1.shape
    win = 2 * radius + 1
    nl = len(levels)
    grid = (b, np_ // tq)
    w2p_max = max(w2pl for (_, _, w2pl) in levels)

    kernel = functools.partial(_bwd_kernel, radius=radius, scale=scale,
                               levels=levels, mxu_dtype=mxu_dtype,
                               band=band, rescale=rescale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, 1, tq), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, tq, c), lambda bi, ti: (bi, ti, 0)),
        ] + [
            pl.BlockSpec((1, f2.shape[1], c), lambda bi, ti: (bi, 0, 0))
            for f2 in f2s
        ] + [
            pl.BlockSpec((1, nl * win * win, tq),
                         lambda bi, ti: (bi, 0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, c), lambda bi, ti: (bi, ti, 0)),
        ] + [
            pl.BlockSpec((1, f2.shape[1], c), lambda bi, ti: (bi, 0, 0))
            for f2 in f2s
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, np_, c), jnp.float32),
        ] + [
            jax.ShapeDtypeStruct(f2.shape, jnp.float32) for f2 in f2s
        ],
        scratch_shapes=[pltpu.VMEM((win * w2p_max, tq), jnp.float32),
                        pltpu.VMEM((tq, c), jnp.float32)],
        interpret=interpret,
        name=klayout.KERNEL_NAMES["corr_bwd"],
    )(cx, cy, f1, *f2s, g)


def _unblock_columns(f2, h2p, w2p, xb):
    """``_block_columns`` undone: the row-major level."""
    if xb == w2p:
        return f2
    b, _, c = f2.shape
    f2 = f2.reshape(b, h2p // _CHUNK, w2p // xb, _CHUNK, xb, c)
    return f2.transpose(0, 1, 3, 2, 4, 5).reshape(b, h2p * w2p, c)


def _raster_coords(coords, np_):
    """``(B, H, W, 2)`` coordinates in raster order, padded to ``np_``:
    cx/cy (B, 1, Np). Edge-padded (the last real one replicated) rather
    than zero-padded: padded queries contribute nothing (their f1 rows
    and cotangents are zero), but a zero cy would drag the tail tile's
    y-band up to row 0 and defeat the band skip for queries near the
    image bottom."""
    b, h, w, _ = coords.shape
    cf = jnp.pad(coords.reshape(b, h * w, 2),
                 ((0, 0), (0, np_ - h * w), (0, 0)), mode="edge")
    return cf[..., 0][:, None, :], cf[..., 1][:, None, :]


def _tile_coords(coords, th, tw):
    """``(B, H, W, 2)`` coordinates edge-padded to the ``(th, tw)``
    tiles' grid and permuted into tile order: cx/cy (B, 1, Hp*Wp)."""
    b, h, w, _ = coords.shape
    hp, wp = _round_up(h, th), _round_up(w, tw)
    cf = jnp.pad(coords, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)),
                 mode="edge")
    cf = cf.reshape(b, hp // th, th, wp // tw, tw, 2).transpose(
        0, 1, 3, 2, 4, 5).reshape(b, 1, hp * wp, 2)
    return cf[..., 0], cf[..., 1]


def _forward(f1, f2s, coords, radius, scale, interpret, levels, tq,
             mxu_dtype, band, rescale, out_dtype, tout, tiling):
    """The forward launch over ``LookupOperands``' arrays and ``coords``
    (B, H, W, 2): a raster one (``tiling`` None) or a 2-D tile's.
    Returns (B, H, W, L*win*win)."""
    b, h, w, _ = coords.shape
    nf = len(levels) * (2 * radius + 1) ** 2
    if tiling is None:
        cx, cy = _raster_coords(coords, f1.shape[1])
        out = _pallas_fwd(f1, f2s, cx, cy, radius, scale, interpret,
                          levels, tq, mxu_dtype, band, rescale, out_dtype,
                          tout)
        if not tout:
            out = jnp.swapaxes(out, 1, 2)                # (B, Np, L*win*win)
        return out[:, :h * w].reshape(b, h, w, nf)
    cx, cy = _tile_coords(coords, tiling.th, tiling.tw)
    out = _pallas_fwd(f1, f2s, cx, cy, radius, scale, interpret, levels,
                      tq, mxu_dtype, band, rescale, out_dtype, True, tiling)
    return out[:, :h, :w]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _windowed(f1, f2s, coords, radius, scale, interpret, levels, tq,
              mxu_dtype, band, rescale, out_dtype, tout, tiling):
    return _forward(f1, f2s, coords, radius, scale, interpret, levels, tq,
                    mxu_dtype, band, rescale, out_dtype, tout, tiling)


def _windowed_fwd(f1, f2s, coords, radius, scale, interpret, levels, tq,
                  mxu_dtype, band, rescale, out_dtype, tout, tiling):
    out = _forward(f1, f2s, coords, radius, scale, interpret, levels, tq,
                   mxu_dtype, band, rescale, out_dtype, tout, tiling)
    return out, (f1, f2s, coords)


def _windowed_bwd(radius, scale, interpret, levels, tq, mxu_dtype, band,
                  rescale, out_dtype, tout, tiling, res, g):
    # Whatever tile the forward took, the backward runs raster tiles of
    # ``tq`` over the row-major levels: a 2-D tile's operands are taken
    # back to those, and the gradients to theirs.
    f1, f2s, coords = res
    b, h, w, _ = coords.shape
    c = f1.shape[-1]
    if tiling is not None:
        f1 = _raster_f1(f1[:, :h, :w], tq)
        f2s = tuple(_unblock_columns(f2, h2lp, w2pl, xb)
                    for f2, (_, h2lp, w2pl), (xb, _) in
                    zip(f2s, levels, tiling.windows))
    np_ = f1.shape[1]
    cx, cy = _raster_coords(coords, np_)
    # The backward kernel consumes the query-minor cotangent; one XLA
    # transpose here (training only — eval never differentiates).
    # out_dtype shapes only the forward output; the cotangent g already
    # arrives in it, and gradient outputs are always float32.
    g = g.reshape(b, h * w, g.shape[-1])
    g = jnp.swapaxes(jnp.pad(g, ((0, 0), (0, np_ - h * w), (0, 0))), 1, 2)
    grads = _pallas_bwd(f1, f2s, cx, cy, g, radius, scale, interpret,
                        levels, tq, mxu_dtype, band, rescale)
    df1, df2s = grads[0].astype(f1.dtype), grads[1:]
    if tiling is not None:
        hp, wp = _round_up(h, tiling.th), _round_up(w, tiling.tw)
        df1 = jnp.pad(df1[:, :h * w].reshape(b, h, w, c),
                      ((0, 0), (0, hp - h), (0, wp - w), (0, 0)))
        df2s = tuple(_block_columns(df2, h2lp, w2pl, xb)
                     for df2, (_, h2lp, w2pl), (xb, _) in
                     zip(df2s, levels, tiling.windows))
    # Zero coordinate gradient — the contract of the reference extension
    # (correlation_kernel.cu:307) and of the detach-per-iteration scan.
    return (df1, tuple(df2.astype(f2.dtype) for df2, f2 in zip(df2s, f2s)),
            jnp.zeros_like(coords))


_windowed.defvjp(_windowed_fwd, _windowed_bwd)


def _raster_f1(fmap1, tq):
    """``(B, H, W, C)`` query features in raster order, zero-padded to a
    multiple of ``tq``: (B, Np, C)."""
    b, h, w, c = fmap1.shape
    n = h * w
    return jnp.pad(fmap1.reshape(b, n, c),
                   ((0, 0), (0, _round_up(n, tq) - n), (0, 0)))


@jax.tree_util.register_pytree_node_class
class LookupOperands:
    """The fused lookup's operands in the kernel's own layout: query
    features padded to its tiles (``f1``: (B, Hp, Wp, C) for a 2-D tile,
    (B, Np, C) raster) and every pooled level padded and, where the tile
    reads a window of its columns, cut into column blocks (``levels``:
    (B, H2lp*W2lp, C)). Built once a pair (``lookup_operands``), outside
    the refinement loop, and read by each iteration's
    ``windowed_lookup``; the level shapes and the tile ride along as
    static data."""

    def __init__(self, f1, levels, shapes, tiling):
        self.f1, self.levels = f1, tuple(levels)
        self.shapes, self.tiling = tuple(shapes), tiling

    def tree_flatten(self):
        return (self.f1, self.levels), (self.shapes, self.tiling)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _operands(fmap1, pyramid2, tiling) -> LookupOperands:
    shapes = tuple(tuple(f2.shape[1:3]) for f2 in pyramid2)
    levels = _level_geometry(shapes)
    f2s = tuple(_pad_level(f2, h2p, w2p)
                for f2, (_, h2p, w2p) in zip(pyramid2, levels))
    if tiling is None:
        f1 = _raster_f1(fmap1, _choose_tile(fmap1.shape[1] * fmap1.shape[2]))
    else:
        _, h, w, _ = fmap1.shape
        f1 = jnp.pad(fmap1, ((0, 0), (0, _round_up(h, tiling.th) - h),
                             (0, _round_up(w, tiling.tw) - w), (0, 0)))
        f2s = tuple(_block_columns(f2, h2lp, w2pl, xb)
                    for f2, (_, h2lp, w2pl), (xb, _) in
                    zip(f2s, levels, tiling.windows))
    return LookupOperands(f1, f2s, shapes, tiling)


def lookup_operands(fmap1: jnp.ndarray, pyramid2, radius: int,
                    rescale: bool = True) -> LookupOperands:
    """What ``windowed_lookup`` reads, built once a pair: ``fmap1``
    ``(B, H, W, C)`` and the pooled ``pyramid2`` levels in the layout of
    ``choose_query_tile``'s tile for these shapes. Plain XLA pads and
    transposes, differentiable as such."""
    _, h, w, c = fmap1.shape
    levels = _level_geometry([f2.shape[1:3] for f2 in pyramid2])
    return _operands(fmap1, pyramid2,
                     choose_query_tile(h, w, levels, radius, c, rescale))


def sweep_stats(coords, pyramid_shapes, radius: int, tile=None,
                channels: int = 256) -> dict:
    """What the forward kernel computes for a batch of lookup
    coordinates, tile by tile, from its own rules mapped over the tiles
    in numpy (no kernel launch); canonical ``rescale=True`` levels, any
    band mode but "off". Per level and in total over all query tiles:

    * the y-sweep: the (target row, y-offset) pairs the kernel folds
      (``diagonal``: whole blocks of diagonals where it takes the
      diagonal sweep, which a window always does, the dense count where
      it keeps the dense one) beside the pairs a dense sweep of the same
      chunk-aligned band folds, all ``2r+1`` offsets of every row
      (``dense``), each once a window the tile reads; ``live`` is how
      many pairs can carry a nonzero weight (a row of the image on a
      live diagonal);
    * ``products``: target positions the tile's chunk products multiply
      each of its queries against (band rows x columns read);
      ``xside``: the columns read, which are the sublane rows of each of
      the x-side contraction's (2r+1)^2 multiply-reduces;
      ``tiles_windowed``: tiles whose columns one window holds (0 where
      the tiling reads the level whole); the others read it a window at
      a time.

    ``coords``: ``(B, H, W, 2)`` pixel coords (x, y) at level-0 scale;
    ``pyramid_shapes``: per-level ``(h2l, w2l)``; ``tile``: an int for
    raster tiles of that many queries, a ``_Tiling``, or ``None`` for the
    wrapper's choice (``choose_query_tile`` at ``channels`` of query
    features: RAFT's 256 by default; RAFT-small's 128 picks the same
    tile on the Sintel grid)."""
    import numpy as np

    coords = np.asarray(coords, np.float32)
    b, h, w, _ = coords.shape
    geometry = _level_geometry(pyramid_shapes)
    if tile is None:
        tile = (choose_query_tile(h, w, geometry, radius, channels)
                or _choose_tile(h * w))
    if isinstance(tile, _Tiling):
        th, tw = tile.th, tile.tw
        hp, wp = _round_up(h, th), _round_up(w, tw)
        cf = np.pad(coords, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)),
                    mode="edge").reshape(b, hp // th, th, wp // tw, tw, 2)
        cf = cf.transpose(0, 1, 3, 2, 4, 5).reshape(-1, th * tw, 2)
        windows = tile.windows
    else:
        cf = np.pad(coords.reshape(b, h * w, 2),
                    ((0, 0), (0, _round_up(h * w, tile) - h * w), (0, 0)),
                    mode="edge").reshape(-1, tile, 2)
        windows = tuple((w2pl, w2pl) for (_, _, w2pl) in geometry)
    win = 2 * radius + 1
    offs = np.arange(win) - radius                       # (win,)
    band_rows = _band_scratch_rows(geometry, radius)
    levels = []
    for l, ((h2l, h2lp, w2pl), (xb, xw)) in enumerate(zip(geometry,
                                                           windows)):
        cxl, cyl = (cf[..., k] * np.float32(1.0 / 2 ** l) for k in (0, 1))
        c_lo, c_hi = (np.asarray(v, np.int64) for v in jax.vmap(
            lambda t: _band_chunks(t, radius, h2l, h2lp // _CHUNK))(cyl))
        d_lo, d_hi = (np.asarray(v, np.int64) for v in jax.vmap(
            lambda t: _live_diagonals(t, radius, h2l))(cyl))
        chunks = np.maximum(c_hi - c_lo, 0)
        nblk = (d_hi - d_lo + _DIAG_BLOCK) // _DIAG_BLOCK
        if xw < w2pl:           # a window folds by diagonals, always
            fits = np.asarray(jax.vmap(lambda t: _column_window(
                t, radius, w2pl, xb, xw // xb)[1])(cxl))
            windows_read = np.where(fits, 1, -(-w2pl // xw))
            diagonal = chunks > 0
        else:
            fits = np.zeros(len(cyl), bool)
            windows_read = np.ones(len(cyl), np.int64)
            held = min(band_rows, h2lp) // _CHUNK
            diagonal = ((held * _CHUNK >= win + _DIAG_BLOCK)
                        & (chunks <= held)
                        & (nblk * _DIAG_BLOCK < chunks * _CHUNK))
        width = windows_read * xw
        dense = chunks * _CHUNK * win * windows_read
        # rows of the image that offset ``off`` meets on a live diagonal
        y_lo = np.maximum(d_lo[:, None] + offs, 0)
        y_hi = np.minimum(d_hi[:, None] + offs + 1, h2l)
        live = np.where(chunks > 0, np.maximum(y_hi - y_lo, 0).sum(axis=1), 0)
        levels.append({
            "diagonal": int(np.where(
                diagonal, nblk * _DIAG_BLOCK * win * windows_read,
                dense).sum()),
            "dense": int(dense.sum()), "live": int(live.sum()),
            "tiles_diagonal": int(diagonal.sum()),
            "products": int((chunks * _CHUNK * width).sum()),
            "xside": int(width.sum()), "tiles_windowed": int(fits.sum())})
    out = {key: sum(v[key] for v in levels)
           for key in ("diagonal", "dense", "live", "products", "xside")}
    out.update(levels=levels, tiles=int(cf.shape[0]), tq=int(cf.shape[1]),
               tile=([tile.th, tile.tw] if isinstance(tile, _Tiling)
                     else None))
    return out


def _resolve_band(band) -> str:
    """Normalize the band argument to one of ``{"dynamic","static","off"}``.
    ``None`` reads ``RAFT_CORR_BAND`` (unset/"1" → dynamic, "static" →
    masked-static, "0" → off); bools are accepted for backward
    compatibility (True → dynamic, False → off)."""
    if band is None:
        band = {"0": "off", "static": "static"}.get(
            os.environ.get("RAFT_CORR_BAND", "1"), "dynamic")
    if band is True:
        band = "dynamic"
    elif band is False:
        band = "off"
    if band not in ("dynamic", "static", "off"):
        raise ValueError(f"band must be 'dynamic', 'static' or 'off' "
                         f"(or True/False/None), got {band!r}")
    return band


#: Rows of chunk products (of the widest level) the forward kernel parks
#: for the diagonal sweep: room for a band of 32 - 2 (r + 1) - 7 live
#: diagonals wherever its first row falls in a chunk.
_BAND_ROWS = 32


#: What the diagonal sweep's own temporaries were seen to take beyond the
#: scratch (0.9 MB at Sintel, compiled for a v5e at batch 32 and 128).
_BAND_HEADROOM = 2 * 2 ** 20


def _band_scratch_rows(levels, radius: int) -> int:
    """Rows of the forward kernel's band scratch: ``_BAND_ROWS``, no more
    than the tallest level has, and 0 (dense sweep only) where not even
    one block of diagonals with its ``2r + 1`` rows would fit."""
    rows = min(_BAND_ROWS, max((h2lp for (_, h2lp, _) in levels), default=0))
    return rows if rows >= 2 * radius + 1 + _DIAG_BLOCK else 0


def _band_scratch_bytes(levels, radius: int, tq: int) -> int:
    """The band scratch rides on top of Mosaic's default scoped limit:
    the forward launch asks for this much more (``_pallas_fwd``) and is
    admitted against this much more (``fused_eligible``), so a shape
    that fitted without the scratch fits with it."""
    w2p_max = max([8] + [w2pl for (_, _, w2pl) in levels])
    return _band_scratch_rows(levels, radius) * w2p_max * tq * 4


def corr_vmem_parts(pyramid_shapes, channels: int,
                    dtype_bytes: int = 4, radius: int = 4,
                    differentiable: bool = False,
                    tq: int = 256) -> dict:
    """Named scoped-VMEM buffer estimate of one launch of the fused corr
    kernel — the forward, or with ``differentiable`` the backward, which
    holds more — in the shared currency of ``raft_tpu.ops.vmem``
    (``fits`` for the eligibility gate, ``preflight`` for the loud
    pre-launch check).

    ``tq`` defaults to the worst admissible query tile (256) so the
    eligibility gate stays tile-independent; the pre-launch preflight
    passes the actual tile."""
    levels = _level_geometry(pyramid_shapes)
    win = 2 * radius + 1
    w2p_max = max([8] + [w2pl for (_, _, w2pl) in levels])
    parts = {
        "pyramid_resident": sum(h2lp * w2pl * channels * dtype_bytes
                                for (_, h2lp, w2pl) in levels),
        # t1/u accumulator scratch at the actual window size, f32 —
        # doubled for margin (chunk matmul operands, out block)
        "tile_scratch": 2 * win * w2p_max * tq * 4,
    }
    if differentiable:
        # f32 df2 output blocks, one a level
        parts["df2_blocks_f32"] = sum(h2lp * w2pl * channels * 4
                                      for (_, h2lp, w2pl) in levels)
        # g block (L*win^2, TQ) + df1 scratch/out (TQ, C), all f32
        parts["bwd_g_df1"] = (len(levels) * win * win * tq
                              + 2 * tq * channels) * 4
        return parts
    band = _band_scratch_bytes(levels, radius, tq)
    if band:
        # the band's chunk products, parked for the diagonal sweep
        parts["band_corr_f32"] = band
    return parts


def _admission_budget(parts) -> int:
    """The default budget, plus the band scratch the launch asks for on
    top of the default limit."""
    return vmem.BUDGET_BYTES + parts.get("band_corr_f32", 0)


def fused_eligible(pyramid_shapes, channels: int,
                   dtype_bytes: int = 4, radius: int = 4,
                   differentiable: bool = False) -> bool:
    """Whether the kernel's VMEM-resident layout holds for these levels:
    every pooled target level stays resident for a whole batch element,
    plus the per-tile scratch.

    ``differentiable=False`` budgets forward-pass residency (the eval
    path). When the lookup may be differentiated (training), pass
    ``differentiable=True``: the backward additionally keeps the
    per-level float32 ``df2`` output blocks plus the ``g`` cotangent
    block and ``df1`` accumulator resident, so the gate tightens rather
    than admitting a shape that compiles forward but fails Mosaic VMEM
    allocation in the backward. Training always runs on crops
    (SURVEY.md §2.5), which fit the tighter budget with a wide margin."""
    for (h2, w2) in pyramid_shapes:
        if h2 == 0 or w2 == 0:
            # Degenerate pooled level (tiny inputs): the jnp fallback
            # short-circuits it to zero windows; the kernel's BlockSpecs
            # can't express a zero-size input block.
            return False
    parts = corr_vmem_parts(pyramid_shapes, channels, dtype_bytes, radius,
                            differentiable)
    return vmem.fits(parts, _admission_budget(parts))


def windowed_correlation_pallas_fused(
        fmap1: jnp.ndarray, pyramid2, coords: jnp.ndarray, radius: int,
        scale: bool = True, mxu_dtype: str = "float32",
        interpret: bool | None = None,
        band: bool | None = None,
        rescale: bool = True,
        out_dtype=jnp.float32) -> jnp.ndarray:
    """All pyramid levels of the on-demand windowed lookup in ONE fused
    Pallas launch; numerically identical to concatenating
    ``raft_tpu.models.corr.windowed_correlation`` over the levels with
    ``coords / 2**level`` (``rescale=True``, canonical RAFT) or with
    un-rescaled ``coords`` at every level (``rescale=False`` — the fork
    drift the sparse-keypoint family was trained with,
    ``core/corr.py:38-42``).

    Args:
      fmap1: ``(B, H, W, C)`` query features.
      pyramid2: sequence of ``(B, H2l, W2l, C)`` pooled target levels.
      coords: ``(B, H, W, 2)`` pixel coords (x, y) at LEVEL-0 scale (the
        kernel applies the per-level ``1/2^l``).
      radius: lookup radius r; per-level window is ``(2r+1)^2``.
      scale: divide by ``sqrt(C)`` (reference ``core/corr.py:61``).
      mxu_dtype: ``'float32'`` or ``'bfloat16'`` operands for the
        correlation matmuls (accumulation is always float32).
      interpret: force Pallas interpreter mode (defaults to True off-TPU
        so the same tests run on CPU).
      band: y-band chunk-skipping mode — ``"dynamic"`` (traced-bound
        loop, fewest iterations), ``"static"`` (masked-static: static
        trip count + per-chunk ``pl.when``, zero Mosaic novelty, ~same
        traffic win) or ``"off"`` (full sweep). All three are
        numerics-exact. Default reads ``RAFT_CORR_BAND`` (unset/"1" →
        dynamic, "static", "0" → off); True/False accepted as
        dynamic/off.

      out_dtype: dtype of the returned windows (default float32).
        Emitted by the kernel's final store — bit-identical to casting
        the float32 accumulator afterwards (one rounding either way;
        ``test_out_dtype_bitexact_vs_external_cast``), but skips the
        XLA convert+copy at the custom-call boundary (~2% of the b64
        headline step). Gradients are always float32.

    The forward's query tile is ``choose_query_tile``'s for these shapes.

    Returns:
      ``(B, H, W, L*(2r+1)^2)`` ``out_dtype``, level-major on the last
      axis.
    """
    return windowed_lookup(lookup_operands(fmap1, pyramid2, radius, rescale),
                           coords, radius, scale, mxu_dtype, interpret, band,
                           rescale, out_dtype)


def _fused(fmap1, pyramid2, coords, radius, scale, mxu_dtype, interpret,
           band, rescale, out_dtype, tiling):
    """``windowed_correlation_pallas_fused`` with the forward's query
    tile given: ``None`` for raster tiles, else a ``_Tiling``."""
    return windowed_lookup(_operands(fmap1, pyramid2, tiling), coords,
                           radius, scale, mxu_dtype, interpret, band,
                           rescale, out_dtype)


def windowed_lookup(operands: LookupOperands, coords: jnp.ndarray,
                    radius: int, scale: bool = True,
                    mxu_dtype: str = "float32",
                    interpret: bool | None = None, band=None,
                    rescale: bool = True, out_dtype=jnp.float32):
    """``windowed_correlation_pallas_fused`` over operands built once a
    pair by ``lookup_operands`` (with the same ``radius`` and
    ``rescale``): one refinement iteration's lookup, no relayout."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    band = _resolve_band(band)
    _, h, w, _ = coords.shape
    c = operands.f1.shape[-1]
    levels = _level_geometry(operands.shapes)
    # the raster tile; a 2-D tile holds as many queries or fewer
    tq = _choose_tile(h * w)

    # VMEM preflight (shared with the GRU kernel, raft_tpu.ops.vmem):
    # fail loudly with an itemized requested-vs-16MB breakdown before
    # handing Mosaic a config it would reject with a raw scoped-VMEM
    # OOM after a long compile (the tile-512 case, BASELINE.md).
    # Forward-pass estimate — the launch being admitted here; interpret
    # mode has no VMEM to budget.
    if not interpret:
        parts = corr_vmem_parts(operands.shapes, c,
                                jnp.dtype(operands.f1.dtype).itemsize,
                                radius, tq=tq)
        vmem.preflight(parts, f"corr fused kernel (tq={tq})",
                       _admission_budget(parts))

    # Transposed output store (default ON): the kernel emits each output
    # tile query-major — (TQ, L*win*win) — deleting the XLA swapaxes
    # copy at the custom-call boundary for one in-VMEM per-tile
    # transpose (layout-contract invariant 2, raft_tpu.ops.layout).
    # Bit-exact (test_tout_bitexact); measured +1.4% on the
    # b64 headline (93.4 → 94.8 pairs/s, the copy.257 row of the
    # round-5 profile). RAFT_CORR_TOUT=0 restores the query-minor
    # store of a raster launch; trace-time read, like RAFT_CORR_BAND.
    tout = env_bool("RAFT_CORR_TOUT", True)
    return _windowed(operands.f1, operands.levels, coords, radius, scale,
                     interpret, levels, tq, mxu_dtype, band, rescale,
                     jnp.dtype(out_dtype), tout, operands.tiling)


def windowed_correlation_pallas(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                                coords: jnp.ndarray, radius: int,
                                scale: bool = True,
                                interpret: bool | None = None,
                                mxu_dtype: str = "float32",
                                band: bool | None = None) -> jnp.ndarray:
    """Single-level wrapper of the fused kernel — drop-in Pallas
    replacement for ``raft_tpu.models.corr.windowed_correlation``
    (``coords`` already at ``fmap2``'s scale).

    Returns ``(B, H, W, (2r+1)^2)`` float32 correlation features.
    """
    return windowed_correlation_pallas_fused(
        fmap1, (fmap2,), coords, radius, scale=scale, mxu_dtype=mxu_dtype,
        interpret=interpret, band=band)
