"""Fused SepConvGRU cell — Pallas TPU kernel.

One horizontal-then-vertical GRU step per launch, attacking the round-5
profile's dominant inefficiency: the refinement scan's update-block convs
ran at 5-16% MFU (~162 ms, 13% of the b64 step) under an XLA-chosen
batch-second-minor ``{3,0,2,1}`` layout, with every gate activation
(z, r, q, two full GRU steps) round-tripping HBM between conv launches
(BASELINE.md "Round-5 headline work"). This kernel is the same
keep-the-inner-loop-in-VMEM move ``corr_pallas.py`` proved for the
correlation lookup, applied to RAFT's other per-iteration hot path — the
ConvGRU update operator of the paper.

Design
------
* **Separable convs as shifted MXU matmuls.** A ``(1, 5)`` conv over NHWC
  is, per tap ``d ∈ {-2..2}``, a ``(rows, Cin) @ (Cin, Cout)`` matmul of
  the *row-shifted* input against that tap's weight slice; a ``(5, 1)``
  conv is the same with shifts of ``d*W`` rows. The kernel flattens each
  ``(H, W)`` tile to a 2-D ``(rows, channels)`` block — channels on the
  lane axis (128/256 for RAFT), flattened spatial on the sublane axis —
  so every tap is one MXU matmul and "image geometry" reduces to shift +
  mask: a column-validity mask for horizontal taps (``col + d ∈ [0, W)``)
  and a global-row-validity mask for vertical taps (``row + d ∈ [0, H)``),
  both exactly reproducing the convs' zero padding.
* **Gate kernels pre-concatenated.** The z and r convs of each step share
  their input, so their weights are merged along the output axis before
  launch (``pack_weights`` — the ``_concat_conv`` weight-merge idea from
  ``models/update.py``) and each tap feeds one ``(rows, Cin) @ (Cin, 2C)``
  matmul. The ``h``/``x`` halves of the concatenated GRU input get
  separate weight slices, so the ``concat([h, x])`` is never materialized.
  Since round 7 the x half generalizes to a *tuple of parts*
  (``split_x_weights``): when the fused motion encoder
  (``motion_pallas.py``) feeds this kernel, x arrives as
  ``(inp, [motion‖flow])`` with per-part weight row slices — conceptually
  the ``[inp | motion | flow]`` split — so ``concat([inp,
  motion_features])`` is never materialized between the two kernels
  either. A single-part x reproduces the round-6 kernel exactly (same
  operands, same accumulation order).
* **Fused VPU epilogue.** sigmoid/tanh/blend for both GRU steps run on
  the block while it is VMEM-resident; only the final hidden state is
  stored, in the consumer's dtype and axis order
  (``raft_tpu.ops.layout`` invariants 1-3) — inside the refinement scan
  the intermediate ``h`` after the horizontal step and all six gate
  activations never touch HBM.
* **Row-tile grid with clamped halo blocks.** Grid ``(B, Hpad/TH)``. The
  vertical step needs the horizontal step's output ±2 rows, whose r-gate
  needs ±2 more, so each launch assembles ``TH + 8`` rows: ``h`` and ``x``
  are passed *three times* with prev/cur/next block index maps (clamped
  at the edges; clamp garbage is neutralized by the row-validity masks).
  The horizontal step is recomputed on the 8 halo rows — ``(TH+8)/TH``
  redundant work, the classic halo-vs-relaunch trade — which is why the
  wrapper picks the largest ``TH ∈ {16, 8, 4}`` whose VMEM estimate fits
  (``raft_tpu.ops.vmem.preflight`` runs before every real launch).

Numerics
--------
Matmuls accumulate in float32 (``preferred_element_type``) and are cast
to the compute dtype before the bias add and nonlinearity — the same
contract as the flax path (float32 params, bf16 compute under the
mixed-precision policy). The tap decomposition changes the reduction
*order* vs ``lax.conv_general_dilated`` (per-tap partial sums instead of
one fused reduction), so parity with the flax ``SepConvGRU`` is
tolerance-checked, not bit-exact, even at f32 (
``tests/test_gru_pallas.py`` asserts ≤1e-5 relative at f32 and documents
the bf16 tolerance). ``RAFT_GRU_PALLAS=0`` restores the flax conv path
bit-for-bit.

The custom VJP differentiates a pure-jnp reference implementing the
*identical* shifted-matmul math (recompute-from-residuals, like the
banded corr kernel's backward strategy) — gradients flow to ``h``, ``x``
and the packed weights, and through ``pack_weights`` back to the flax
param tree. A hand-written Pallas backward kernel is on-hardware
performance debt; the forward is where the scan's HBM traffic lived.

``RAFT_GRU_PALLAS`` (trace-time, parsed by ``raft_tpu.utils.envflags``):
``auto``/unset — kernel on TPU when eligible, flax path otherwise (CPU
tests opt in explicitly, mirroring ``RAFT_CORR_BACKEND``); ``1`` — force
(interpret mode off-TPU; raises if ineligible); ``0`` — flax path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.ops import layout as klayout
from raft_tpu.ops import vmem
from raft_tpu.utils.envflags import env_enum

# Vertical halo rows on each side of a row tile: the vertical convs reach
# ±2 rows of the horizontal step's output, whose r-gate products reach ±2
# more. Row tiles must be at least this tall (halo comes from ONE
# neighboring block).
_HALO = 4

_TAPS = 5  # separable kernel width; offsets d = k - 2 for k in range(5)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Weight packing (the _concat_conv weight-merge idea, kernel-shaped)
# ---------------------------------------------------------------------------

def pack_weights(horiz, vert, hidden_dim: int):
    """Merge the six separable-conv param pairs into the kernel's 2-D
    matmul layout.

    Args:
      horiz: ``((kz, bz), (kr, br), (kq, bq))`` for the (1,5) step —
        kernels ``(1, 5, Cin, C)`` flax HWIO, biases ``(C,)``.
      vert: same for the (5,1) step — kernels ``(5, 1, Cin, C)``.
      hidden_dim: C; ``Cin = C + Cx`` (hidden ‖ input features).

    Returns a 12-tuple of 2-D arrays per step ``s``:
    ``wzr{s}h (5C, 2C)``, ``wzr{s}x (5Cx, 2C)`` — z‖r gate weights merged
    on the output axis (one matmul for both gates, exact: each output
    channel's dot product is unchanged) and split into the h-/x-input
    halves (so the ``concat([h, x])`` is never formed); ``wq{s}h (5C, C)``,
    ``wq{s}x (5Cx, C)``; biases ``bzr{s} (1, 2C)``, ``bq{s} (1, C)``.
    Rows are tap-major: tap ``k``'s slice is ``[k*Cin_part, (k+1)*Cin_part)``.

    Pure jnp on the existing param tree (untouched, so the torch-weight
    mapping survives); differentiable, so training gradients flow through
    the packing back to the flax params. XLA hoists it out of the
    refinement scan (loop-invariant).
    """
    c = hidden_dim

    def step(pairs, squeeze_axis):
        (kz, bz), (kr, br), (kq, bq) = pairs
        for k in (kz, kr, kq):
            if k.shape[squeeze_axis] != 1 or k.shape[3] != c:
                raise ValueError(
                    f"pack_weights: expected separable kernel with "
                    f"axis {squeeze_axis} == 1 and {c} output channels, "
                    f"got {k.shape}")
        kz, kr, kq = (jnp.squeeze(k, axis=squeeze_axis)
                      for k in (kz, kr, kq))          # (5, Cin, C)
        taps, cin, _ = kz.shape
        if taps != _TAPS or cin <= c:
            raise ValueError(
                f"pack_weights: expected ({_TAPS}, Cin>{c}, {c}) taps, "
                f"got {kz.shape}")
        cx = cin - c
        wzr = jnp.concatenate([kz, kr], axis=2)       # (5, Cin, 2C)
        wq = kq
        return (wzr[:, :c, :].reshape(_TAPS * c, 2 * c),
                wzr[:, c:, :].reshape(_TAPS * cx, 2 * c),
                wq[:, :c, :].reshape(_TAPS * c, c),
                wq[:, c:, :].reshape(_TAPS * cx, c),
                jnp.concatenate([bz, br]).reshape(1, 2 * c),
                bq.reshape(1, c))

    return step(horiz, 0) + step(vert, 1)


def _x_parts(m):
    """Normalize an x-weight entry (array or tuple of per-part slices)."""
    return tuple(m) if isinstance(m, (tuple, list)) else (m,)


def split_x_weights(mats, cxs):
    """Re-slice the packed x-input weights for a multi-part x.

    ``mats`` is the ``pack_weights`` 12-tuple whose x entries have
    tap-major rows over the *full* ``Cx = sum(cxs)`` input; ``cxs`` are
    the channel widths of the x parts the caller will pass as a tuple
    (e.g. ``(128, 128)`` for ``(inp, [motion‖flow])``). Each x-weight
    matrix is split into per-part matrices with the same tap-major row
    layout — tap ``k`` of part ``p`` owns rows ``[k*cxs[p],
    (k+1)*cxs[p])`` — so per-tap matmuls against the un-concatenated
    parts sum to exactly the full-input matmul. Pure differentiable
    slicing; a single-part split returns ``mats`` unchanged.
    """
    if len(cxs) == 1:
        return mats
    (wzr1h, wzr1x, wq1h, wq1x, bzr1, bq1,
     wzr2h, wzr2x, wq2h, wq2x, bzr2, bq2) = mats
    cx = sum(cxs)
    offs = []
    o = 0
    for cp in cxs:
        offs.append(o)
        o += cp

    def split(m):
        if m.shape[0] != _TAPS * cx:
            raise ValueError(
                f"split_x_weights: weight has {m.shape[0]} rows, "
                f"expected {_TAPS}*{cx} for x parts {cxs}")
        return tuple(
            jnp.concatenate(
                [m[k * cx + off:k * cx + off + cp] for k in range(_TAPS)],
                axis=0)
            for off, cp in zip(offs, cxs))

    return (wzr1h, split(wzr1x), wq1h, split(wq1x), bzr1, bq1,
            wzr2h, split(wzr2x), wq2h, split(wq2x), bzr2, bq2)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def gate_sigmoid(v):
    """Sigmoid of a gate tile, computed in float32 and cast back.

    Mosaic (jax 0.9.0) rejects ``jax.nn.sigmoid`` on a bf16 tile — the
    logistic lowering broadcasts an f32 constant into a bf16 vector and
    fails verification — and the v5e VPU/EUP has no bf16 path anyway, so
    the upcast is what the hardware does. Shared by the kernel, the fused
    step kernel and their jnp twins so all stay the same math; a no-op
    at f32."""
    return jax.nn.sigmoid(v.astype(jnp.float32)).astype(v.dtype)


def _shift_rows(v, s: int):
    """``out[n] = v[n + s]`` along the sublane axis, zero-filled at the
    edges (out-of-assembly sources are either image padding or rows whose
    contribution the validity masks zero anyway)."""
    if s == 0:
        return v
    pad = jnp.zeros((abs(s), v.shape[1]), v.dtype)
    if s > 0:
        return jnp.concatenate([v[s:], pad], axis=0)
    return jnp.concatenate([pad, v[:s]], axis=0)


def halo_assemble(blocks, g: int, hw: int):
    """Concatenate ``2n+1`` consecutive ``(g, C)`` tile blocks into one
    ``(g + 2*hw,  C)`` working span with ``hw`` halo rows per side.

    ``blocks`` are the neighbor block values in tile order
    ``[cur-n, ..., cur, ..., cur+n]`` where ``n = ceil(hw/g)`` — the
    generalization of the one-neighbor ``[prev[g-hw:], cur, next[:hw]]``
    assembly to halos DEEPER than the tile itself (the fused step
    kernel's combined receptive field, or motion's TH=4 rung where
    halo=5 > th=4).  Inner neighbors contribute whole blocks; only the
    outermost pair is sliced.  At grid edges the clamped index maps
    make outer blocks garbage, which the callers' global-row validity
    masks zero — exactly as in the n=1 case.
    """
    n = (len(blocks) - 1) // 2
    lead = hw - (n - 1) * g            # rows taken from the outermost pair
    parts = [blocks[0][g - lead:]]
    parts += list(blocks[1:n]) + [blocks[n]] + list(blocks[n + 1:2 * n])
    parts.append(blocks[2 * n][:lead])
    return jnp.concatenate(parts, axis=0)


def _gru_kernel(*refs, w: int, h_img: int, th: int, nparts: int):
    """One fused SepConvGRU step for a TH-row tile (+4 halo rows/side).

    ``refs`` is ``(hp, hc, hn, <3 refs per x part>, <weights>, out)``;
    the prev/cur/next triples are the SAME flattened ``(Hpad*W, C[in])``
    arrays under clamped block index maps (see ``_pallas_gru``); all six
    gate convs, both blends, and the intermediate hidden state live
    entirely in VMEM.
    """
    out_ref = refs[-1]
    hp_ref, hc_ref, hn_ref = refs[:3]
    xrefs = refs[3:3 + 3 * nparts]
    wr = refs[3 + 3 * nparts:-1]
    p = nparts
    # Weight layout (matches _flatten_mats): per step — wzr h, wzr x
    # parts, wq h, wq x parts, bzr, bq.
    wzr1h_ref, wzr1x_refs = wr[0], wr[1:1 + p]
    wq1h_ref, wq1x_refs = wr[1 + p], wr[2 + p:2 + 2 * p]
    bzr1_ref, bq1_ref = wr[2 + 2 * p], wr[3 + 2 * p]
    o = 4 + 2 * p
    wzr2h_ref, wzr2x_refs = wr[o], wr[o + 1:o + 1 + p]
    wq2h_ref, wq2x_refs = wr[o + 1 + p], wr[o + 2 + p:o + 2 + 2 * p]
    bzr2_ref, bq2_ref = wr[o + 2 + 2 * p], wr[o + 3 + 2 * p]

    c = out_ref.shape[-1]
    g = th * w                     # rows per tile (flattened)
    hw = _HALO * w                 # halo rows (flattened)
    m = th + 2 * _HALO             # assembly height
    rows = m * w
    cdt = hc_ref.dtype
    ti = pl.program_id(1)

    # Working span: cur tile plus _HALO rows from each neighbor. At the
    # grid edges the neighbor index maps clamp to cur, so these halo rows
    # are garbage — the global-row masks below zero their contributions.
    ha = halo_assemble([hp_ref[0], hc_ref[0], hn_ref[0]], g, hw)
    xas = tuple(
        halo_assemble([xrefs[3 * i][0], xrefs[3 * i + 1][0],
                       xrefs[3 * i + 2][0]], g, hw)
        for i in range(p))

    # Flattened-index geometry: column (for horizontal tap validity) and
    # global image row (for vertical tap validity / padded-row exclusion).
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = ri - (ri // w) * w
    grow = ti * th - _HALO + ri // w

    def hmask(d):
        cd = col + d
        return ((cd >= 0) & (cd < w)).astype(cdt)

    def vmask(d):
        gr = grow + d
        return ((gr >= 0) & (gr < h_img)).astype(cdt)

    def sepconv(vh, vxs, wh_ref, wx_refs, b_ref, shift_mul, mask):
        """One merged separable conv: Σ_taps shifted-masked matmuls of the
        h-part and each x-part operand (h first, then parts in order —
        the single-part accumulation order is the round-6 kernel's); f32
        accumulation, compute-dtype bias add (the flax Conv contract)."""
        ch = vh.shape[1]
        nout = b_ref.shape[1]
        acc = jnp.zeros((rows, nout), jnp.float32)
        for k in range(_TAPS):
            d = k - 2
            mk = mask(d)
            acc += jax.lax.dot_general(
                _shift_rows(vh, d * shift_mul) * mk,
                wh_ref[k * ch:(k + 1) * ch, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for vx, wx_ref in zip(vxs, wx_refs):
                chx = vx.shape[1]
                acc += jax.lax.dot_general(
                    _shift_rows(vx, d * shift_mul) * mk,
                    wx_ref[k * chx:(k + 1) * chx, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return acc.astype(cdt) + b_ref[...]

    # Horizontal step over the full assembly (the halo rows' h1 feed the
    # vertical step's taps; (TH+8)/TH recompute — see module docstring).
    zr1 = gate_sigmoid(sepconv(ha, xas, wzr1h_ref, wzr1x_refs,
                               bzr1_ref, 1, hmask))
    z1, r1 = zr1[:, :c], zr1[:, c:]
    q1 = jnp.tanh(sepconv(r1 * ha, xas, wq1h_ref, wq1x_refs,
                          bq1_ref, 1, hmask))
    h1 = (1 - z1) * ha + z1 * q1

    # Vertical step; only the cur rows of the outputs are consumed, and
    # every tap they draw on lies inside the assembly span.
    zr2 = gate_sigmoid(sepconv(h1, xas, wzr2h_ref, wzr2x_refs,
                               bzr2_ref, w, vmask))
    z2, r2 = zr2[:, :c], zr2[:, c:]
    q2 = jnp.tanh(sepconv(r2 * h1, xas, wq2h_ref, wq2x_refs,
                          bq2_ref, w, vmask))
    h2 = (1 - z2) * h1 + z2 * q2

    # Consumer dtype + axis order at the boundary (layout contract 1-3).
    klayout.boundary_store(out_ref, h2[hw:hw + g])


def _full_spec(arr):
    shape = arr.shape
    return pl.BlockSpec(shape, lambda bi, ti: tuple(0 for _ in shape))


def _flatten_mats(mats):
    """Flatten the (possibly part-nested) 12-entry mats structure into
    the kernel's flat operand order; plain arrays act as 1-tuples."""
    flat = []
    for m in mats:
        flat.extend(m if isinstance(m, (tuple, list)) else (m,))
    return flat


def _pallas_gru(static, h2d, xs2d, mats):
    """h2d: (B, Hpad*W, C); xs2d: tuple of (B, Hpad*W, cx_p) x parts;
    mats: pack_weights output (x entries arrays for one part, per-part
    tuples from split_x_weights otherwise), already in the compute
    dtype. Returns (B, Hpad*W, C) cdt."""
    w, h_img, th, interpret = static
    b, n, c = h2d.shape
    g = th * w
    grid = (b, n // g)
    last = grid[1] - 1
    nparts = len(xs2d)

    kernel = functools.partial(_gru_kernel, w=w, h_img=h_img, th=th,
                               nparts=nparts)

    def spec_of(channels, idx_fn):
        return pl.BlockSpec((1, g, channels), idx_fn)

    prev = lambda bi, ti: (bi, jnp.maximum(ti - 1, 0), 0)
    cur = lambda bi, ti: (bi, ti, 0)
    nxt = lambda bi, ti: (bi, jnp.minimum(ti + 1, last), 0)

    flat_mats = _flatten_mats(mats)
    in_specs = [spec_of(c, prev), spec_of(c, cur), spec_of(c, nxt)]
    operands = [h2d, h2d, h2d]
    for x2d in xs2d:
        cx = x2d.shape[-1]
        in_specs += [spec_of(cx, prev), spec_of(cx, cur), spec_of(cx, nxt)]
        operands += [x2d, x2d, x2d]
    in_specs += [_full_spec(m) for m in flat_mats]
    out_specs, out_shape = klayout.query_tiled_out(b, n, c, g, h2d.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem.scan_compiler_params(),
        name=klayout.KERNEL_NAMES["gru"],
    )(*operands, *flat_mats)


# ---------------------------------------------------------------------------
# Reference (identical shifted-matmul math, pure jnp) — backward + parity
# ---------------------------------------------------------------------------

def _bshift(v, s: int):
    if s == 0:
        return v
    pad = jnp.zeros((v.shape[0], abs(s), v.shape[2]), v.dtype)
    if s > 0:
        return jnp.concatenate([v[:, s:], pad], axis=1)
    return jnp.concatenate([pad, v[:, :s]], axis=1)


def reference_gru(static, h2d, x2d, mats):
    """Pure-jnp twin of the kernel: the same tap decomposition, masks and
    cast points on the full flattened array (no tiling/halo). Serves as
    the custom-VJP backward (recompute-from-residuals) and as the
    kernel-parity oracle in tests. ``x2d`` may be one array or a tuple
    of parts (with mats' x entries split to match)."""
    w, h_img = static[0], static[1]
    (wzr1h, wzr1x, wq1h, wq1x, bzr1, bq1,
     wzr2h, wzr2x, wq2h, wq2x, bzr2, bq2) = mats
    xs = x2d if isinstance(x2d, (tuple, list)) else (x2d,)
    wzr1x, wq1x, wzr2x, wq2x = (_x_parts(m)
                                for m in (wzr1x, wq1x, wzr2x, wq2x))
    b, n, c = h2d.shape
    cdt = h2d.dtype

    ri = jnp.arange(n)[None, :, None]
    col = ri % w
    row = ri // w

    def hmask(d):
        cd = col + d
        return ((cd >= 0) & (cd < w)).astype(cdt)

    def vmask(d):
        gr = row + d
        return ((gr >= 0) & (gr < h_img)).astype(cdt)

    def sepconv(vh, vxs, wh, wxs, bias, shift_mul, mask):
        ch = vh.shape[-1]
        acc = jnp.zeros((b, n, bias.shape[1]), jnp.float32)
        for k in range(_TAPS):
            d = k - 2
            mk = mask(d)
            acc += jax.lax.dot_general(
                _bshift(vh, d * shift_mul) * mk,
                wh[k * ch:(k + 1) * ch, :],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for vx, wx in zip(vxs, wxs):
                chx = vx.shape[-1]
                acc += jax.lax.dot_general(
                    _bshift(vx, d * shift_mul) * mk,
                    wx[k * chx:(k + 1) * chx, :],
                    (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return acc.astype(cdt) + bias

    zr1 = gate_sigmoid(sepconv(h2d, xs, wzr1h, wzr1x, bzr1, 1, hmask))
    z1, r1 = zr1[..., :c], zr1[..., c:]
    q1 = jnp.tanh(sepconv(r1 * h2d, xs, wq1h, wq1x, bq1, 1, hmask))
    h1 = (1 - z1) * h2d + z1 * q1

    zr2 = gate_sigmoid(sepconv(h1, xs, wzr2h, wzr2x, bzr2, w, vmask))
    z2, r2 = zr2[..., :c], zr2[..., c:]
    q2 = jnp.tanh(sepconv(r2 * h1, xs, wq2h, wq2x, bq2, w, vmask))
    return (1 - z2) * h1 + z2 * q2


# ---------------------------------------------------------------------------
# Custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gru(static, h2d, x2d, mats):
    return _pallas_gru(static, h2d, x2d, mats)


def _gru_fwd(static, h2d, x2d, mats):
    return _pallas_gru(static, h2d, x2d, mats), (h2d, x2d, mats)


def _gru_bwd(static, res, g):
    # Recompute-based backward through the identical-math jnp reference
    # (the banded corr kernel's residuals strategy): gradients for h, x
    # and the packed weights; a fused Pallas backward is on-hardware
    # perf debt — the scan's HBM traffic the tentpole targets is in the
    # forward eval path.
    h2d, x2d, mats = res
    _, vjp = jax.vjp(
        lambda hh, xx, mm: reference_gru(static, hh, xx, mm),
        h2d, x2d, mats)
    return vjp(g)


_gru.defvjp(_gru_fwd, _gru_bwd)


# ---------------------------------------------------------------------------
# VMEM budget + eligibility + env resolution
# ---------------------------------------------------------------------------

def gru_vmem_parts(h_img: int, w: int, c: int, cx: int, th: int,
                   dtype_bytes: int) -> dict:
    """Named scoped-VMEM estimate for one launch: Mosaic's calibrated
    per-row figure over the ``(TH + 8) * W`` assembly span
    (``vmem.scan_rows_parts``), stretched linearly for channel counts
    beyond the calibrated C=128 / Cx=256."""
    rows = (th + 2 * _HALO) * w
    return vmem.scan_rows_parts("gru", rows, dtype_bytes,
                                max(1.0, (c + cx) / 384))


def choose_rows(h_img: int, w: int, c: int, cx: int,
                dtype_bytes: int) -> int | None:
    """Largest row-tile TH in {16, 8, 4} whose VMEM estimate fits the
    scan-body budget and whose flattened tile is sublane-aligned.
    None → no admissible tile (caller falls back to the flax path)."""
    return vmem.choose_rows(
        (16, 8, 4), w,
        lambda th: gru_vmem_parts(h_img, w, c, cx, th, dtype_bytes))


def gru_eligible(h_img: int, w: int, c: int, cx: int, dtype,
                 interpret: bool) -> bool:
    """Whether the fused kernel admits this shape. Interpret mode (CPU
    tests) has no VMEM or alignment constraints; real launches require
    lane-aligned channel counts (128-multiples — RAFT's C=128/Cx=256)
    and an admissible row tile."""
    if h_img < 1 or w < 1 or c < 1 or cx < 1:
        return False
    if interpret:
        return True
    if c % 128 or cx % 128:
        return False
    return choose_rows(h_img, w, c, cx, jnp.dtype(dtype).itemsize) is not None


def resolve_mode() -> str:
    """``RAFT_GRU_PALLAS`` → {'auto', '0', '1'} (trace-time, like
    RAFT_CORR_BACKEND). Misspellings fail loudly via envflags."""
    return env_enum("RAFT_GRU_PALLAS", ("auto", "0", "1"), "auto")


def should_fuse(h, x, hidden_dim: int, mode: str | None = None) -> bool:
    """Dispatch decision for SepConvGRU.__call__: '0' → flax path; '1' →
    kernel (interpret off-TPU), raising if the shape is inadmissible;
    'auto' → kernel only on a real TPU backend when eligible (CPU runs
    keep the flax path — interpret mode is a parity tool, not a fast
    path — mirroring the RAFT_CORR_BACKEND=auto contract). When auto
    rejects an otherwise-wanted TPU launch on the VMEM/alignment
    envelope, the fallback is LOGGED (``vmem.log_fallback``), never
    silent. ``x`` may be one array or a tuple of parts."""
    if mode is None:
        mode = resolve_mode()
    if mode == "0":
        return False
    if h.ndim != 4 or h.shape[-1] != hidden_dim:
        if mode == "1":
            raise ValueError(
                f"RAFT_GRU_PALLAS=1 but the hidden state has shape "
                f"{h.shape} (expected NHWC with {hidden_dim} channels)")
        return False
    xs = x if isinstance(x, (tuple, list)) else (x,)
    cx = sum(xx.shape[-1] for xx in xs)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        from raft_tpu.parallel.spatial import keeps_xla_under_partitioning
        if keeps_xla_under_partitioning("RAFT_GRU_PALLAS", mode):
            return False
    interpret = not on_tpu
    _, hh, ww, c = h.shape
    ok = gru_eligible(hh, ww, c, cx, h.dtype, interpret)
    if mode == "1":
        if not ok:
            raise ValueError(
                f"RAFT_GRU_PALLAS=1 but shape (H={hh}, W={ww}, C={c}, "
                f"Cx={cx}, dtype={h.dtype}) doesn't fit the "
                f"kernel's VMEM/alignment envelope; use auto to fall "
                f"back to the flax path")
        return True
    if on_tpu and not ok:
        vmem.log_fallback(
            "RAFT_GRU_PALLAS",
            f"(H={hh}, W={ww}, C={c}, Cx={cx}, "
            f"dtype={jnp.dtype(h.dtype).name})",
            gru_vmem_parts(hh, ww, c, cx, 4,
                           jnp.dtype(h.dtype).itemsize))
    return on_tpu and ok


def sepconv_gru(h, x, mats, *, dtype=None, interpret: bool | None = None,
                th: int | None = None):
    """Apply one fused SepConvGRU cell (horizontal then vertical step).

    Args:
      h: ``(B, H, W, C)`` hidden state (the scan carry — returned in the
        same layout and dtype, layout-contract invariant 4).
      x: ``(B, H, W, Cx)`` conditioning features, or a tuple of parts
        summing to Cx — e.g. ``(inp, [motion‖flow])`` from the fused
        motion encoder. Parts are consumed un-concatenated, against
        per-part weight slices (``split_x_weights``); a single array is
        exactly the round-6 path.
      mats: ``pack_weights`` output (float32 flax params; cast to the
        compute dtype here). Pass the un-split 12-tuple either way —
        the per-part re-slicing happens here (loop-invariant, hoisted).
      dtype: compute dtype (the flax module's ``dtype``); default
        ``h.dtype``.
      interpret: force Pallas interpret mode (defaults to True off-TPU,
        the corr kernel's convention).
      th: row-tile override for tests; default = largest admissible.

    Returns ``(B, H, W, C)`` in ``h``'s dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, hh, ww, c = h.shape
    xs = tuple(x) if isinstance(x, (tuple, list)) else (x,)
    cxs = tuple(xx.shape[-1] for xx in xs)
    cx = sum(cxs)
    cdt = jnp.dtype(dtype) if dtype is not None else h.dtype
    out_dt = h.dtype
    mats = split_x_weights(mats, cxs)

    if th is None:
        if interpret:
            # No VMEM to budget; the smallest legal tile minimizes the
            # H padding on the tiny shapes parity tests use.
            th = _HALO
        else:
            # None → _HALO so an inadmissible forced launch fails in the
            # preflight below with the itemized breakdown.
            th = choose_rows(hh, ww, c, cx, cdt.itemsize) or _HALO
    th = max(th, _HALO)
    if not interpret:
        vmem.preflight(gru_vmem_parts(hh, ww, c, cx, th, cdt.itemsize),
                       f"fused GRU kernel (th={th}, w={ww})",
                       vmem.SCAN_LIMIT_BYTES)

    hpad = _round_up(hh, th)
    n = hpad * ww
    h2d = h.astype(cdt).reshape(b, hh * ww, c)
    xs2d = tuple(xx.astype(cdt).reshape(b, hh * ww, xx.shape[-1])
                 for xx in xs)
    if hpad != hh:
        grow_n = (hpad - hh) * ww
        h2d = jnp.pad(h2d, ((0, 0), (0, grow_n), (0, 0)))
        xs2d = tuple(jnp.pad(x2d, ((0, 0), (0, grow_n), (0, 0)))
                     for x2d in xs2d)
    mats = tuple(
        tuple(p.astype(cdt) for p in m) if isinstance(m, (tuple, list))
        else m.astype(cdt)
        for m in mats)

    static = (ww, hh, th, bool(interpret))
    out = _gru(static, h2d, xs2d, mats)
    return out[:, :hh * ww].reshape(b, hh, ww, c).astype(out_dt)
