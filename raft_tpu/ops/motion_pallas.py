"""Fused BasicMotionEncoder — Pallas TPU kernel.

The round-7 tentpole: the *other* half of the round-5 scan-body conv
residual. BASELINE.md's b64 per-op profile charges ~162 ms/step (13%) to
the refinement scan's update-block convs at 5-16% MFU; PR 7's fused
SepConvGRU cell (``gru_pallas.py``) took the six gate convs, and this
kernel takes the remaining five — the motion encoder's
``convc1`` (1x1 on the corr window) → ``convc2`` (3x3),
``convf1`` (7x7 on 2-channel flow) → ``convf2`` (3x3), and the fusing
``conv`` (3x3) — whose ``convc2`` alone did 0.4 TFLOP in 44 ms
(9 TFLOP/s) under XLA's ``{3,0,2,1}`` layout. One launch per
``(B, Hpad/TH)`` grid tile; every intermediate activation (four ReLU
feature maps per iteration per tile) stays VMEM-resident instead of
round-tripping HBM between five conv launches.

Design (the gru_pallas playbook, full-2-D edition)
--------------------------------------------------
* **2-D convs as shifted MXU matmuls.** On the flattened ``(rows, C)``
  tile a ``(K, K)`` conv is, per tap ``(dy, dx)``, one
  ``(rows, Cin) @ (Cin, Cout)`` matmul of the input shifted by
  ``dy*W + dx`` flattened rows — 9 taps for the 3x3s —
  masked by the *combined* column validity (``col + dx ∈ [0, W)``) and
  global-row validity (``row + dy ∈ [0, H)``), exactly reproducing the
  convs' zero padding. ``convc1`` is 1x1: a single unshifted, unmasked
  matmul. The 7x7 on the 2-channel flow is ONE matmul too: its 49
  shifted, masked copies of the flow are laid side by side on the lane
  axis (``flow_patches``) and contracted with the tap-major weights at
  once — a tap of two channels would otherwise stream the whole span
  through the MXU for two rows of weights, 49 times.
* **Both output concats killed by weight packing.** The fusing ``conv``
  reads ``concat([cor, flo])``; its kernel is pre-split into ``cor``-
  and ``flo``-input row slices (``pack_weights`` — ``_concat_conv`` in
  kernel form), so each tap is two matmuls summed into one accumulator
  and the 256-channel intermediate concat never exists. The output
  concat ``[out ‖ flow]`` (126 + 2 = 128 channels, lane-aligned) is
  emitted directly by the final store. Downstream, ``gru_pallas``
  splits its x-input weights into per-part row slices
  (``split_x_weights`` — conceptually ``[inp | motion | flow]``), so
  ``concat([inp, motion_features])`` is never materialized between the
  two kernels either.
* **Clamped halos sized for the 3-conv receptive-field depth.** The
  flow branch needs ±5 rows (7x7 → ±3, then two 3x3 → ±1 each); the
  corr branch ±2 (1x1 contributes nothing). Each launch assembles
  ``TH + 10`` rows from ``ceil(5/TH)`` neighbor blocks per side under
  clamped index maps (``gru_pallas.halo_assemble`` — one neighbor at
  TH≥8, two at the TH=4 rung, where the halo is deeper than the tile;
  clamp garbage is neutralized by the row masks). The window is
  *exact*: the deepest tap chain of a cur-tile output lands on the
  assembly's first/last row.

Numerics
--------
Same contract as the GRU kernel: f32 accumulation
(``preferred_element_type``) cast to the compute dtype before each bias
add + ReLU (the flax Conv contract); the flow passthrough channels are
stored from the *uncast* flow operand, exactly as the conv path's
``concat([out, flow])`` leaves ``flow`` untouched. The tap
decomposition reorders reductions vs ``lax.conv_general_dilated``, so
parity is tolerance-checked (``tests/test_motion_pallas.py``, ≤2e-4);
``RAFT_MOTION_PALLAS=0`` restores the conv path bit-for-bit.

The custom VJP recomputes through a pure-jnp twin implementing the
identical shifted-matmul math; gradients reach flow, corr and — through
``pack_weights`` — the flax param tree. A hand-written Pallas backward
is on-hardware perf debt, as for the GRU cell.

``RAFT_MOTION_PALLAS`` (trace-time, parsed by
``raft_tpu.utils.envflags``): ``auto``/unset — kernel on TPU when the
shape is admissible (since round 10's TH=4 rung + phase-peak liveness
accounting that includes Sintel f32; shapes the ladder still rejects
fall back with a loud ``vmem.log_fallback``, never silently); ``1`` —
force (interpret mode off-TPU; raises if ineligible); ``0`` — conv
path.
Only ``BasicUpdateBlock`` dispatches here; ``SmallUpdateBlock``'s
encoder has a different conv chain and always keeps the conv path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.ops import layout as klayout
from raft_tpu.ops import vmem
from raft_tpu.ops.gru_pallas import (_bshift, _round_up, _shift_rows,
                                     halo_assemble)
from raft_tpu.utils.envflags import env_enum

# Vertical halo rows on each side of a row tile: the flow branch's
# receptive-field depth (convf1 7x7 → ±3, convf2 → ±1, conv → ±1). The
# corr branch needs only ±2 and shares the same assembly. Tiles shorter
# than the halo draw it from ceil(_HALO/TH) neighbor blocks per side
# (halo_assemble).
_HALO = 5

# Row-tile ladder for real launches. The TH=4 rung (round 10) is what
# admits Sintel f32: halo deeper than the tile, paid for by smaller
# assemblies under the phase-peak liveness estimate.
_ROW_LADDER = (16, 8, 4)

# The VMEM calibration (vmem._ROW_BYTES) was taken at the canonical
# BasicMotionEncoder widths (256/192/128/64/126, fixed by the
# architecture) and 324 corr channels, which Mosaic pads to 3 x 128
# lanes; a wider window is uncalibrated and keeps the conv path.
_MAX_CORR_CHANNELS = 384

# Lanes of convf1's patch operand: its 49 taps x 2 flow channels, padded
# to one MXU contraction slice.
_PATCH_LANES = 128


# ---------------------------------------------------------------------------
# Weight packing (the _concat_conv weight-merge idea, kernel-shaped)
# ---------------------------------------------------------------------------

def pack_weights(convc1, convc2, convf1, convf2, conv):
    """Flatten the five-conv chain into the kernel's 2-D matmul layout.

    Each arg is a ``(kernel, bias)`` pair in flax HWIO:
    ``convc1 (1,1,Cc,C1)``, ``convc2 (3,3,C1,C2)``, ``convf1 (7,7,2,F1)``,
    ``convf2 (3,3,F1,F2)``, ``conv (3,3,C2+F2,Co)``.

    Returns an 11-tuple of 2-D arrays: ``wc1 (Cc, C1)``, ``bc1 (1, C1)``,
    ``wc2 (9*C1, C2)``, ``bc2``, ``wf1 (49*2, F1)``, ``bf1``,
    ``wf2 (9*F1, F2)``, ``bf2``, ``woc (9*C2, Co)``, ``wof (9*F2, Co)``,
    ``bo (1, Co)``. Spatial-conv rows are tap-major — tap
    ``t = (dy+r)*K + (dx+r)`` owns rows ``[t*Cin, (t+1)*Cin)`` — which is
    exactly the HWIO reshape order. The fusing ``conv``'s kernel is split
    along its *input* axis into the ``cor`` (first C2) and ``flo`` (last
    F2) row groups so the ``concat([cor, flo])`` intermediate is never
    formed.

    Pure jnp on the existing param tree (untouched, so the torch-weight
    mapping survives); differentiable, so training gradients flow through
    the packing back to the flax params. XLA hoists it out of the
    refinement scan (loop-invariant).
    """
    (kc1, bc1), (kc2, bc2), (kf1, bf1), (kf2, bf2), (ko, bo) = (
        convc1, convc2, convf1, convf2, conv)
    for k, hw in ((kc1, 1), (kc2, 3), (kf1, 7), (kf2, 3), (ko, 3)):
        if k.ndim != 4 or k.shape[0] != hw or k.shape[1] != hw:
            raise ValueError(
                f"pack_weights: expected a ({hw},{hw},Cin,Cout) HWIO "
                f"kernel, got {k.shape}")
    cc, c1 = kc1.shape[2], kc1.shape[3]
    c2, f1, f2, co = kc2.shape[3], kf1.shape[3], kf2.shape[3], ko.shape[3]
    if kf1.shape[2] != 2:
        raise ValueError(
            f"pack_weights: convf1 must read 2-channel flow, got "
            f"{kf1.shape}")
    if (kc2.shape[2] != c1 or kf2.shape[2] != f1
            or ko.shape[2] != c2 + f2):
        raise ValueError(
            "pack_weights: chain channel mismatch — "
            f"convc2 in={kc2.shape[2]} (want {c1}), "
            f"convf2 in={kf2.shape[2]} (want {f1}), "
            f"conv in={ko.shape[2]} (want {c2 + f2})")
    return (kc1.reshape(cc, c1), bc1.reshape(1, c1),
            kc2.reshape(9 * c1, c2), bc2.reshape(1, c2),
            kf1.reshape(49 * 2, f1), bf1.reshape(1, f1),
            kf2.reshape(9 * f1, f2), bf2.reshape(1, f2),
            ko[:, :, :c2, :].reshape(9 * c2, co),
            ko[:, :, c2:, :].reshape(9 * f2, co),
            bo.reshape(1, co))


# ---------------------------------------------------------------------------
# Conv helpers shared with the fused step kernel (ops/step_pallas.py)
# ---------------------------------------------------------------------------

def tap_valid(col, grow, w: int, h_img: int, dy: int, dx: int):
    """Whether the source of tap ``(dy, dx)`` lies inside the image, per
    flattened row: ``col + dx in [0, W)`` and ``grow + dy in [0, H)`` —
    the convs' zero padding, and what neutralizes clamped halo rows."""
    cd = col + dx
    gr = grow + dy
    return (cd >= 0) & (cd < w) & (gr >= 0) & (gr < h_img)


def conv_taps(valid, ops, b_ref, ksize: int, w: int):
    """One spatial conv on the flattened ``(rows, C)`` span: the sum over
    ``(dy, dx)`` taps of shifted-masked MXU matmuls, summed across the
    input operands (the fusing conv has two — its concat killed by the
    weight split); f32 accumulation, compute-dtype bias add (the flax
    Conv contract). ``valid(dy, dx)`` is the tap's ``tap_valid``."""
    r = ksize // 2
    cdt = b_ref.dtype
    acc = jnp.zeros((ops[0][0].shape[0], b_ref.shape[1]), jnp.float32)
    t = 0
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            mk = valid(dy, dx).astype(cdt)
            for v, w_ref in ops:
                cin = v.shape[1]
                acc += jax.lax.dot_general(
                    _shift_rows(v, dy * w + dx) * mk,
                    w_ref[t * cin:(t + 1) * cin, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            t += 1
    return acc.astype(cdt) + b_ref[...]


def kernel_mats(mats):
    """``pack_weights`` output as the kernels take it: ``wf1 (49*2, F1)``
    zero-padded to one whole 128-row contraction slice, the right-hand
    side of ``flow_conv7``; the rest as packed."""
    mats = list(mats)
    mats[4] = jnp.pad(mats[4],
                      ((0, _PATCH_LANES - mats[4].shape[0]), (0, 0)))
    return mats


def flow_patches(fac, col, grow, w: int, h_img: int, stride: int = 0):
    """The left-hand side of ``flow_conv7``: a ``(rows, 128)`` operand
    whose lane ``2*t + c`` holds channel ``c`` of the flow shifted by tap
    ``t``'s ``dy*W + dx`` rows, zero where ``tap_valid`` fails — the 49
    shifted, masked copies side by side (lanes 98.. are zero, against
    ``kernel_mats``' zero rows). Built separably: rows outside the image
    are zeroed at the source (a tap with a valid column reads the row
    ``dy`` below its own, so that is the tap's row mask), the flow is
    broadcast over the lanes by channel parity, each lane takes its own
    ``dx`` shift under that shift's column mask (7 row shifts), then its
    own ``dy`` shift by whole image rows (7 more). Every value is exactly
    the per-tap ``_shift_rows(fac, dy*W + dx) * mask``. ``stride`` is the
    flattened rows an image row takes where that is more than its ``w``
    columns (the fused step kernel pads a row to whole sublane tiles)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _PATCH_LANES), 1)
    tap = lane // 2
    ldy = tap // 7 - 3          # lanes past tap 48 read 4: no dy matches
    ldx = tap - (tap // 7) * 7 - 3
    # Assembled in 32 bits (exact for a compute-dtype flow; Mosaic has no
    # relayout for a row mask and a lane mask meeting on packed bf16).
    src = jnp.where((grow >= 0) & (grow < h_img),
                    fac.astype(jnp.float32), 0.0)
    ft = jnp.where(lane - tap * 2 == 0, src[:, 0:1], src[:, 1:2])
    zero = jnp.zeros_like(ft)
    gx = zero
    for dx in range(-3, 4):
        cd = col + dx
        gx = jnp.where((cd >= 0) & (cd < w) & (ldx == dx),
                       _shift_rows(ft, dx), gx)
    patches = zero
    for dy in range(-3, 4):
        patches = jnp.where(ldy == dy, _shift_rows(gx, dy * (stride or w)),
                            patches)
    return patches.astype(fac.dtype)


def flow_conv7(fac, wf1p_ref, b_ref, col, grow, w: int, h_img: int):
    """``convf1`` (7x7 on the 2-channel flow) as ONE contraction over its
    98 tap-channels instead of 49 products with K = 2, each of which
    streams the whole span through the MXU for two rows of weights.
    Against the per-tap sum only the order of the 98 f32 partial sums
    changes (inside one product instead of across 49)."""
    return jax.lax.dot_general(
        flow_patches(fac, col, grow, w, h_img), wf1p_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(fac.dtype) + b_ref[...]


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _motion_kernel(*refs, w: int, h_img: int, th: int):
    """The whole motion-encoder chain for one TH-row tile (+5 halo
    rows/side). ``refs`` is ``(<2nb+1 corr refs>, <2nb+1 flow refs>,
    <11 weight refs>, out)`` where ``nb = ceil(_HALO/th)``; the corr/
    flow neighbor refs are the SAME flattened arrays under clamped
    block index maps; the four intermediate feature maps live entirely
    in VMEM and the final store emits ``[out ‖ flow]`` in the
    consumer's dtype."""
    out_ref = refs[-1]
    nb = -(-_HALO // th)           # neighbor blocks per side
    ncorr = 2 * nb + 1
    corr_refs = refs[:ncorr]
    flow_refs = refs[ncorr:2 * ncorr]
    (wc1_ref, bc1_ref, wc2_ref, bc2_ref, wf1_ref, bf1_ref,
     wf2_ref, bf2_ref, woc_ref, wof_ref, bo_ref) = refs[2 * ncorr:-1]

    g = th * w                     # rows per tile (flattened)
    hw = _HALO * w                 # halo rows (flattened)
    m = th + 2 * _HALO             # assembly height
    rows = m * w
    cdt = corr_refs[nb].dtype
    ti = pl.program_id(1)

    # Working span: cur tile plus _HALO rows from each side's neighbor
    # blocks. Clamped edge garbage is neutralized by the global-row
    # masks below. The window is exact for the 3-conv receptive-field
    # depth: conv needs flo2 on rows [4, th+6), flo2 needs flo1 on
    # [3, th+7), and flo1's ±3 taps there read flow rows [0, th+10) —
    # the full assembly.
    ca = halo_assemble([r[0] for r in corr_refs], g, hw)
    fa = halo_assemble([r[0] for r in flow_refs], g, hw)

    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = ri - (ri // w) * w
    grow = ti * th - _HALO + ri // w

    def valid(dy, dx):
        return tap_valid(col, grow, w, h_img, dy, dx)

    def conv2d(ops, b_ref, ksize):
        return conv_taps(valid, ops, b_ref, ksize, w)

    # Corr branch: 1x1 is one unshifted matmul (no padding geometry);
    # garbage on out-of-image assembly rows is masked by convc2's taps.
    cor = jax.nn.relu(jax.lax.dot_general(
        ca, wc1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(cdt) + bc1_ref[...])
    cor = jax.nn.relu(conv2d([(cor, wc2_ref)], bc2_ref, 3))

    # Flow branch: convs read the compute-dtype cast; the passthrough
    # below reads fa uncast (the conv path leaves flow untouched).
    fac = fa.astype(cdt)
    flo = jax.nn.relu(flow_conv7(fac, wf1_ref, bf1_ref, col, grow, w,
                                 h_img))
    flo = jax.nn.relu(conv2d([(flo, wf2_ref)], bf2_ref, 3))

    # Fusing conv over [cor ‖ flo] without the concat, then the direct
    # [out ‖ flow] emission (consumer dtype via the layout contract).
    out = jax.nn.relu(conv2d([(cor, woc_ref), (flo, wof_ref)], bo_ref, 3))
    klayout.boundary_store(out_ref, jnp.concatenate(
        [out[hw:hw + g].astype(out_ref.dtype),
         fa[hw:hw + g].astype(out_ref.dtype)], axis=1))


def _full_spec(arr):
    shape = arr.shape
    return pl.BlockSpec(shape, lambda bi, ti: tuple(0 for _ in shape))


def _pallas_motion(static, flow2d, corr2d, mats):
    """flow2d: (B, Hpad*W, 2) in the *input* dtype; corr2d:
    (B, Hpad*W, Cc) in the compute dtype; mats: pack_weights output in
    the compute dtype. Returns (B, Hpad*W, Co+2) in the promoted
    output dtype."""
    w, h_img, th, interpret, out_dt = static
    b, n, cc = corr2d.shape
    cf = flow2d.shape[-1]
    co = mats[-1].shape[1]
    g = th * w
    grid = (b, n // g)
    last = grid[1] - 1

    kernel = functools.partial(_motion_kernel, w=w, h_img=h_img, th=th)
    nb = -(-_HALO // th)
    mats = kernel_mats(mats)

    def neighbor_specs(channels):
        return [pl.BlockSpec(
                    (1, g, channels),
                    lambda bi, ti, k=k: (bi, jnp.clip(ti + k, 0, last), 0))
                for k in range(-nb, nb + 1)]

    in_specs = (neighbor_specs(cc) + neighbor_specs(cf)
                + [_full_spec(m) for m in mats])
    operands = [corr2d] * (2 * nb + 1) + [flow2d] * (2 * nb + 1) + mats
    # Layout-contract invariant 6: the [out ‖ flow] emission is the
    # GRU's packed x part, declared as a handoff.
    out_specs, out_shape = klayout.handoff_tiled_out(b, n, co + cf, g,
                                                     out_dt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem.scan_compiler_params(),
        name=klayout.KERNEL_NAMES["motion"],
    )(*operands)


# ---------------------------------------------------------------------------
# Reference (identical shifted-matmul math, pure jnp) — backward + parity
# ---------------------------------------------------------------------------

def reference_motion(static, flow2d, corr2d, mats):
    """Pure-jnp twin of the kernel: the same tap order, masks and cast
    points on the full flattened array (no tiling/halo). Serves as the
    custom-VJP backward (recompute-from-residuals) and as the
    kernel-parity oracle in tests."""
    w, h_img = static[0], static[1]
    (wc1, bc1, wc2, bc2, wf1, bf1, wf2, bf2, woc, wof, bo) = mats
    b, n, _ = corr2d.shape
    cdt = corr2d.dtype

    ri = jnp.arange(n)[None, :, None]
    col = ri % w
    row = ri // w

    def mask(dy, dx):
        cd = col + dx
        gr = row + dy
        return ((cd >= 0) & (cd < w)
                & (gr >= 0) & (gr < h_img)).astype(cdt)

    def conv2d(ops, bias, ksize):
        r = ksize // 2
        acc = jnp.zeros((b, n, bias.shape[1]), jnp.float32)
        t = 0
        for dy in range(-r, r + 1):
            for dx in range(-r, r + 1):
                mk = mask(dy, dx)
                for v, wm in ops:
                    cin = v.shape[-1]
                    acc += jax.lax.dot_general(
                        _bshift(v, dy * w + dx) * mk,
                        wm[t * cin:(t + 1) * cin, :],
                        (((2,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                t += 1
        return acc.astype(cdt) + bias

    cor = jax.nn.relu(jax.lax.dot_general(
        corr2d, wc1, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(cdt) + bc1)
    cor = jax.nn.relu(conv2d([(cor, wc2)], bc2, 3))
    fac = flow2d.astype(cdt)
    flo = jax.nn.relu(conv2d([(fac, wf1)], bf1, 7))
    flo = jax.nn.relu(conv2d([(flo, wf2)], bf2, 3))
    out = jax.nn.relu(conv2d([(cor, woc), (flo, wof)], bo, 3))
    return jnp.concatenate([out, flow2d], axis=-1)


# ---------------------------------------------------------------------------
# Custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _motion(static, flow2d, corr2d, mats):
    return _pallas_motion(static, flow2d, corr2d, mats)


def _motion_fwd(static, flow2d, corr2d, mats):
    return _pallas_motion(static, flow2d, corr2d, mats), (flow2d, corr2d,
                                                          mats)


def _motion_bwd(static, res, g):
    # Recompute-based backward through the identical-math jnp twin (the
    # gru/corr kernels' residuals strategy): gradients for flow, corr
    # and the packed weights; a fused Pallas backward is on-hardware
    # perf debt — the scan's HBM traffic lives in the forward eval path.
    flow2d, corr2d, mats = res
    _, vjp = jax.vjp(
        lambda ff, cc, mm: reference_motion(static, ff, cc, mm),
        flow2d, corr2d, mats)
    return vjp(g)


_motion.defvjp(_motion_fwd, _motion_bwd)


# ---------------------------------------------------------------------------
# VMEM budget + eligibility + env resolution
# ---------------------------------------------------------------------------

def motion_vmem_parts(h_img: int, w: int, cc: int, th: int,
                      dtype_bytes: int) -> dict:
    """Named scoped-VMEM estimate for one launch: Mosaic's calibrated
    per-row figure over the ``(TH + 10) * W`` assembly span
    (``vmem.scan_rows_parts``). The figure holds for the canonical
    widths and up to ``_MAX_CORR_CHANNELS`` corr channels, which
    ``motion_eligible`` enforces."""
    rows = (th + 2 * _HALO) * w
    return vmem.scan_rows_parts("motion", rows, dtype_bytes)


def choose_rows(h_img: int, w: int, cc: int,
                dtype_bytes: int) -> int | None:
    """Largest row-tile TH in the {16, 8, 4} ladder whose VMEM estimate
    fits the admission budget and whose flattened tile is
    sublane-aligned (vmem.choose_rows). None → no admissible tile
    (auto falls back to the conv path). At Sintel eval shapes (H=55,
    W=128, Ccorr=324) both dtypes admit th=16 under the explicit
    100 MiB limit — asserted in tests/test_motion_pallas.py and compiled
    for the chip in tests/test_chip_compile.py."""
    return vmem.choose_rows(
        _ROW_LADDER, w,
        lambda th: motion_vmem_parts(h_img, w, cc, th, dtype_bytes))


def motion_eligible(h_img: int, w: int, cc: int, dtype,
                    interpret: bool) -> bool:
    """Whether the fused kernel admits this shape. Interpret mode (CPU
    tests) has no VMEM or alignment constraints; real launches require
    an admissible row tile (the 128-channel [out‖flow] output is
    lane-aligned by construction)."""
    if h_img < 1 or w < 1 or cc < 1:
        return False
    if interpret:
        return True
    if cc > _MAX_CORR_CHANNELS:
        return False
    return choose_rows(h_img, w, cc, jnp.dtype(dtype).itemsize) is not None


def resolve_mode() -> str:
    """``RAFT_MOTION_PALLAS`` → {'auto', '0', '1'} (trace-time, like
    RAFT_GRU_PALLAS). Misspellings fail loudly via envflags."""
    return env_enum("RAFT_MOTION_PALLAS", ("auto", "0", "1"), "auto")


def should_fuse(flow, corr, mode: str | None = None) -> bool:
    """Dispatch decision for BasicUpdateBlock.__call__: '0' → conv path;
    '1' → kernel (interpret off-TPU), raising if inadmissible; 'auto' →
    kernel only on a real TPU backend when eligible — and when the VMEM
    table rejects the shape there, the fallback is LOGGED
    (vmem.log_fallback), never silent."""
    if mode is None:
        mode = resolve_mode()
    if mode == "0":
        return False
    shape_ok = (flow.ndim == 4 and flow.shape[-1] == 2
                and corr.ndim == 4 and corr.shape[:3] == flow.shape[:3])
    if not shape_ok:
        if mode == "1":
            raise ValueError(
                f"RAFT_MOTION_PALLAS=1 but flow/corr have shapes "
                f"{flow.shape}/{corr.shape} (expected NHWC with matching "
                f"spatial dims and 2 flow channels)")
        return False
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        from raft_tpu.parallel.spatial import keeps_xla_under_partitioning
        if keeps_xla_under_partitioning("RAFT_MOTION_PALLAS", mode):
            return False
    interpret = not on_tpu
    _, hh, ww, _ = flow.shape
    cc = corr.shape[-1]
    ok = motion_eligible(hh, ww, cc, corr.dtype, interpret)
    if mode == "1":
        if not ok:
            raise ValueError(
                f"RAFT_MOTION_PALLAS=1 but shape (H={hh}, W={ww}, "
                f"Ccorr={cc}, dtype={jnp.dtype(corr.dtype).name}) "
                f"doesn't fit the kernel's VMEM envelope; use auto to "
                f"fall back to the conv path")
        return True
    if on_tpu and not ok:
        vmem.log_fallback(
            "RAFT_MOTION_PALLAS",
            f"(H={hh}, W={ww}, Ccorr={cc}, "
            f"dtype={jnp.dtype(corr.dtype).name})",
            motion_vmem_parts(hh, ww, cc, _ROW_LADDER[-1],
                              jnp.dtype(corr.dtype).itemsize))
    return on_tpu and ok


def motion_encoder(flow, corr, mats, *, dtype=None,
                   interpret: bool | None = None, th: int | None = None):
    """Apply the fused BasicMotionEncoder chain.

    Args:
      flow: ``(B, H, W, 2)`` current flow estimate — also passed through
        untouched as the output's last two channels.
      corr: ``(B, H, W, Cc)`` correlation window
        (``levels * (2r+1)^2`` channels).
      mats: ``pack_weights`` output (float32 flax params; cast to the
        compute dtype here).
      dtype: compute dtype (the flax module's ``dtype``); default
        ``corr.dtype``.
      interpret: force Pallas interpret mode (defaults to True off-TPU).
      th: row-tile override for tests; default = largest admissible.

    Returns ``(B, H, W, Co+2)`` — ``[out ‖ flow]`` — in the promotion of
    the compute dtype with ``flow.dtype`` (the conv path's concat
    semantics).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, hh, ww, cf = flow.shape
    cc = corr.shape[-1]
    co = mats[-1].shape[1]
    cdt = jnp.dtype(dtype) if dtype is not None else corr.dtype
    out_dt = jnp.promote_types(cdt, flow.dtype)

    if th is None:
        if interpret:
            # No VMEM to budget; a small tile minimizes the H padding
            # on the tiny shapes parity tests use.
            th = _HALO
        else:
            # None → the smallest rung so an inadmissible forced launch
            # fails in the preflight below with the itemized breakdown.
            th = choose_rows(hh, ww, cc, cdt.itemsize) or _ROW_LADDER[-1]
    if not interpret:
        vmem.preflight(
            motion_vmem_parts(hh, ww, cc, th, cdt.itemsize),
            f"fused motion encoder (th={th}, w={ww})",
            vmem.SCAN_LIMIT_BYTES)

    hpad = _round_up(hh, th)
    n = hpad * ww
    corr2d = corr.astype(cdt).reshape(b, hh * ww, cc)
    # Flow keeps its own dtype end-to-end: the convs cast it to the
    # compute dtype in-kernel, the passthrough channels don't.
    flow2d = flow.reshape(b, hh * ww, cf)
    if hpad != hh:
        grow_n = (hpad - hh) * ww
        corr2d = jnp.pad(corr2d, ((0, 0), (0, grow_n), (0, 0)))
        flow2d = jnp.pad(flow2d, ((0, 0), (0, grow_n), (0, 0)))
    mats = tuple(m.astype(cdt) for m in mats)

    static = (ww, hh, th, bool(interpret), out_dt)
    out = _motion(static, flow2d, corr2d, mats)
    return out[:, :hh * ww].reshape(b, hh, ww, co + cf)
