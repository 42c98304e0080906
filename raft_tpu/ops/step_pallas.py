"""Fused one-launch refine iteration — motion encoder → SepConvGRU
(→ flow head) as a single Pallas TPU kernel.

The round-10 tentpole, and the ROADMAP's "fuse the whole scan body"
ceiling-raiser. PRs 7+10 fused the scan body's conv residual into two
kernels — ``motion_pallas`` (five convs) and ``gru_pallas`` (six gate
convs) — but they are *separate* launches: every one of the 12 refine
iterations writes the packed ``[motion ‖ flow]`` activation
(``B x H/8 x W/8 x 128``) to HBM at the motion kernel's boundary and
reads it straight back at the GRU's. The layout contract's handoff
invariant (``ops/layout.py`` invariant 6) made that buffer alias-able;
this kernel makes it *disappear* — FlashAttention's move, applied to
the update block: chain the producer and consumer inside one
``(B, Hpad/TH)`` grid launch so the handoff value (and ``h2`` into the
flow head) never leaves VMEM. Because PR 15's contbatch ``step``
executable IS this scan body, the fusion speeds batched, streaming,
brownout, and continuous serving at once.

Two fusion depths, chosen by admission (``plan_fusion``):

* ``'mg'`` — motion encoder + GRU, emitting the new hidden state. Used
  on iterations that also need the mask head (``compute_mask=True``),
  whose ``_concat_conv`` stays on the XLA side, and whenever the flow
  head pushes the estimate over budget.
* ``'mgf'`` — + the flow head's two 3x3 convs, emitting ``(h2, delta)``
  as two outputs. Admitted wherever ``'mg'`` is at bf16 (Sintel, chairs
  and KITTI feature maps); at f32 it costs a rung or the depth.

No tap of a two-channel conv gets an MXU pass of its own: ``convf1``
(7x7 on the 2-channel flow) is one contraction over its 98 tap-channels
(``motion_pallas.flow_conv7``, shared with the stand-alone motion
kernel) and the flow head's last conv (3x3, 256 -> 2) is one product
with its nine taps on the output axis (``folded_head_conv``). As 49
products with K = 2 and 9 with N = 2 they were a quarter of the passes
the kernel streams and, by their per-tap temporaries, a third of its
VMEM.

Halos compose across the chain: the GRU's SepConv pair needs ±4 rows
of valid *x* (and the flow head another ±2 of valid ``h2``), and the
motion chain needs ±5 beyond wherever its output must be valid — so
the corr/flow windows carry ``hm = hg + 5`` halo rows (9 for ``mg``,
11 for ``mgf``) assembled from ``ceil(hm/th)`` neighbor blocks per
side (``gru_pallas.halo_assemble``), while net/inp carry ``hg``. The
motion chain is computed over its full span and sliced down to the GRU
span; every row of the slice is exact by the same masks the
stand-alone kernels use, so the fused result is the *identical*
shifted-matmul arithmetic — parity with the two-launch chain is
near-bit-exact at f32, and ≤2e-4 vs the conv path
(``tests/test_step_pallas.py``).

VMEM admission is ``step_vmem_parts`` — Mosaic's calibrated per-row
figure over the motion span, which is this kernel's peak
(``vmem.scan_rows_parts``) — under the shared ``vmem.choose_rows``
ladder ``(16, 8, 4)`` and the explicit 100 MiB limit; at Sintel shapes
bf16 admits TH=16 at both depths and f32 TH=8 at both. A shape the
ladder rejects (e.g. W=240 at 1080p, either depth) falls back, loudly
logged, to the two-launch chain, never silently.

The custom VJP recomputes through the identical-math jnp twin
(``reference_motion`` → ``reference_gru`` → flow-head taps); a fused
Pallas backward is on-hardware perf debt, as for the component
kernels.

``RAFT_STEP_PALLAS`` (trace-time, ``utils/envflags``): ``auto`` —
fuse on TPU where admissible, else fall back loudly to the two-launch
chain (whose own flags then apply); ``0`` — today's behavior,
byte-identical; ``1`` — force (interpret off-TPU; raises on TPU if no
tile admits, so a forced A/B arm can't silently degrade).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.ops import layout as klayout
from raft_tpu.ops import vmem
from raft_tpu.ops.gru_pallas import (_TAPS, _bshift, _flatten_mats,
                                     _full_spec, _round_up, _shift_rows,
                                     gate_sigmoid, halo_assemble,
                                     split_x_weights)
from raft_tpu.ops.gru_pallas import reference_gru
from raft_tpu.ops.motion_pallas import (_MAX_CORR_CHANNELS, conv_taps,
                                        flow_conv7, kernel_mats,
                                        reference_motion, tap_valid)
from raft_tpu.utils.envflags import STEP_FLAG, resolve_step_pallas

# Per-stage receptive-field depths (rows each side). The GRU needs its
# x/net assembly valid ±_HALO_GRU rows around the tile; the flow head
# needs h2 valid another ±_HALO_FLOW_HEAD; the motion chain needs its
# inputs ±_HALO_MOTION beyond wherever its output must be valid.
_HALO_MOTION = 5
_HALO_GRU = 4
_HALO_FLOW_HEAD = 2

# Row-tile ladder for real launches (same rungs as the component
# kernels; Sintel and chairs bf16 ride the top rung at both depths).
_ROW_LADDER = (16, 8, 4)


def halos(flow_head: bool) -> tuple[int, int]:
    """``(hg, hm)``: halo rows each side for the net/inp (GRU-span) and
    corr/flow (motion-span) windows of one fused launch."""
    hg = _HALO_GRU + (_HALO_FLOW_HEAD if flow_head else 0)
    return hg, hg + _HALO_MOTION


# ---------------------------------------------------------------------------
# Weight packing (flow head; motion/GRU reuse their kernels' packers)
# ---------------------------------------------------------------------------

def pack_flow_head(conv1, conv2):
    """Flatten the FlowHead pair (3x3 ``C→Fh`` + 3x3 ``Fh→2``) into the
    kernel's tap-major 2-D layout: ``(wfh1 (9*C, Fh), bfh1 (1, Fh),
    wfh2 (9*Fh, 2), bfh2 (1, 2))``. Pure jnp on the flax params
    (differentiable; hoisted out of the scan as loop-invariant)."""
    (k1, b1), (k2, b2) = conv1, conv2
    for k in (k1, k2):
        if k.ndim != 4 or k.shape[0] != 3 or k.shape[1] != 3:
            raise ValueError(
                f"pack_flow_head: expected (3,3,Cin,Cout) HWIO kernels, "
                f"got {k.shape}")
    if k2.shape[3] != 2 or k2.shape[2] != k1.shape[3]:
        raise ValueError(
            f"pack_flow_head: chain mismatch — conv2 {k2.shape} must "
            f"read conv1's {k1.shape[3]} channels and emit 2")
    cin, fh = k1.shape[2], k1.shape[3]
    return (k1.reshape(9 * cin, fh), b1.reshape(1, fh),
            k2.reshape(9 * fh, 2), b2.reshape(1, 2))


def fold_head_taps(wfh2):
    """The flow head's second conv with its taps on the output axis:
    tap-major ``(9*Fh, 2)`` rows as ``(Fh, 18)`` columns
    ``[W_0 | ... | W_8]`` — the right-hand side of ``folded_head_conv``."""
    fhid = wfh2.shape[0] // 9
    return wfh2.reshape(9, fhid, 2).transpose(1, 0, 2).reshape(fhid, 18)


def folded_head_conv(valid, v, wcat_ref, b_ref, w: int):
    """The 3x3 ``Fh -> 2`` conv as ONE product ``v @ [W_0 | ... | W_8]``
    and nine shifted, masked adds of two float32 columns each, instead
    of nine products with N = 2 (each a whole pass of the span through
    the MXU for two output columns). ``delta[p] = sum_t mask_t[p] *
    (v[p + s_t] @ W_t)`` is the per-tap sum with the shift and mask
    applied to the product's rows instead of the operand's: the same
    float32 terms added in the same order, then the same cast and
    compute-dtype bias add."""
    taps = jax.lax.dot_general(
        v, wcat_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc = jnp.zeros((v.shape[0], 2), jnp.float32)
    t = 0
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            acc += (_shift_rows(taps[:, 2 * t:2 * t + 2], dy * w + dx)
                    * valid(dy, dx).astype(jnp.float32))
            t += 1
    return acc.astype(v.dtype) + b_ref[...]


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _step_kernel(*refs, w: int, h_img: int, th: int, fh: bool):
    """One whole refine-scan iteration for a TH-row tile.

    ``refs`` is ``(<2nm+1 corr>, <2nm+1 flow>, <2ng+1 net>, <2ng+1 inp>,
    <11 motion mats>, <16 GRU mats>, [4 flow-head mats,] h2_out
    [, delta_out])`` — neighbor refs are the SAME flattened arrays
    under clamped block index maps. The motion chain runs over the
    deep (±hm) span; its ``[out ‖ flow]`` is sliced to the GRU (±hg)
    span and consumed as the second x part without ever being stored;
    with ``fh`` the flow head consumes ``h2`` in the same launch.
    """
    nouts = 2 if fh else 1
    out_refs = refs[-nouts:]
    refs = refs[:-nouts]
    hg, hm = halos(fh)
    nm = -(-hm // th)
    ng = -(-hg // th)
    ncorr = 2 * nm + 1
    nnet = 2 * ng + 1
    i = 0
    corr_refs = refs[i:i + ncorr]; i += ncorr
    flow_refs = refs[i:i + ncorr]; i += ncorr
    net_refs = refs[i:i + nnet]; i += nnet
    inp_refs = refs[i:i + nnet]; i += nnet
    (wc1_ref, bc1_ref, wc2_ref, bc2_ref, wf1_ref, bf1_ref,
     wf2_ref, bf2_ref, woc_ref, wof_ref, bo_ref) = refs[i:i + 11]
    i += 11
    (wzr1h, wzr1xa, wzr1xb, wq1h, wq1xa, wq1xb, bzr1, bq1,
     wzr2h, wzr2xa, wzr2xb, wq2h, wq2xa, wq2xb, bzr2, bq2) = refs[i:i + 16]
    i += 16
    fh_refs = refs[i:i + 4] if fh else None

    g = th * w
    c = out_refs[0].shape[-1]
    cdt = net_refs[ng].dtype
    ti = pl.program_id(1)

    # ---- motion chain over the deep (±hm) span ------------------------
    rows_m = (th + 2 * hm) * w
    ca = halo_assemble([r[0] for r in corr_refs], g, hm * w)
    fa = halo_assemble([r[0] for r in flow_refs], g, hm * w)

    rim = jax.lax.broadcasted_iota(jnp.int32, (rows_m, 1), 0)
    colm = rim - (rim // w) * w
    growm = ti * th - hm + rim // w

    def mvalid(dy, dx):
        return tap_valid(colm, growm, w, h_img, dy, dx)

    def conv2d(valid, ops, b_ref, ksize):
        return conv_taps(valid, ops, b_ref, ksize, w)

    cor = jax.nn.relu(jax.lax.dot_general(
        ca, wc1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(cdt) + bc1_ref[...])
    cor = jax.nn.relu(conv2d(mvalid, [(cor, wc2_ref)], bc2_ref, 3))
    fac = fa.astype(cdt)
    flo = jax.nn.relu(flow_conv7(fac, wf1_ref, bf1_ref, colm, growm, w,
                                 h_img))
    flo = jax.nn.relu(conv2d(mvalid, [(flo, wf2_ref)], bf2_ref, 3))
    out_m = jax.nn.relu(conv2d(mvalid, [(cor, woc_ref), (flo, wof_ref)],
                               bo_ref, 3))
    # The handoff, fused away: [motion ‖ flow] sliced from the deep span
    # to the GRU (±hg) span — valid on every slice row by the masks
    # above — and consumed in-register as the GRU's second x part.
    off = (hm - hg) * w
    rows_g = (th + 2 * hg) * w
    mot = jnp.concatenate([out_m, fac], axis=1)[off:off + rows_g]

    # ---- SepConvGRU over the (±hg) span -------------------------------
    ha = halo_assemble([r[0] for r in net_refs], g, hg * w)
    xia = halo_assemble([r[0] for r in inp_refs], g, hg * w)
    xas = (xia, mot)

    rig = jax.lax.broadcasted_iota(jnp.int32, (rows_g, 1), 0)
    colg = rig - (rig // w) * w
    growg = ti * th - hg + rig // w

    def hmask(d):
        cd = colg + d
        return ((cd >= 0) & (cd < w)).astype(cdt)

    def vmask(d):
        gr = growg + d
        return ((gr >= 0) & (gr < h_img)).astype(cdt)

    def sepconv(vh, vxs, wh_ref, wx_refs, b_ref, shift_mul, mask):
        ch = vh.shape[1]
        nout = b_ref.shape[1]
        acc = jnp.zeros((rows_g, nout), jnp.float32)
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

    zr1 = gate_sigmoid(sepconv(ha, xas, wzr1h, (wzr1xa, wzr1xb),
                               bzr1, 1, hmask))
    z1, r1 = zr1[:, :c], zr1[:, c:]
    q1 = jnp.tanh(sepconv(r1 * ha, xas, wq1h, (wq1xa, wq1xb),
                          bq1, 1, hmask))
    h1 = (1 - z1) * ha + z1 * q1
    zr2 = gate_sigmoid(sepconv(h1, xas, wzr2h, (wzr2xa, wzr2xb),
                               bzr2, w, vmask))
    z2, r2 = zr2[:, :c], zr2[:, c:]
    q2 = jnp.tanh(sepconv(r2 * h1, xas, wq2h, (wq2xa, wq2xb),
                          bq2, w, vmask))
    h2 = (1 - z2) * h1 + z2 * q2

    hw_g = hg * w
    klayout.boundary_store(out_refs[0], h2[hw_g:hw_g + g])

    # ---- flow head (mgf): two more 3x3s on the SAME resident h2 -------
    if fh:
        wfh1, bfh1, wfh2, bfh2 = fh_refs

        def gvalid(dy, dx):
            return tap_valid(colg, growg, w, h_img, dy, dx)

        fh1 = jax.nn.relu(conv2d(gvalid, [(h2, wfh1)], bfh1, 3))
        delta = folded_head_conv(gvalid, fh1, wfh2, bfh2, w)
        klayout.boundary_store(out_refs[1], delta[hw_g:hw_g + g])


def _pallas_step(static, net2d, inp2d, flow2d, corr2d, mmats, gmats,
                 fmats):
    """net2d/inp2d: (B, Hpad*W, C/Cinp); flow2d: (B, Hpad*W, 2);
    corr2d: (B, Hpad*W, Cc) — all already in the compute dtype; mats
    pre-packed and cast. Returns (B, Hpad*W, C) or a (h2, delta)
    pair."""
    w, h_img, th, interpret, fh = static
    b, n, c = net2d.shape
    g = th * w
    grid = (b, n // g)
    last = grid[1] - 1
    hg, hm = halos(fh)
    nm = -(-hm // th)
    ng = -(-hg // th)

    kernel = functools.partial(_step_kernel, w=w, h_img=h_img, th=th,
                               fh=fh)

    in_specs, operands = [], []
    for arr, nb in ((corr2d, nm), (flow2d, nm), (net2d, ng), (inp2d, ng)):
        chn = arr.shape[-1]
        for k in range(-nb, nb + 1):
            in_specs.append(pl.BlockSpec(
                (1, g, chn),
                lambda bi, ti, k=k: (bi, jnp.clip(ti + k, 0, last), 0)))
            operands.append(arr)
    flat_mats = kernel_mats(mmats) + list(_flatten_mats(gmats))
    if fh:
        wfh1, bfh1, wfh2, bfh2 = fmats
        flat_mats += [wfh1, bfh1, fold_head_taps(wfh2), bfh2]
    in_specs += [_full_spec(m) for m in flat_mats]

    spec_h, shape_h = klayout.query_tiled_out(b, n, c, g, net2d.dtype)
    if fh:
        spec_d, shape_d = klayout.query_tiled_out(b, n, 2, g,
                                                  net2d.dtype)
        out_specs, out_shape = [spec_h, spec_d], [shape_h, shape_d]
    else:
        out_specs, out_shape = spec_h, shape_h
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem.scan_compiler_params(),
        name=klayout.KERNEL_NAMES["step"],
    )(*operands, *flat_mats)
    return tuple(out) if fh else out


# ---------------------------------------------------------------------------
# Reference (identical math, pure jnp) — backward + parity oracle
# ---------------------------------------------------------------------------

def reference_step(static, net2d, inp2d, flow2d, corr2d, mmats, gmats,
                   fmats):
    """Pure-jnp twin: reference_motion → reference_gru → (optionally)
    the flow head's taps, on the full flattened array. Identical tap
    order, masks and cast points to the fused kernel; serves as the
    custom-VJP backward and the parity oracle in tests."""
    w, h_img = static[0], static[1]
    fh = bool(fmats)
    mot = reference_motion((w, h_img), flow2d, corr2d, mmats)
    h2 = reference_gru((w, h_img), net2d, (inp2d, mot), gmats)
    if not fh:
        return h2
    wfh1, bfh1, wfh2, bfh2 = fmats
    b, n, _ = h2.shape
    cdt = h2.dtype
    ri = jnp.arange(n)[None, :, None]
    col = ri % w
    row = ri // w

    def mask(dy, dx):
        cd = col + dx
        gr = row + dy
        return ((cd >= 0) & (cd < w)
                & (gr >= 0) & (gr < h_img)).astype(cdt)

    def conv2d(v, wm, bias):
        cin = v.shape[-1]
        acc = jnp.zeros((b, n, bias.shape[1]), jnp.float32)
        t = 0
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                acc += jax.lax.dot_general(
                    _bshift(v, dy * w + dx) * mask(dy, dx),
                    wm[t * cin:(t + 1) * cin, :],
                    (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                t += 1
        return acc.astype(cdt) + bias

    fh1 = jax.nn.relu(conv2d(h2, wfh1, bfh1))
    delta = conv2d(fh1, wfh2, bfh2)
    return h2, delta


# ---------------------------------------------------------------------------
# Custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _step(static, net2d, inp2d, flow2d, corr2d, mmats, gmats, fmats):
    return _pallas_step(static, net2d, inp2d, flow2d, corr2d, mmats,
                        gmats, fmats)


def _step_fwd(static, net2d, inp2d, flow2d, corr2d, mmats, gmats, fmats):
    out = _pallas_step(static, net2d, inp2d, flow2d, corr2d, mmats,
                       gmats, fmats)
    return out, (net2d, inp2d, flow2d, corr2d, mmats, gmats, fmats)


def _step_bwd(static, res, g):
    # Recompute-based backward through the identical-math jnp twin —
    # gradients reach net, inp, flow, corr and (through the packers)
    # the flax param tree. A fused Pallas backward is on-hardware perf
    # debt, as for the component kernels.
    net2d, inp2d, flow2d, corr2d, mmats, gmats, fmats = res
    _, vjp = jax.vjp(
        lambda *a: reference_step(static, *a),
        net2d, inp2d, flow2d, corr2d, mmats, gmats, fmats)
    return vjp(g)


_step.defvjp(_step_fwd, _step_bwd)


# ---------------------------------------------------------------------------
# Admission + dispatch
# ---------------------------------------------------------------------------

def step_vmem_parts(w: int, th: int, dtype_bytes: int, *,
                    flow_head: bool = False) -> dict:
    """Named scoped-VMEM estimate for one fused launch: Mosaic's
    calibrated per-row figure over the deep (motion) span of
    ``TH + 2*hm`` rows — 9 halo rows a side for ``mg``, 11 for ``mgf``.
    Holds for the canonical widths (C = Cinp = 128, at most 384 corr
    channels), which ``plan_fusion`` enforces."""
    _, hm = halos(flow_head)
    return vmem.scan_rows_parts("step", (th + 2 * hm) * w, dtype_bytes)


def choose_rows(h_img: int, w: int, cc: int, dtype_bytes: int, *,
                flow_head: bool = False) -> int | None:
    """Largest admissible row tile for one fused launch under the
    shared (16, 8, 4) ladder and ``step_vmem_parts``; None → this
    fusion depth doesn't fit (the caller steps down mgf → mg →
    two-launch chain). At Sintel eval shapes bf16 admits TH=16 at both
    depths; at 1080p (W=240) neither admits any — asserted in
    tests/test_step_pallas.py."""
    return vmem.choose_rows(
        _ROW_LADDER, w,
        lambda th: step_vmem_parts(w, th, dtype_bytes,
                                   flow_head=flow_head))


def resolve_mode() -> str:
    """``RAFT_STEP_PALLAS`` → {'auto', '0', '1'} (trace-time; bakes
    into each compiled executable, so serving warmup covers it)."""
    return resolve_step_pallas()


def plan_fusion(net, inp, corr, flow, want_flow_head: bool,
                mode: str | None = None) -> str | None:
    """Dispatch decision for ``BasicUpdateBlock.__call__``: None (keep
    the two-launch chain / conv path, whose own flags then apply),
    ``'mg'`` or ``'mgf'``.

    '0' → None always (byte-identical to today). '1' → force: off-TPU
    runs the interpreter (parity tooling); on TPU raises if even 'mg'
    fits no tile. 'auto' → fuse only on a real TPU backend, preferring
    'mgf' where wanted and admissible, stepping down to 'mg', and
    falling back to None with a LOUD ``vmem.log_fallback`` when the
    ladder rejects the shape entirely.
    """
    if mode is None:
        mode = resolve_mode()
    if mode == "0":
        return None
    shape_ok = (net.ndim == 4 and inp.ndim == 4 and corr.ndim == 4
                and flow.ndim == 4 and flow.shape[-1] == 2
                and net.shape[:3] == inp.shape[:3] == corr.shape[:3]
                and corr.shape[:3] == flow.shape[:3])
    if not shape_ok:
        if mode == "1":
            raise ValueError(
                f"{STEP_FLAG}=1 but net/inp/corr/flow have shapes "
                f"{net.shape}/{inp.shape}/{corr.shape}/{flow.shape} "
                f"(expected NHWC with matching spatial dims and 2 flow "
                f"channels)")
        return None
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        # Interpret mode is a parity tool, not a fast path: only a
        # forced '1' runs it; auto keeps the XLA/chained path off-TPU.
        return ("mgf" if want_flow_head else "mg") if mode == "1" else None
    from raft_tpu.parallel.spatial import keeps_xla_under_partitioning
    if keeps_xla_under_partitioning(STEP_FLAG, mode):
        return None
    _, hh, ww, c = net.shape
    cinp = inp.shape[-1]
    cc = corr.shape[-1]
    d = jnp.dtype(net.dtype).itemsize
    # The widths the VMEM figure was calibrated at (vmem._ROW_BYTES).
    widths_ok = c == 128 and cinp == 128 and cc <= _MAX_CORR_CHANNELS
    if widths_ok and want_flow_head and choose_rows(
            hh, ww, cc, d, flow_head=True):
        return "mgf"
    if widths_ok and choose_rows(hh, ww, cc, d, flow_head=False):
        return "mg"
    if mode == "1":
        raise ValueError(
            f"{STEP_FLAG}=1 but shape (H={hh}, W={ww}, C={c}, "
            f"Ccorr={cc}, dtype={jnp.dtype(net.dtype).name}) admits no "
            f"row tile even for the 'mg' fusion; use auto to fall back "
            f"to the two-launch chain")
    vmem.log_fallback(
        STEP_FLAG,
        f"(H={hh}, W={ww}, C={c}, Ccorr={cc}, "
        f"dtype={jnp.dtype(net.dtype).name})",
        step_vmem_parts(ww, _ROW_LADDER[-1], d))
    return None


def fused_step(net, inp, corr, flow, mmats, gmats, fmats=None, *,
               dtype=None, interpret: bool | None = None,
               th: int | None = None):
    """Run one fused refine iteration.

    Args:
      net: ``(B, H, W, C)`` hidden state (the scan carry).
      inp: ``(B, H, W, Cinp)`` context features (first GRU x part).
      corr: ``(B, H, W, Cc)`` correlation window.
      flow: ``(B, H, W, 2)`` current flow estimate.
      mmats: ``motion_pallas.pack_weights`` output.
      gmats: ``gru_pallas.pack_weights`` output (un-split; split into
        the (inp, motion) x parts here).
      fmats: ``pack_flow_head`` output, or None for the 'mg' depth.
      dtype: compute dtype (the flax module's); default ``net.dtype``.
      interpret: force Pallas interpret mode (defaults to True
        off-TPU).
      th: row-tile override for tests; default = largest admissible.

    Returns ``(B, H, W, C)`` h2 in ``net.dtype`` — or, with ``fmats``,
    an ``(h2, delta_flow)`` pair with ``delta_flow (B, H, W, 2)`` in
    the compute dtype (the conv flow head's output dtype).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fh = fmats is not None
    b, hh, ww, c = net.shape
    cinp = inp.shape[-1]
    cc = corr.shape[-1]
    co = mmats[-1].shape[1]
    cdt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(net.dtype)
    out_dt = net.dtype

    if th is None:
        if interpret:
            th = 4
        else:
            th = choose_rows(hh, ww, cc, cdt.itemsize,
                             flow_head=fh) or _ROW_LADDER[-1]
    if not interpret:
        vmem.preflight(
            step_vmem_parts(ww, th, cdt.itemsize, flow_head=fh),
            f"fused step kernel (th={th}, w={ww}, flow_head={fh})",
            vmem.SCAN_LIMIT_BYTES)

    hpad = _round_up(hh, th)

    def to2d(a):
        a2 = a.astype(cdt).reshape(b, hh * ww, a.shape[-1])
        if hpad != hh:
            a2 = jnp.pad(a2, ((0, 0), (0, (hpad - hh) * ww), (0, 0)))
        return a2

    net2d, inp2d, flow2d, corr2d = map(to2d, (net, inp, flow, corr))
    mmats = tuple(m.astype(cdt) for m in mmats)
    gmats = tuple(
        tuple(p.astype(cdt) for p in m) if isinstance(m, (tuple, list))
        else m.astype(cdt)
        for m in split_x_weights(gmats, (cinp, co + 2)))
    fmats = tuple(m.astype(cdt) for m in fmats) if fh else ()

    static = (ww, hh, th, bool(interpret), fh)
    out = _step(static, net2d, inp2d, flow2d, corr2d, mmats, gmats,
                fmats)
    if fh:
        h2, delta = out
        return (h2[:, :hh * ww].reshape(b, hh, ww, c).astype(out_dt),
                delta[:, :hh * ww].reshape(b, hh, ww, 2))
    return out[:, :hh * ww].reshape(b, hh, ww, c).astype(out_dt)
