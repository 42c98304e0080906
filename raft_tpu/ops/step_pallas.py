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

Two fusion depths, by what the iteration needs (``plan_fusion``):

* ``'mg'`` — motion encoder + GRU, emitting the new hidden state. Used
  on iterations that also need the mask head (``compute_mask=True``),
  whose ``_concat_conv`` stays on the XLA side.
* ``'mgf'`` — + the flow head's two 3x3 convs, emitting ``(h2, delta)``
  as two outputs. Admitted wherever ``'mg'`` is.

No tap of a two-channel conv gets an MXU pass of its own: ``convf1``
(7x7 on the 2-channel flow) is one contraction over its 98 tap-channels
(``motion_pallas.flow_patches``, shared with the stand-alone motion
kernel) and the flow head's last conv (3x3, 256 -> 2) is one product
with its nine taps on the output axis (``folded_head_conv``). As 49
products with K = 2 and 9 with N = 2 they were a quarter of the passes
the kernel streams and, by their per-tap temporaries, a third of its
VMEM.

Every row of every stage is computed once. The receptive fields
compose across the chain — an output row of ``mg`` reads corr and flow
9 rows each side, one of ``mgf`` 11 (``halos``) — and a tile that
stood alone would have to recompute that many rows of the motion chain
and of the GRU beside every ``th`` it keeps (22 and 12 for 16 at
``mgf``). Instead the grid walks the row tiles of one image in order
and each stage produces its next ``th`` rows a grid step into a span
in VMEM scratch that holds (the last rows the stage produced in the
previous steps) + (its new rows), which its readers slice: 2 rows kept
for a 3x3, 6 of the flow for the 7x7, 4 for the vertical gate convs,
more where a later stage reads the same rows further behind
(``_carry_rows``); at the end of a step a span moves up by ``th``
rows. A stage with vertical taps runs that many rows behind its
source, so the outputs run ``hm`` rows behind the inputs: an image
takes ``ceil(hm / th)`` closing grid steps after its last tile, the
output block of a step is completed from a kept part and a new part,
and every input block is fetched once. Rows outside the image are
zeroed where they are produced, by global row, which is the convs'
zero padding; the kept rows are zeroed at an image's first step. An
image row takes a whole number of sublane tiles of flattened rows (62
columns are padded to 64 by the wrapper, beside the rows' pad to whole
tiles), so every slice of a span starts on a tile boundary: on the
chip, spans sliced off the boundary read wrong. Per output row the
arithmetic is the stand-alone kernels': the same products with the
same operands summed in the same order, only the rows that share a
product differ — parity with the two-launch chain is bit-exact at f32,
and ≤2e-4 vs the conv path (``tests/test_step_pallas.py``).

VMEM admission is ``step_vmem_parts`` — Mosaic's own figures for this
body, a part by the tile's rows and a part by the width for the
rows kept, beside the weights — under the ladder ``(16, 8)`` and the
explicit 100 MiB limit. Every admitted rung streams ``(tiles + closing
steps) * th`` rows a stage for the image's ``H``, so ``choose_rows``
takes the admitted rung that streams fewest (the taller on a tie): TH
8 for a 55-row Sintel map (72 rows against 80 at TH 16), TH 16 for
chairs' 46. A shape the ladder rejects falls back, loudly logged, to
the two-launch chain, never silently.

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
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops import layout as klayout
from raft_tpu.ops import vmem
from raft_tpu.ops.gru_pallas import (_TAPS, _bshift, _flatten_mats,
                                     _full_spec, _round_up, _shift_rows,
                                     gate_sigmoid, split_x_weights)
from raft_tpu.ops.gru_pallas import reference_gru
from raft_tpu.ops.motion_pallas import (_MAX_CORR_CHANNELS, flow_patches,
                                        kernel_mats, reference_motion)
from raft_tpu.utils.envflags import STEP_FLAG, resolve_step_pallas

# Rows each stage runs behind the input blocks of its grid step: a stage
# with vertical taps of reach r reads its source to r rows below its own
# row, so it runs r rows behind that source. ``convc1`` (1x1) runs with
# the input; the GRU's horizontal half has no vertical taps and runs
# with ``[motion | flow]``.
_LAG_FLO1 = 3                    # convf1, 7x7 on the flow
_LAG_MID = _LAG_FLO1 + 1         # convc2 and convf2
_LAG_MOT = _LAG_MID + 1          # the motion conv; zr1, q1, h1
_LAG_ZR2 = _LAG_MOT + 2          # the vertical gates
_LAG_H2 = _LAG_ZR2 + 2           # q2, h2: the 'mg' output
_LAG_FH1 = _LAG_H2 + 1           # the flow head's first conv
_LAG_DELTA = _LAG_FH1 + 1        # the 'mgf' output

# Row-tile ladder for real launches. The component kernels' TH = 4 rung
# is left out: the streamed body reads wrong there on the chip (v5e,
# PR 36: a third of the outputs off at Sintel and chairs, both depths,
# where TH 8 and 16 equal the self-contained tiles bit for bit; equal
# in interpret mode too, cause not found), and every shape that fits a
# rung fits TH 8.
_ROW_LADDER = (16, 8)

# Flattened rows an image row is padded to a multiple of: a packed
# bfloat16 sublane tile (two float32 ones).
_ROW_ALIGN = 16


def halos(flow_head: bool) -> tuple[int, int]:
    """``(hg, hm)``: rows each side of an output row that the chain
    reads of net/inp (GRU ±4, flow head ±2 more) and of corr/flow (the
    motion encoder's ±5 beyond that). No tile assembles them: ``hm`` is
    what the streamed body's outputs run behind its input blocks, and
    ``ceil(hm / th)`` the closing grid steps of an image."""
    hm = _LAG_DELTA if flow_head else _LAG_H2
    return hm - _LAG_MOT, hm


def grid_steps(h_img: int, th: int, flow_head: bool) -> int:
    """Grid steps an image takes at tile height ``th``: its row tiles,
    then the closing steps its outputs lag by. Every stage computes
    ``th`` rows in each."""
    return -(-h_img // th) + -(-halos(flow_head)[1] // th)


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


def folded_head_conv(taps, b_ref, w: int, w_img: int, g: int, dtype):
    """The 3x3 ``Fh -> 2`` conv from its product ``fh1 @ [W_0 | ... |
    W_8]`` (``taps``: float32, 18 columns, over the ``g`` output rows
    and one image row each side) as nine shifted adds of two columns
    each, instead of nine products with N = 2 (each a whole pass of the
    rows through the MXU for two output columns). ``delta[p] = sum_t
    (fh1[p + s_t] @ W_t)`` is the per-tap sum with the shift applied to
    the product's rows instead of the operand's: the same float32 terms
    added in the same order, then the same cast and compute-dtype bias
    add. Rows of ``fh1`` outside the image are zero at the source (so
    are their products); a tap that leaves the image sideways is zeroed
    here (``w_img`` columns of the ``w`` an image row takes)."""
    col = _col(taps.shape[0], w)
    shifted = {0: taps}
    for dx in (-1, 1):
        shifted[dx] = (_shift_rows(taps, dx)
                       * _col_valid(col, dx, w_img).astype(jnp.float32))
    acc = jnp.zeros((g, 2), jnp.float32)
    t = 0
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            acc += shifted[dx][(1 + dy) * w:(1 + dy) * w + g,
                               2 * t:2 * t + 2]
            t += 1
    return acc.astype(dtype) + b_ref[...]


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _col(rows: int, w: int):
    """Image column of each of ``rows`` flattened rows, as ``(rows, 1)``."""
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return ri - (ri // w) * w


def _col_valid(col, dx: int, w_img: int):
    cd = col + dx
    return (cd >= 0) & (cd < w_img)


def conv3_rows(ops, b_ref, w: int, w_img: int, g: int):
    """A 3x3 conv's ``g`` output rows from operands that span them and
    one image row each side: per tap ``(dy, dx)`` one MXU product of
    exactly ``g`` rows, summed over the input operands in
    ``motion_pallas.conv_taps``' order (float32 accumulation,
    compute-dtype bias add). The sideways shift and its column mask are
    applied once a ``dx`` to the whole span, and a tap's ``dy`` is a
    slice of it by whole image rows; rows outside the image are zero at
    the source, which is the conv's zero padding. Value for value the
    operands ``conv_taps`` builds with a shift and a mask a tap. An
    image row takes ``w`` flattened rows, ``w_img`` of them columns."""
    cdt = b_ref.dtype
    col = _col(ops[0][0].shape[0], w)
    shifted = {0: [v for v, _ in ops]}
    for dx in (-1, 1):
        mk = _col_valid(col, dx, w_img).astype(cdt)
        shifted[dx] = [_shift_rows(v, dx) * mk for v, _ in ops]
    acc = jnp.zeros((g, b_ref.shape[1]), jnp.float32)
    t = 0
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            for (v, w_ref), sv in zip(ops, shifted[dx]):
                cin = v.shape[1]
                acc += _dot(sv[(1 + dy) * w:(1 + dy) * w + g],
                            w_ref[t * cin:(t + 1) * cin, :])
            t += 1
    return acc.astype(cdt) + b_ref[...]


def _carry_rows(th: int, fh: bool) -> dict:
    """Image rows each stage keeps in VMEM for the next grid step: what
    its consumers read above this step's ``th`` new rows (a source at
    lag ``a`` read by a stage at lag ``b`` with reach ``r`` keeps ``b +
    r - a`` rows; the deepest reader decides), and for the outputs the
    rows that complete the next output block."""
    out_lag = -(-halos(fh)[1] // th) * th    # the out blocks', in rows
    rows = {
        "cor1": _LAG_MID + 1,            # convc1 runs with the input
        "flow": _LAG_FLO1 + 3,
        "flo1": 2, "cor2": 2, "flo2": 2,
        "net": _LAG_MOT,
        "inp": _LAG_H2 + 2,              # q2 reads x two rows above h2
        "mot": _LAG_H2 + 2 - _LAG_MOT,
        "h1": _LAG_H2 - _LAG_MOT,        # zr2's taps, then h2's blend
        "rh": _LAG_H2 + 2 - _LAG_ZR2,
        "z2": _LAG_H2 - _LAG_ZR2,
        "h2": max(out_lag - _LAG_H2, 2 if fh else 0),
    }
    if fh:
        rows["taps"] = 2
        rows["delta"] = out_lag - _LAG_DELTA
    return rows


def _step_kernel(*refs, w: int, w_img: int, h_img: int, th: int, fh: bool,
                 stages):
    """One grid step of the streamed refine iteration: every stage of
    the chain produces its next ``th`` rows of the image.

    ``refs`` is ``(corr, flow, net, inp, <11 motion mats>, <16 GRU
    mats>, [4 flow-head mats,] h2_out [, delta_out], <one VMEM span a
    name of stages>)``. An image row takes ``w`` flattened rows, of
    which ``w_img`` are image columns. Grid step ``ti`` brings input
    rows ``[ti*th, (ti+1)*th)``; a stage at lag ``a`` produces rows
    ``[ti*th - a, (ti+1)*th - a)`` into its span — (the trailing rows it
    kept from the last step) + (its new rows) — which its readers slice,
    and no row of any stage is computed twice. The outputs run ``hm``
    rows behind, so the out block of step ``ti`` is block ``ti -
    ceil(hm/th)``, completed from a kept part and a new part, and an
    image takes ``ceil(hm/th)`` closing steps whose input blocks are the
    last block again (their rows lie below the image and are masked like
    any other).

    Rows outside the image are zeroed where they are produced (the
    global row of a stage's row ``i`` is ``ti*th - lag + i // w``), so a
    reader's vertical taps read the convs' zero padding with no mask of
    their own; sideways taps keep their column masks.
    """
    nouts = 2 if fh else 1
    span = dict(zip(stages, refs[len(refs) - len(stages):]))
    refs = refs[:len(refs) - len(stages)]
    out_refs = refs[-nouts:]
    corr_ref, flow_ref, net_ref, inp_ref = refs[:4]
    (wc1_ref, bc1_ref, wc2_ref, bc2_ref, wf1_ref, bf1_ref,
     wf2_ref, bf2_ref, woc_ref, wof_ref, bo_ref) = refs[4:15]
    (wzr1h, wzr1xa, wzr1xb, wq1h, wq1xa, wq1xb, bzr1, bq1,
     wzr2h, wzr2xa, wzr2xb, wq2h, wq2xa, wq2xb, bzr2, bq2) = refs[15:31]

    g = th * w
    c = out_refs[0].shape[-1]
    cdt = net_ref.dtype
    ti = pl.program_id(1)

    def kept(ref):
        """Image rows of a span above this step's ``th`` new ones."""
        return ref.shape[0] // w - th

    # A new image: the kept rows are the last one's tail (or, at the very
    # first step, whatever the memory held), and a row mask applied by
    # multiplication must never meet a stale Inf or NaN.
    @pl.when(ti == 0)
    def _():
        for ref in span.values():
            if kept(ref):
                ref[:kept(ref) * w, :] = jnp.zeros(
                    (kept(ref) * w, ref.shape[1]), ref.dtype)

    def put(name, new):
        """This step's rows of stage ``name``, below the rows it kept."""
        ref = span[name]
        ref[kept(ref) * w:, :] = new
        return ref

    def rows_of(ref, first, n=th):
        return ref[first * w:(first + n) * w, :]

    def at(ref, src_lag, lag, up=0):
        """``(ref, its image row that lies ``up`` rows above the first
        row of a stage at ``lag``)``, for a span whose new rows are at
        ``src_lag``."""
        return ref, kept(ref) + src_lag - lag - up

    row = ti * th + jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0) // w

    def inside(lag):
        gr = row - lag
        return ((gr >= 0) & (gr < h_img)).astype(cdt)

    def conv3(ops, b_ref):
        return conv3_rows(ops, b_ref, w, w_img, g)

    # ---- motion encoder ------------------------------------------------
    cor1 = put("cor1", jax.nn.relu(
        _dot(corr_ref[0], wc1_ref[...]).astype(cdt) + bc1_ref[...])
        * inside(0))
    cor2 = put("cor2", jax.nn.relu(conv3(
        [(rows_of(*at(cor1, 0, _LAG_MID, 1), th + 2), wc2_ref)], bc2_ref))
        * inside(_LAG_MID))
    # The flow keeps its clamped or padded rows here: ``flow_patches``
    # zeroes a source row outside the image itself, and the passthrough
    # is masked with the motion features below.
    flow = put("flow", flow_ref[0].astype(cdt))
    nfl = flow.shape[0]
    rif = jax.lax.broadcasted_iota(jnp.int32, (nfl, 1), 0)
    patches = flow_patches(flow[...], _col(nfl, w),
                           ti * th - kept(flow) + rif // w, w_img, h_img,
                           stride=w)
    first = kept(flow) - _LAG_FLO1
    flo1 = put("flo1", jax.nn.relu(
        _dot(patches[first * w:first * w + g], wf1_ref[...]).astype(cdt)
        + bf1_ref[...]) * inside(_LAG_FLO1))
    flo2 = put("flo2", jax.nn.relu(conv3([(flo1[...], wf2_ref)], bf2_ref))
               * inside(_LAG_MID))
    out_m = jax.nn.relu(conv3([(cor2[...], woc_ref), (flo2[...], wof_ref)],
                              bo_ref))
    # The handoff, fused away: [motion | flow] is the GRU's second x part
    # and never leaves VMEM.
    mot = put("mot", jnp.concatenate(
        [out_m, rows_of(*at(flow, 0, _LAG_MOT))], axis=1)
        * inside(_LAG_MOT))

    # ---- SepConvGRU ----------------------------------------------------
    net = put("net", net_ref[0])
    inp = put("inp", inp_ref[0] * inside(0))

    col = _col(g, w)
    hmask = [_col_valid(col, k - 2, w_img).astype(cdt)
             for k in range(_TAPS)]

    def hconv(vh, vxs, wh_ref, wx_refs, b_ref):
        """A (1, 5) gate conv on this step's rows: per tap a shifted,
        column-masked product of the h part, then of each x part."""
        acc = jnp.zeros((g, b_ref.shape[1]), jnp.float32)
        for k in range(_TAPS):
            for v, wm_ref in zip((vh, *vxs), (wh_ref, *wx_refs)):
                ch = v.shape[1]
                acc += _dot(_shift_rows(v, k - 2) * hmask[k],
                            wm_ref[k * ch:(k + 1) * ch, :])
        return acc.astype(cdt) + b_ref[...]

    def vconv(vh, vxs, wh_ref, wx_refs, b_ref):
        """A (5, 1) gate conv: ``vh`` and each of ``vxs`` is ``(span,
        image row of the span that tap -2 of the first output row
        reads)``; a tap is a slice by whole image rows, and rows outside
        the image are already zero."""
        acc = jnp.zeros((g, b_ref.shape[1]), jnp.float32)
        for k in range(_TAPS):
            for (v, first), wm_ref in zip((vh, *vxs), (wh_ref, *wx_refs)):
                ch = v.shape[1]
                acc += _dot(rows_of(v, first + k),
                            wm_ref[k * ch:(k + 1) * ch, :])
        return acc.astype(cdt) + b_ref[...]

    ha = rows_of(*at(net, 0, _LAG_MOT))
    xas = (rows_of(*at(inp, 0, _LAG_MOT)),
           rows_of(*at(mot, _LAG_MOT, _LAG_MOT)))
    zr1 = gate_sigmoid(hconv(ha, xas, wzr1h, (wzr1xa, wzr1xb), bzr1))
    z1, r1 = zr1[:, :c], zr1[:, c:]
    q1 = jnp.tanh(hconv(r1 * ha, xas, wq1h, (wq1xa, wq1xb), bq1))
    h1 = put("h1", ((1 - z1) * ha + z1 * q1) * inside(_LAG_MOT))

    zr2 = gate_sigmoid(vconv(
        at(h1, _LAG_MOT, _LAG_ZR2, 2),
        (at(inp, 0, _LAG_ZR2, 2), at(mot, _LAG_MOT, _LAG_ZR2, 2)),
        wzr2h, (wzr2xa, wzr2xb), bzr2))
    z2, r2 = put("z2", zr2[:, :c]), zr2[:, c:]
    rh = put("rh", r2 * rows_of(*at(h1, _LAG_MOT, _LAG_ZR2)))
    q2 = jnp.tanh(vconv(
        at(rh, _LAG_ZR2, _LAG_H2, 2),
        (at(inp, 0, _LAG_H2, 2), at(mot, _LAG_MOT, _LAG_H2, 2)),
        wq2h, (wq2xa, wq2xb), bq2))
    z2 = rows_of(*at(z2, _LAG_ZR2, _LAG_H2))
    h2 = (1 - z2) * rows_of(*at(h1, _LAG_MOT, _LAG_H2)) + z2 * q2
    h2 = put("h2", h2 * inside(_LAG_H2) if fh else h2)
    # The out blocks run ``closing * th`` rows behind the input's.
    out_lag = -(-halos(fh)[1] // th) * th
    klayout.boundary_store(out_refs[0],
                           rows_of(*at(h2, _LAG_H2, out_lag)))

    # ---- flow head (mgf): two more 3x3s on the SAME resident h2 -------
    if fh:
        wfh1, bfh1, wfh2, bfh2 = refs[31:35]
        fh1 = jax.nn.relu(conv3(
            [(rows_of(*at(h2, _LAG_H2, _LAG_FH1, 1), th + 2), wfh1)],
            bfh1)) * inside(_LAG_FH1)
        taps = put("taps", _dot(fh1, wfh2[...]))
        delta = put("delta", folded_head_conv(taps[...], bfh2, w, w_img, g,
                                              cdt))
        klayout.boundary_store(out_refs[1],
                               rows_of(*at(delta, _LAG_DELTA, out_lag)))

    # Keep each stage's trailing rows for the next step, after every
    # reader of this step's spans: the span moves up by ``th`` rows, at
    # most ``th`` rows at a time, so that no copy reads rows it writes.
    for ref in span.values():
        for r0 in range(0, kept(ref), th):
            n = min(th, kept(ref) - r0)
            ref[r0 * w:(r0 + n) * w, :] = ref[(r0 + th) * w:
                                              (r0 + th + n) * w, :]


def _pallas_step(static, net2d, inp2d, flow2d, corr2d, mmats, gmats,
                 fmats):
    """net2d/inp2d: (B, H*W, C/Cinp); flow2d: (B, H*W, 2); corr2d:
    (B, H*W, Cc) — all already in the compute dtype; mats pre-packed and
    cast. Pads the rows to whole tiles and an image row to whole
    sublane tiles (every slice the kernel takes of a span then starts on
    a tile boundary), runs the launch and slices back. Returns
    (B, H*W, C) or a (h2, delta) pair."""
    w_img, h_img, th, interpret, fh = static
    b, _, c = net2d.shape
    w = _round_up(w_img, _ROW_ALIGN)
    tiles = -(-h_img // th)
    closing = -(-halos(fh)[1] // th)
    g = th * w
    n = tiles * g
    dtype = net2d.dtype

    def padded(a2):
        if w == w_img:      # whole rows only: cheaper on the flat array
            return jnp.pad(a2, ((0, 0), (0, n - h_img * w), (0, 0)))
        a4 = a2.reshape(b, h_img, w_img, a2.shape[-1])
        a4 = jnp.pad(a4, ((0, 0), (0, tiles * th - h_img),
                          (0, w - w_img), (0, 0)))
        return a4.reshape(b, n, a2.shape[-1])

    def unpadded(a2):
        if w == w_img:
            return a2[:, :h_img * w]
        a4 = a2.reshape(b, tiles * th, w, a2.shape[-1])
        return a4[:, :h_img, :w_img].reshape(b, h_img * w_img,
                                             a2.shape[-1])

    widths = {"cor1": mmats[0].shape[1], "flow": 2,
              "flo1": mmats[4].shape[1], "cor2": mmats[2].shape[1],
              "flo2": mmats[6].shape[1], "net": c,
              "inp": inp2d.shape[-1], "mot": mmats[-1].shape[1] + 2,
              "h1": c, "rh": c, "z2": c, "h2": c, "taps": 18, "delta": 2}
    stages = _carry_rows(th, fh)
    kernel = functools.partial(_step_kernel, w=w, w_img=w_img, h_img=h_img,
                               th=th, fh=fh, stages=tuple(stages))

    # Every input block is fetched once, in order (the closing steps
    # name the last block again, which is no new fetch); the output
    # blocks run ``closing`` steps behind, and the steps before the
    # first of them leave block 0 in VMEM until it is whole.
    operands = [padded(a) for a in (corr2d, flow2d, net2d, inp2d)]
    in_specs = [pl.BlockSpec(
        (1, g, a.shape[-1]),
        lambda bi, ti: (bi, jnp.minimum(ti, tiles - 1), 0))
        for a in operands]
    flat_mats = kernel_mats(mmats) + list(_flatten_mats(gmats))
    if fh:
        wfh1, bfh1, wfh2, bfh2 = fmats
        flat_mats += [wfh1, bfh1, fold_head_taps(wfh2), bfh2]
    in_specs += [_full_spec(m) for m in flat_mats]

    def lagged_out(feat):
        return (pl.BlockSpec(
            (1, g, feat),
            lambda bi, ti: (bi, jnp.maximum(ti - closing, 0), 0)),
            jax.ShapeDtypeStruct((b, n, feat), dtype))

    spec_h, shape_h = lagged_out(c)
    if fh:
        spec_d, shape_d = lagged_out(2)
        out_specs, out_shape = [spec_h, spec_d], [shape_h, shape_d]
    else:
        out_specs, out_shape = spec_h, shape_h
    out = pl.pallas_call(
        kernel,
        grid=(b, tiles + closing),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(
            ((rows + th) * w, widths[name]),
            jnp.float32 if name == "taps" else dtype)
            for name, rows in stages.items()],
        interpret=interpret,
        # Images are independent; an image's row tiles are walked in
        # order, each step reading what the last one kept.
        compiler_params=vmem.scan_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        name=klayout.KERNEL_NAMES["step"],
    )(*operands, *flat_mats)
    return tuple(map(unpadded, out)) if fh else unpadded(out)


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
    """Named scoped-VMEM estimate for one fused launch, from Mosaic's
    own reports for the streamed body (``vmem.STEP_BYTES``): the
    weights, the rows the stages keep for the next grid step (by the
    padded width alone: they are image rows, however tall the tile) and
    what is live of a grid step's ``th`` rows. Either depth is held to
    the 'mgf' figures. Holds for
    the canonical widths (C = Cinp = 128, at most 384 corr channels),
    which ``plan_fusion`` enforces."""
    weights, per_column, per_row = vmem.STEP_BYTES[dtype_bytes]
    w = _round_up(w, _ROW_ALIGN)
    return {"weights": weights, "rows_kept": per_column * w,
            "tile_rows_live": per_row * th * w}


def choose_rows(h_img: int, w: int, cc: int, dtype_bytes: int, *,
                flow_head: bool = False) -> int | None:
    """The row tile of one fused launch, from the shape alone: of the
    rungs of the ladder that fit ``step_vmem_parts``, the one whose grid
    streams least — every stage computes ``grid_steps * th`` rows for
    the image's ``h_img``, and a grid step costs about half a row more
    (Sintel 'mgf' on the chip, batch 128: 80 rows in 5 steps at TH 16
    took 63.6 ms with the wrapper's pad to 64 rows, 72 in 9 at TH 8
    53.0 ms); the taller rung on a tie. None → no rung fits (the
    caller falls back to the two-launch chain)."""
    admitted = [th for th in _ROW_LADDER
                if vmem.fits(
                    step_vmem_parts(w, th, dtype_bytes,
                                    flow_head=flow_head),
                    vmem.SCAN_LIMIT_BYTES)]
    return min(admitted, default=None,
               key=lambda th: (grid_steps(h_img, th, flow_head)
                               * (2 * th + 1), -th))


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
    runs the interpreter (parity tooling); on TPU raises if the shape
    fits no tile. 'auto' → fuse only on a real TPU backend, at the depth
    that is wanted (the streamed body's working rows do not grow with
    the depth, so no shape admits 'mg' alone), falling back to None with
    a LOUD ``vmem.log_fallback`` when the ladder rejects the shape.
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
    # The widths the VMEM figures were read at (vmem.STEP_BYTES).
    widths_ok = c == 128 and cinp == 128 and cc <= _MAX_CORR_CHANNELS
    if widths_ok and choose_rows(hh, ww, cc, d,
                                 flow_head=want_flow_head):
        return "mgf" if want_flow_head else "mg"
    if mode == "1":
        raise ValueError(
            f"{STEP_FLAG}=1 but shape (H={hh}, W={ww}, C={c}, "
            f"Ccorr={cc}, dtype={jnp.dtype(net.dtype).name}) admits no "
            f"row tile; use auto to fall back to the two-launch chain")
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
      th: row-tile override for tests (a real launch takes only a rung
        of the ladder); default = ``choose_rows``.

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
        if th not in _ROW_LADDER:
            raise ValueError(
                f"fused step kernel: th={th} is no rung of {_ROW_LADDER}, "
                f"the tiles the kernel is held to on the chip")
        vmem.preflight(
            step_vmem_parts(ww, th, cdt.itemsize, flow_head=fh),
            f"fused step kernel (th={th}, w={ww}, flow_head={fh})",
            vmem.SCAN_LIMIT_BYTES)

    def to2d(a):
        return a.astype(cdt).reshape(b, hh * ww, a.shape[-1])

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
        return (h2.reshape(b, hh, ww, c).astype(out_dt),
                delta.reshape(b, hh, ww, 2))
    return out.reshape(b, hh, ww, c).astype(out_dt)
