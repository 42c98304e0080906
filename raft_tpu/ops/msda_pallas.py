"""Multi-scale deformable attention — Pallas TPU kernel.

TPU-native equivalent of the reference's dense-regime MSDA CUDA kernels
(reference ``core/ops/src/cuda/ms_deform_im2col_cuda.cuh:238`` forward;
``:302-846`` backward variants): per (query, head, level, point),
bilinearly sample the value map at a predicted location and accumulate
with a predicted attention weight — *without per-sample gathers*.

Why a kernel at all: the vectorized jnp core (`raft_tpu.ops.msda`) is the
right tool for the live sparse model's 100-keypoint decoder (the gathers
are bandwidth-trivial there), but the dense-query *encoder* regime
(the ``ours_07`` encoder stacks, ``OursConfig.encoder_iterations``:
every HW token is a query) pays a full (8, 128) HBM tile per scalar gather — measured at
21.8 ms for ONE encoder layer at 10.5k tokens on v5e (TPU_EXTRAS.json
``msda_dense``), slower than an entire 12-iteration RAFT forward.

Design (same language as ``corr_pallas.py``, not a CUDA translation):

* **Bilinear sampling as separable hat-weight matmuls.** A bilinear
  sample at pixel ``(px, py)`` is ``sum_{y,x} hat(y-py) hat(x-px)
  V[y, x]`` with ``hat(d) = max(0, 1-|d|)`` — only the two neighboring
  rows/columns contribute, and columns outside the map contribute zero
  (``grid_sample(padding_mode='zeros')`` exactly). For a *tile* of
  queries the x-side contraction over all ``P`` points of all ``M``
  heads is a dense MXU matmul of the value level against a computed
  hat-weight matrix; the y-side collapses to a VPU multiply + a tiny
  fixed selection matmul. No gather, no scatter, no serialization on
  the point count.

* **VMEM-resident value level.** The whole per-level value tensor
  (``M*D*H x W`` — ~5.4 MB for the sparse family's largest level at
  d_model=128) stays in VMEM across query tiles (constant index map);
  queries stream through as the lane dimension, 128 per grid step.

* **Backward is the transpose of the same pipeline** plus the exact
  piecewise-constant corner-difference derivative for the sampling
  locations (matching ``F.grid_sample``'s gradient: ``dV[x1]-dV[x0]``
  corner differences — implemented as a second hat-style matmul with
  the sign-window ``c(d) = +1 on (0,1], -1 on (-1,0]``). Value
  gradients accumulate across query tiles by output-block revisiting —
  no atomics, unlike the CUDA backward's ``atomicAdd``
  (``ms_deform_im2col_cuda.cuh:436``). All three inputs get gradients
  (value, sampling locations, attention weights), the full contract of
  the reference extension — unlike the corr kernel, whose coords are
  detached upstream by design.

  Gradient fine print: location gradients agree with the reference
  almost everywhere; at *exactly integer* sampling coordinates both
  pick a subgradient of the same piecewise-linear function (ours the
  corner-difference with the right-open window, same as torch's), and
  the parity tests sample away from the measure-zero kink set.

Numerics: accumulation in float32 regardless of input dtype; parity with
the jnp reference is asserted in ``tests/test_msda_pallas.py`` (forward
and all three gradients), and the module is exercised through
``MSDeformAttn(backend=...)`` in the same file.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.ops.layout import KERNEL_NAMES

_LANE = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _hat(dist: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(0.0, 1.0 - jnp.abs(dist))


def _corner(delta: jnp.ndarray) -> jnp.ndarray:
    """d(hat)/d(-p) with the reference's corner choice: +1 on (0, 1],
    -1 on (-1, 0] (grid_sample's right-open bilinear derivative)."""
    pos = ((delta > 0.0) & (delta <= 1.0)).astype(jnp.float32)
    neg = ((delta > -1.0) & (delta <= 0.0)).astype(jnp.float32)
    return pos - neg


def _sel_matrix(d_head: int, h: int) -> jnp.ndarray:
    """(D, D*H) selection matrix: row d sums the d-th y-block."""
    dh = d_head * h
    rd = jax.lax.broadcasted_iota(jnp.int32, (d_head, dh), 0)
    rk = jax.lax.broadcasted_iota(jnp.int32, (d_head, dh), 1) // h
    return (rd == rk).astype(jnp.float32)


def _fwd_kernel(px_ref, py_ref, aw_ref, v_ref, out_ref, *,
                m_heads: int, points: int, d_head: int, h: int, wp: int):
    dh = d_head * h
    tq = px_ref.shape[-1]
    sel = _sel_matrix(d_head, h)
    xi = jax.lax.broadcasted_iota(jnp.int32, (wp, tq), 0).astype(
        jnp.float32)
    yi = (jax.lax.broadcasted_iota(jnp.int32, (dh, tq), 0) % h).astype(
        jnp.float32)

    for m in range(m_heads):
        vm = v_ref[0, m * dh:(m + 1) * dh, :].astype(jnp.float32)
        acc = jnp.zeros((dh, tq), jnp.float32)
        for p in range(points):
            row = m * points + p
            px = px_ref[0, row:row + 1, :].astype(jnp.float32)  # (1, TQ)
            py = py_ref[0, row:row + 1, :].astype(jnp.float32)
            aw = aw_ref[0, row:row + 1, :].astype(jnp.float32)
            wx = _hat(xi - px)                                  # (WP, TQ)
            tmp = jax.lax.dot_general(
                vm, wx, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # (DH, TQ)
            wy = _hat(yi - py)                                  # (DH, TQ)
            acc = acc + (aw * wy) * tmp
        out_ref[0, m * d_head:(m + 1) * d_head, :] = jax.lax.dot_general(
            sel, acc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (D, TQ)


def _bwd_kernel(px_ref, py_ref, aw_ref, v_ref, g_ref,
                dpx_ref, dpy_ref, daw_ref, dv_ref, *,
                m_heads: int, points: int, d_head: int, h: int, wp: int):
    dh = d_head * h
    tq = px_ref.shape[-1]
    sel = _sel_matrix(d_head, h)
    xi = jax.lax.broadcasted_iota(jnp.int32, (wp, tq), 0).astype(
        jnp.float32)
    yi = (jax.lax.broadcasted_iota(jnp.int32, (dh, tq), 0) % h).astype(
        jnp.float32)
    t = pl.program_id(1)

    for m in range(m_heads):
        vm = v_ref[0, m * dh:(m + 1) * dh, :].astype(jnp.float32)
        gm = g_ref[0, m * d_head:(m + 1) * d_head, :].astype(jnp.float32)
        # Broadcast each channel's cotangent over its y-block: sel^T @ gm.
        gmh = jax.lax.dot_general(
            sel, gm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (DH, TQ)
        dvm = jnp.zeros((dh, wp), jnp.float32)
        for p in range(points):
            row = m * points + p
            px = px_ref[0, row:row + 1, :].astype(jnp.float32)
            py = py_ref[0, row:row + 1, :].astype(jnp.float32)
            aw = aw_ref[0, row:row + 1, :].astype(jnp.float32)
            wx = _hat(xi - px)                                  # (WP, TQ)
            wy = _hat(yi - py)                                  # (DH, TQ)
            tmp = jax.lax.dot_general(
                vm, wx, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # (DH, TQ)
            gw = gmh * wy                                       # (DH, TQ)
            # attention-weight grad: <G, sample> per query
            daw_ref[0, row:row + 1, :] = jnp.sum(
                gw * tmp, axis=0, keepdims=True)
            # x-location grad via the corner-difference window
            tmpc = jax.lax.dot_general(
                vm, _corner(xi - px), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # (DH, TQ)
            dpx_ref[0, row:row + 1, :] = aw * jnp.sum(
                gw * tmpc, axis=0, keepdims=True)
            # y-location grad: corner window on the y side
            dpy_ref[0, row:row + 1, :] = aw * jnp.sum(
                (gmh * _corner(yi - py)) * tmp, axis=0, keepdims=True)
            # value grad: (DH, TQ) x (TQ, WP) matmul, accumulated over p
            dvm = dvm + jax.lax.dot_general(
                aw * gw, wx, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # (DH, WP)

        @pl.when(t == 0)
        def _():
            dv_ref[0, m * dh:(m + 1) * dh, :] = dvm

        @pl.when(t != 0)
        def _():
            dv_ref[0, m * dh:(m + 1) * dh, :] = (
                dv_ref[0, m * dh:(m + 1) * dh, :] + dvm)


def _level_fwd(px, py, aw, v, *, m_heads, points, d_head, h, wp,
               interpret):
    b, mp, npad = px.shape
    mdh = v.shape[1]
    grid = (b, npad // _LANE)
    kernel = functools.partial(_fwd_kernel, m_heads=m_heads,
                               points=points, d_head=d_head, h=h, wp=wp)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mdh, wp), lambda bi, ti: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m_heads * d_head, _LANE),
                               lambda bi, ti: (bi, 0, ti)),
        out_shape=jax.ShapeDtypeStruct((b, m_heads * d_head, npad),
                                       jnp.float32),
        interpret=interpret,
        name=KERNEL_NAMES["msda_fwd"],
    )(px, py, aw, v)


def _level_bwd(px, py, aw, v, g, *, m_heads, points, d_head, h, wp,
               interpret):
    b, mp, npad = px.shape
    mdh = v.shape[1]
    grid = (b, npad // _LANE)
    kernel = functools.partial(_bwd_kernel, m_heads=m_heads,
                               points=points, d_head=d_head, h=h, wp=wp)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mdh, wp), lambda bi, ti: (bi, 0, 0)),
            pl.BlockSpec((1, m_heads * d_head, _LANE),
                         lambda bi, ti: (bi, 0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mp, _LANE), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, mdh, wp), lambda bi, ti: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, mp, npad), jnp.float32),
            jax.ShapeDtypeStruct((b, mp, npad), jnp.float32),
            jax.ShapeDtypeStruct((b, mp, npad), jnp.float32),
            jax.ShapeDtypeStruct((b, mdh, wp), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES["msda_bwd"],
    )(px, py, aw, v, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _msda_level(px, py, aw, v, m_heads, points, d_head, h, wp, interpret):
    return _level_fwd(px, py, aw, v, m_heads=m_heads, points=points,
                      d_head=d_head, h=h, wp=wp, interpret=interpret)


def _msda_level_fwd(px, py, aw, v, m_heads, points, d_head, h, wp,
                    interpret):
    out = _msda_level(px, py, aw, v, m_heads, points, d_head, h, wp,
                      interpret)
    return out, (px, py, aw, v)


def _msda_level_bwd(m_heads, points, d_head, h, wp, interpret, res, g):
    px, py, aw, v = res
    dpx, dpy, daw, dv = _level_bwd(
        px, py, aw, v, g.astype(jnp.float32), m_heads=m_heads,
        points=points, d_head=d_head, h=h, wp=wp, interpret=interpret)
    return (dpx.astype(px.dtype), dpy.astype(py.dtype),
            daw.astype(aw.dtype), dv.astype(v.dtype))


_msda_level.defvjp(_msda_level_fwd, _msda_level_bwd)

# VMEM budget for the resident per-level value block (plus working set).
_VMEM_VALUE_BYTES = 10 * 2 ** 20


def pallas_eligible(value_shape, spatial_shapes) -> bool:
    """Whether the kernel's layout assumptions hold for these shapes:
    every level's ``M*D*H x Wp`` block must fit the VMEM budget and the
    row count must be sublane-aligned."""
    _, _, m, d = value_shape
    for h, w in spatial_shapes:
        wp = _round_up(w, 8)
        if (d * h) % 8 != 0:
            return False
        if m * d * h * wp * 4 > _VMEM_VALUE_BYTES:
            return False
    return True


def ms_deform_attn_pallas(value: jnp.ndarray,
                          spatial_shapes: Sequence[Tuple[int, int]],
                          sampling_locations: jnp.ndarray,
                          attention_weights: jnp.ndarray,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in Pallas replacement for :func:`raft_tpu.ops.msda.ms_deform_attn`.

    Args/returns identical to the jnp core: ``value (B, S, M, D)``,
    ``sampling_locations (B, Lq, M, L, P, 2)`` normalized to [0, 1],
    ``attention_weights (B, Lq, M, L, P)`` → ``(B, Lq, M*D)``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, M, D = value.shape
    _, Lq, _, L, P, _ = sampling_locations.shape
    assert L == len(spatial_shapes)
    assert S == sum(h * w for h, w in spatial_shapes)

    npad = _round_up(Lq, _LANE)
    out = jnp.zeros((B, M * D, npad), jnp.float32)
    start = 0
    for lvl, (H, W) in enumerate(spatial_shapes):
        wp = _round_up(W, 8)
        v = value[:, start:start + H * W].astype(jnp.float32)
        start += H * W
        # (B, HW, M, D) → (B, M, D, H, W) → (B, M*D*H, Wp); row index
        # m*D*H + d*H + y, x on lanes — the kernel's m-major layout.
        v = v.reshape(B, H, W, M, D).transpose(0, 3, 4, 1, 2)
        v = v.reshape(B, M * D * H, W)
        v = jnp.pad(v, ((0, 0), (0, 0), (0, wp - W)))

        loc = sampling_locations[:, :, :, lvl].astype(jnp.float32)
        # normalized → pixel (align_corners=False): u*W - 0.5
        px = loc[..., 0] * W - 0.5                       # (B, Lq, M, P)
        py = loc[..., 1] * H - 0.5
        aw = attention_weights[:, :, :, lvl].astype(jnp.float32)
        # (B, Lq, M, P) → (B, M*P, Lq_pad); padded queries sample far
        # outside every level (zero hat weight) with zero attention.
        def to_rows(x, fill):
            x = x.transpose(0, 2, 3, 1).reshape(B, M * P, Lq)
            return jnp.pad(x, ((0, 0), (0, 0), (0, npad - Lq)),
                           constant_values=fill)
        px, py, aw = to_rows(px, -2.0), to_rows(py, -2.0), to_rows(aw, 0.0)

        out = out + _msda_level(px, py, aw, v, M, P, D, H, wp, interpret)

    out = jnp.swapaxes(out, 1, 2)[:, :Lq]                # (B, Lq, M*D)
    # The jnp core preserves the caller's value dtype; match it so the
    # auto dispatch can't flip output dtype with query count.
    return out.astype(value.dtype)
