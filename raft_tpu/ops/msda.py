"""Multi-scale deformable attention sampling core.

TPU-native equivalent of the reference's ``MultiScaleDeformableAttention``
CUDA extension (reference ``core/ops/src/cuda/ms_deform_im2col_cuda.cuh:238``
forward kernel; pure-torch reference implementation
``core/ops/functions/ms_deform_attn_func.py:41-61``): per (query, head,
level, point), bilinearly sample the value map at a predicted normalized
location and accumulate with a predicted attention weight.

Design note (TPU-first): in the live "ours" model the query set is 100
keypoints × 8 heads × 6 levels × 4 points ≈ 19k samples per image — three
orders of magnitude smaller than the token grid. The op is
bandwidth-trivial; what matters is that the gathers vectorize and fuse under
XLA, so the core is expressed as one batched ``bilinear_sampler`` call per
level (static level loop) and a single weighted reduction. Dense-query
*encoder* layers (the ``ours_07`` encoder stacks,
``OursConfig.encoder_iterations``: every HW token is a query) are a
different regime — per-scalar gathers cost a full
HBM tile each there, so ``backend='auto'`` dispatches them to the
hat-matmul Pallas kernel (:mod:`raft_tpu.ops.msda_pallas`) on TPU.

Sampling convention matches ``F.grid_sample(align_corners=False,
padding_mode='zeros')``: normalized location ``u ∈ [0,1]`` maps to pixel
``u*W - 0.5`` (reference ``ms_deform_attn_func.py:48`` builds
``2*loc - 1`` grids for grid_sample).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.ops.sampling import bilinear_sampler


# Dense-query regimes (encoder stacks: every HW token is a query) switch
# to the Pallas kernel on TPU above this query count; below it the gather
# traffic is trivial and the jnp core fuses fine.
# RAFT_MSDA_MIN_QUERIES overrides the default so an operator can apply a
# crossover measured by scripts/tpu_extras_bench.py::msda_threshold
# (which itself monkeypatches this global per arm) without a code edit.
# Read ONCE at import — set it before importing raft_tpu; malformed
# values fall back to the default rather than poisoning every import.
#
# Default 128: set from the round-4 on-chip crossover sweep
# (TPU_EXTRAS.json ``msda_threshold``, v5e, 2640 value tokens): the
# Pallas kernel never lost at ANY measured query count — 9675us vs
# 9757us (jnp) already at Lq=128, widening to 9122 vs 12079 at
# Lq=2640 — so the threshold is the smallest measured point rather
# than the former unmeasured guess of 512. Below 128 sits only the
# sparse-decoder regime (~100 learned queries/level), where the gather
# path's advantage is architectural (tiny Lq, no dense structure) and
# untimed differences are in the noise.
import os as _os

try:
    _PALLAS_MIN_QUERIES = int(
        _os.environ.get("RAFT_MSDA_MIN_QUERIES", "128"))
except ValueError:
    _PALLAS_MIN_QUERIES = 128


def ms_deform_attn(value: jnp.ndarray,
                   spatial_shapes: Sequence[Tuple[int, int]],
                   sampling_locations: jnp.ndarray,
                   attention_weights: jnp.ndarray,
                   backend: str = "auto") -> jnp.ndarray:
    """Deformable attention sampling.

    Args:
      value: ``(B, S, M, D)`` flattened multi-level value maps,
        ``S = sum(H_l * W_l)``.
      spatial_shapes: static list of per-level ``(H, W)``.
      sampling_locations: ``(B, Lq, M, L, P, 2)`` normalized (x, y) in
        [0, 1].
      attention_weights: ``(B, Lq, M, L, P)``, softmaxed over ``L*P``.
      backend: ``jnp`` (vectorized gathers — right for sparse-query
        decoders), ``pallas`` (the hat-matmul TPU kernel,
        :mod:`raft_tpu.ops.msda_pallas` — right for dense-query encoder
        layers), or ``auto`` (pallas on TPU when the query set is dense
        and the shapes fit the kernel's VMEM layout).

    Returns:
      ``(B, Lq, M*D)``.
    """
    if backend not in ("jnp", "pallas", "auto"):
        raise ValueError(f"unknown MSDA backend {backend!r} "
                         "(expected 'jnp', 'pallas' or 'auto')")
    if backend != "jnp":
        from raft_tpu.ops import msda_pallas
        eligible = msda_pallas.pallas_eligible(value.shape,
                                               spatial_shapes)
        if backend == "pallas" and not eligible:
            raise ValueError(
                "backend='pallas' but the shapes don't fit the kernel's "
                f"VMEM-resident layout (value {value.shape}, levels "
                f"{list(spatial_shapes)}); see msda_pallas.pallas_eligible")
        auto_kernel = (backend == "auto" and eligible
                       and sampling_locations.shape[1] >= _PALLAS_MIN_QUERIES
                       and jax.default_backend() == "tpu")
        if auto_kernel:
            # The kernel has no shard_map wrapper: on a mesh (data-
            # parallel training) GSPMD would refuse it.
            from raft_tpu.parallel.spatial import \
                keeps_xla_under_partitioning
            auto_kernel = not keeps_xla_under_partitioning(
                "ms_deform_attn backend", "auto")
        if backend == "pallas" or auto_kernel:
            return msda_pallas.ms_deform_attn_pallas(
                value, spatial_shapes, sampling_locations,
                attention_weights)
    B, S, M, D = value.shape
    _, Lq, _, L, P, _ = sampling_locations.shape
    assert L == len(spatial_shapes)
    assert S == sum(h * w for h, w in spatial_shapes)

    start = 0
    sampled_levels = []
    for lvl, (H, W) in enumerate(spatial_shapes):
        v = value[:, start:start + H * W]                    # (B, HW, M, D)
        start += H * W
        # (B, HW, M, D) → (B*M, H, W, D)
        v = v.transpose(0, 2, 1, 3).reshape(B * M, H, W, D)
        loc = sampling_locations[:, :, :, lvl]               # (B, Lq, M, P, 2)
        px = loc[..., 0] * W - 0.5                           # align=False
        py = loc[..., 1] * H - 0.5
        coords = jnp.stack([px, py], axis=-1)
        coords = coords.transpose(0, 2, 1, 3, 4).reshape(B * M, Lq * P, 2)
        out = bilinear_sampler(v, coords)                    # (B*M, Lq*P, D)
        sampled_levels.append(out.reshape(B, M, Lq, P, D))

    # (B, M, Lq, L, P, D)
    sampled = jnp.stack(sampled_levels, axis=3)
    weights = attention_weights.transpose(0, 2, 1, 3, 4)     # (B, M, Lq, L, P)
    out = jnp.einsum("bmqlpd,bmqlp->bqmd", sampled, weights)
    return out.reshape(B, Lq, M * D)
