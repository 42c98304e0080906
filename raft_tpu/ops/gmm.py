"""Grouped matrix product over ragged groups of rows: the expert layer's
three products (``raft_expert_gmm``).

Rows arrive sorted by expert; ``group_sizes[e]`` says how many belong to
expert ``e`` of ALL the router's experts, while ``rhs`` holds the
weights of the ``G`` experts that live here, ``group_offset`` on. Row
``r`` of the result is ``lhs[r] @ rhs[e - group_offset]`` where expert
``e`` is held here, and zero elsewhere: an expert-parallel shard's part
of the layer, with no capacity and no dropped row.

On TPU this is the grouped matmul JAX ships for Pallas
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for
the gradient of ``lhs``, ``tgmm`` for the gradient of ``rhs``, behind
its own custom VJP), traced under ``jax.named_scope(KERNEL_NAMES[
"expert_gmm"])`` so that ``kernel_census`` finds all three in a
compiled step; its events in a device trace are named ``gmm.N`` and
``tgmm.N``. ``expert_gmm_reference`` is the jnp twin: one masked dense
product per held expert, differentiated by JAX.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.ops import vmem
from raft_tpu.ops.layout import KERNEL_NAMES

#: (rows, contraction, columns) of one grid step. 512 x 1024 x 1024 in
#: bfloat16 keeps the MXU fed from 7 MiB of VMEM (``tile_parts``).
DEFAULT_TILING = (512, 1024, 1024)


def tile_parts(tiling: Tuple[int, int, int], in_bytes: int,
               out_bytes: int) -> dict:
    """Named VMEM estimate of one launch: double-buffered ``lhs``,
    ``rhs`` and output tiles and the float32 accumulator."""
    tm, tk, tn = tiling
    return {"lhs_tiles": 2 * tm * tk * in_bytes,
            "rhs_tiles": 2 * tk * tn * in_bytes,
            "out_tiles": 2 * tm * tn * out_bytes,
            "accumulator": tm * tn * 4}


def fit_tiling(m: int, k: int, n: int,
               tiling: Tuple[int, int, int] = DEFAULT_TILING
               ) -> Optional[Tuple[int, int, int]]:
    """``tiling`` shrunk to the problem, or ``None`` where the kernel
    cannot tile it (rows not a multiple of the row tile, or a dimension
    that no 128-multiple divides)."""
    tm, tk, tn = tiling
    tm = min(tm, m)
    if m % tm or tm % 8:
        return None
    out = [tm]
    for dim, tile in ((k, tk), (n, tn)):
        tile = min(tile, dim)
        while tile >= 128 and dim % tile:
            tile -= 128
        if tile < 128 or dim % tile:
            return None
        out.append(tile)
    return tuple(out)


def expert_gmm_reference(lhs, rhs, group_sizes, group_offset: int = 0):
    """The jnp twin: for each held expert, the rows between its offsets
    times its matrix, float32 accumulation, the result in ``lhs``'s
    dtype."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
    for g in range(rhs.shape[0]):
        e = group_offset + g
        here = (rows >= starts[e]) & (rows < ends[e])
        out = out + jnp.where(
            here, jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32),
            0.0)
    return out.astype(lhs.dtype)


def expert_gmm(lhs, rhs, group_sizes, group_offset: int = 0, *,
               impl: Optional[str] = None,
               tiling: Tuple[int, int, int] = DEFAULT_TILING,
               interpret: Optional[bool] = None):
    """``lhs`` (rows, K) sorted by expert, ``rhs`` (G, K, N) of the held
    experts, ``group_sizes`` (all experts,) int32. ``impl`` ``"pallas"``
    / ``"xla"`` forces a path; by default the kernel runs on TPU where
    the shapes tile and the twin elsewhere."""
    fitted = fit_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2], tiling)
    if impl is None:
        impl = ("pallas" if jax.default_backend() == "tpu"
                and fitted is not None else "xla")
    if impl == "xla":
        return expert_gmm_reference(lhs, rhs, group_sizes, group_offset)
    if fitted is None:
        raise ValueError(
            f"expert_gmm: no tiling of {tiling} fits lhs {lhs.shape} x "
            f"rhs {rhs.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    vmem.preflight(
        tile_parts(fitted, lhs.dtype.itemsize, lhs.dtype.itemsize),
        f"expert_gmm tiling {fitted}")
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    with jax.named_scope(KERNEL_NAMES["expert_gmm"]):
        return megablox.gmm(
            lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, fitted,
            jnp.asarray(group_offset, jnp.int32), None, False, interpret)
