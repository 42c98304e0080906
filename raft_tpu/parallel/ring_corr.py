"""Ring / sequence-parallel all-pairs correlation.

The correlation volume is RAFT's attention matrix: ``(B, HW, HW)`` scores
between every pixel of image 1 (queries) and image 2 (targets)
(reference ``core/corr.py:53-61``). At high resolution it dominates memory
exactly like long-context attention — so it shards the same way:

* **queries** (image-1 pixels) are sharded over the ``spatial`` mesh axis
  (rows of the image: shard ``j`` owns rows ``[j*H/d, (j+1)*H/d)``);
* **targets** (image-2 features) rotate around the ring via
  ``lax.ppermute`` while each device accumulates its block of correlation
  columns — the ring-attention pattern. No device ever materializes more
  than ``(HW)²/d`` of the volume, and the feature chunks ride ICI
  neighbor-to-neighbor.

Downstream stages stay local: pyramid pooling reduces over *target* pixels
(each device holds its queries' full rows), and the windowed lookup reads
only the querying pixel's own row block. Only the final 8x upsampled flow
crosses shard boundaries, which XLA handles when unsharding the output.

Everything here runs inside ``shard_map`` over a
:func:`raft_tpu.parallel.mesh.make_mesh` mesh and is exercised on the
8-virtual-device CPU mesh in ``tests/test_ring_corr.py``.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.models.corr import pyramid_lookup
from raft_tpu.ops.sampling import avg_pool2x2
from raft_tpu.parallel.mesh import SPATIAL_AXIS


def _ring_volume(fmap1: jnp.ndarray, fmap2: jnp.ndarray, n_shards: int,
                 scale: bool, axis_name: str) -> jnp.ndarray:
    """shard_map body: (B, Hs, W, C) local shards → (B, Hs*W, H, W) local
    query rows of the full correlation volume. The query axis stays
    separate from batch so the *global* array (queries sharded over
    ``spatial`` on axis 1) is batch-major — shard-major flattening would
    interleave shards and batch elements for B > 1."""
    B, Hs, W, C = fmap1.shape
    q = fmap1.reshape(B, Hs * W, C).astype(jnp.float32)
    idx = jax.lax.axis_index(axis_name)

    perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    cur = fmap2
    blocks = []
    for _ in range(n_shards):
        t = cur.reshape(B, Hs * W, C).astype(jnp.float32)
        # (B, Q_loc, T_chunk) block of correlation columns
        blocks.append(jnp.einsum("bnc,bmc->bnm", q, t,
                                 preferred_element_type=jnp.float32))
        cur = jax.lax.ppermute(cur, axis_name, perm)
    # blocks[s] holds target shard (idx + s) % d; roll to absolute order
    stacked = jnp.stack(blocks, axis=0)          # (d, B, Q_loc, Hs*W)
    ordered = jnp.roll(stacked, shift=idx, axis=0)
    corr = ordered.reshape(n_shards, B, Hs * W, Hs, W)
    corr = corr.transpose(1, 2, 0, 3, 4).reshape(
        B, Hs * W, n_shards * Hs, W)
    if scale:
        corr = corr / jnp.sqrt(jnp.float32(C))
    return corr


def _ring_pyramid(fmap1, fmap2, n_shards, num_levels, scale, axis_name):
    corr = _ring_volume(fmap1, fmap2, n_shards, scale, axis_name)
    pyramid = [corr]
    for _ in range(num_levels - 1):
        pyramid.append(avg_pool2x2(pyramid[-1], spatial_axes=(2, 3)))
    return tuple(pyramid)


def ring_corr_pyramid(fmap1: jnp.ndarray, fmap2: jnp.ndarray, mesh: Mesh,
                      num_levels: int = 4, scale: bool = True):
    """Build the all-pairs correlation pyramid with queries sharded over
    the mesh's ``spatial`` axis and image-2 features ring-rotated.

    Args:
      fmap1, fmap2: (B, H, W, C); H must divide by the spatial axis size.
    Returns:
      Pyramid tuple; level l is (B, H*W, H/2^l, W/2^l) with the query
      axis (1) sharded over ``spatial`` — ``level.reshape(B*H*W, ...)``
      is the single-device ``build_corr_pyramid`` layout.
    """
    d = mesh.shape[SPATIAL_AXIS]
    body = functools.partial(_ring_pyramid, n_shards=d,
                             num_levels=num_levels, scale=scale,
                             axis_name=SPATIAL_AXIS)
    spec_in = P(None, SPATIAL_AXIS, None, None)
    spec_out = tuple(P(None, SPATIAL_AXIS) for _ in range(num_levels))
    return shard_map(body, mesh=mesh, in_specs=(spec_in, spec_in),
                     out_specs=spec_out)(fmap1, fmap2)


def ring_lookup(pyramid, coords: jnp.ndarray, radius: int, mesh: Mesh,
                rescale: bool = True) -> jnp.ndarray:
    """Windowed lookup into a query-sharded pyramid. ``coords`` is the
    full (B, H, W, 2) grid (absolute pixel coords, sharded or shardable on
    H); the lookup is embarrassingly parallel over queries."""
    def body(*args):
        pyr, c = args[:-1], args[-1]
        pyr = tuple(p.reshape((-1,) + p.shape[2:]) for p in pyr)
        return pyramid_lookup(pyr, c, radius, rescale)

    num_levels = len(pyramid)
    spec_pyr = tuple(P(None, SPATIAL_AXIS) for _ in range(num_levels))
    return shard_map(
        body, mesh=mesh,
        in_specs=spec_pyr + (P(None, SPATIAL_AXIS, None, None),),
        out_specs=P(None, SPATIAL_AXIS, None, None))(*pyramid, coords)


def sequence_parallel_specs(num_levels: int = 4
                            ) -> Tuple[P, Sequence[P]]:
    """The PartitionSpecs of the sequence-parallel correlation state:
    (fmap spec, per-level pyramid specs) — for callers composing these
    kernels into larger pjit programs."""
    return (P(None, SPATIAL_AXIS, None, None),
            tuple(P(None, SPATIAL_AXIS) for _ in range(num_levels)))
