"""Device-mesh construction and sharding helpers.

One logical mesh with two axes:

* ``data`` — data parallelism (the reference's only active strategy,
  ``nn.DataParallel`` at ``train.py:342``); batch dim sharded, params
  replicated, gradient all-reduce inserted by XLA over ICI.
* ``spatial`` — optional sharding of the spatial/query axis of the
  correlation volume for high-resolution inputs (the sequence-parallel
  analogue; SURVEY.md §5 "long-context equivalent").
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.families import family_of

DATA_AXIS = "data"
SPATIAL_AXIS = "spatial"

def make_mesh(n_data: Optional[int] = None, n_spatial: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a ``(data, spatial)`` mesh.

    Defaults to all visible devices on the data axis — the BASELINE.json
    data-parallel config ("v5e-8 pmap" equivalent). Device order follows
    ``jax.devices()`` so the data axis rides ICI within a slice.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        if len(devices) % n_spatial:
            raise ValueError(
                f"{len(devices)} devices not divisible by n_spatial={n_spatial}")
        n_data = len(devices) // n_spatial
    arr = np.asarray(devices[: n_data * n_spatial]).reshape(
        n_data, n_spatial)
    return Mesh(arr, (DATA_AXIS, SPATIAL_AXIS))


def validate_spatial_shards(spatial_shards: int, model_family: str,
                            image_height: Optional[int] = None) -> None:
    """Shared upfront validation for the ``spatial_shards`` options of
    train/evaluate: one place for the contract so wording and rules
    cannot drift.

    ``image_height`` (when known upfront, e.g. the training crop) must
    divide by the shard count — otherwise ``shard_batch`` silently falls
    back to data-only sharding and every mesh column redundantly
    computes full rows."""
    if spatial_shards < 1:
        raise ValueError(
            f"spatial_shards must be >= 1 (got {spatial_shards})")
    if spatial_shards == 1:
        return
    if not family_of(model_family).spatial_shards:
        raise ValueError(
            "spatial sharding supports the canonical RAFT family only "
            f"(got model_family={model_family!r})")
    n_dev = len(jax.devices())
    if n_dev < spatial_shards or n_dev % spatial_shards:
        raise ValueError(
            f"spatial_shards={spatial_shards} must divide the device "
            f"count ({n_dev})")
    if image_height is not None and image_height % spatial_shards:
        raise ValueError(
            f"image height {image_height} is not divisible by "
            f"spatial_shards={spatial_shards}; rows could not be "
            "sharded (pick a divisor of the padded height)")


def batch_spec() -> P:
    """PartitionSpec for batch-leading arrays: shard dim 0 over data."""
    return P(DATA_AXIS)


def shard_batch(batch, mesh: Mesh):
    """Device_put a host batch (pytree of arrays with leading batch dim):
    batch dim over ``data``; for spatial arrays (ndim >= 3: images, flows,
    valid masks) the row dim additionally shards over ``spatial``, so a 2-D
    mesh runs data x sequence parallel with XLA inserting halo exchanges
    and collectives."""
    def put(x):
        spec = (P(DATA_AXIS, SPATIAL_AXIS) if getattr(x, "ndim", 0) >= 3
                and x.shape[1] % mesh.shape[SPATIAL_AXIS] == 0
                else batch_spec())
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, batch)


def replicate(tree, mesh: Mesh):
    """Fully replicate a pytree (params / opt state) over the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)
