"""Whole-model sequence(spatial)-parallel execution.

Two composable mechanisms cover the long-context axis (image resolution —
SURVEY.md §5 "long-context equivalent"):

* :mod:`raft_tpu.parallel.ring_corr` — explicit ring correlation via
  ``shard_map`` + ``ppermute`` (memory-bounded, ring-attention pattern).
* This module — *compiler-partitioned* spatial parallelism: annotate the
  image inputs as sharded over rows (``spatial`` mesh axis) and jit the
  unmodified model; XLA's SPMD partitioner inserts the halo exchanges for
  every convolution and the collectives for the correlation einsums. This
  is the "pick a mesh, annotate shardings, let XLA insert collectives"
  recipe — no model surgery, works for the full RAFT forward including
  encoders, scan, and convex upsampling.

Both shard rows of the image; they interoperate (same mesh, same specs).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS

_LOG = logging.getLogger(__name__)

# Trace-time spatial-mesh context (round 5, VERDICT r4 #2): XLA's SPMD
# partitioner cannot split a Pallas custom call, so under compiler-
# partitioned spatial execution the on-demand correlation kernel needs
# an explicit shard_map wrapper — but the model is jitted UNMODIFIED
# and has no mesh argument. The spatial entry points (spatial_jit, the
# mesh arm of make_train_step) set this context around tracing;
# models.corr.alternate_lookup reads it and, when set, runs the fused
# kernel per-shard: queries/coords/output row-sharded, pooled target
# pyramid replicated (XLA inserts ONE all-gather, loop-invariant to
# the refinement scan; its transpose is the correct cross-shard psum
# for the fmap2 gradient). Exact for arbitrary flow magnitude — unlike
# a halo exchange, whose correctness would depend on flow staying
# within the halo.
_SPATIAL_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "spatial_kernel_mesh", default=None)


@contextlib.contextmanager
def spatial_kernel_mesh(mesh: Optional[Mesh]):
    """Declare (at trace time) that model code runs spatially sharded
    over ``mesh`` — lets mesh-less model internals (the correlation
    engine) wrap their Pallas calls in shard_map."""
    token = _SPATIAL_KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _SPATIAL_KERNEL_MESH.reset(token)


def current_spatial_kernel_mesh() -> Optional[Mesh]:
    return _SPATIAL_KERNEL_MESH.get()


def keeps_xla_under_partitioning(flag: str, mode: str) -> bool:
    """Static dispatch rule for the Mosaic kernels that carry no
    ``shard_map`` wrapper (the scan-body kernels: GRU, motion, fused
    step). Under a kernel mesh of more than one device the program is
    partitioned by GSPMD, which refuses them ("Mosaic kernels cannot be
    automatically partitioned"); the correlation kernel has its wrapper
    (``models.corr._sharded_fused_lookup``), these do not yet. Called on
    a TPU backend at trace time: returns True when the caller must keep
    its XLA path (said once per traced program, at WARNING); a forced
    ``<flag>=1`` raises instead of degrading."""
    mesh = current_spatial_kernel_mesh()
    if mesh is None or mesh.size == 1:
        return False
    if mode == "1":
        raise ValueError(
            f"{flag}=1 but the model is traced over a {dict(mesh.shape)} "
            f"mesh: this kernel has no shard_map wrapper and GSPMD cannot "
            f"partition a Mosaic kernel; use auto (XLA path) on a mesh")
    _LOG.warning(
        "%s=auto: keeping the XLA path — the model is traced over a %s "
        "mesh and this kernel has no shard_map wrapper (GSPMD cannot "
        "partition a Mosaic kernel)", flag, dict(mesh.shape))
    return True


def image_spec(shard_batch: bool = True) -> P:
    """(B, H, W, C) images: batch over ``data``, rows over ``spatial``."""
    return P(DATA_AXIS if shard_batch else None, SPATIAL_AXIS)


def spatial_jit(apply_fn: Callable, mesh: Mesh,
                shard_batch: bool = True,
                donate: bool = False,
                warm_init: bool = False) -> Callable:
    """Jit ``apply_fn(variables, image1, image2)`` with both images
    sharded over (data, spatial) and params replicated.

    The returned callable runs the full model spatially partitioned: at
    Sintel/KITTI resolution each device holds ``1/d`` of every activation
    and of the (HW)²-sized correlation volume. Outputs are produced with
    the same (batch, rows) sharding; ``jax.device_get`` assembles them.

    ``apply_fn`` must be positional-only in (variables, image1, image2) —
    ``jax.jit`` with ``in_shardings`` rejects kwargs, so bind options like
    ``test_mode`` into ``apply_fn`` first (``functools.partial`` /
    closure).

    ``donate=True`` donates the two image buffers (argnums 1, 2) to the
    executable — the serving steady state re-stacks fresh host arrays
    every batch, so the device copies are dead after dispatch; composes
    with sharding exactly like the plain-jit families.

    ``warm_init=True`` selects the warm-start signature
    ``apply_fn(variables, image1, image2, flow_init)``: the low-res init
    flow (B, H/8, W/8, 2) gets its OWN row-sharding spec — the same
    (batch, rows) layout as the images, legal because the caller pads
    image rows to a multiple of ``spatial_shards * 8`` so the /8 feature
    rows divide the spatial axis too. flow_init is never donated (same
    policy as the unsharded warm family: it is the caller's propagated
    state, not a dead buffer).
    """
    ispec = NamedSharding(mesh, image_spec(shard_batch))
    rep = NamedSharding(mesh, P())

    if warm_init:
        def traced_warm(variables, image1, image2, flow_init):
            with spatial_kernel_mesh(mesh):
                return apply_fn(variables, image1, image2, flow_init)

        return jax.jit(
            traced_warm,
            in_shardings=(rep, ispec, ispec, ispec),
            donate_argnums=(1, 2) if donate else (),
        )

    def traced(variables, image1, image2):
        # context active during TRACING (the body runs inside jit), so
        # the correlation engine can see the mesh — see
        # spatial_kernel_mesh above
        with spatial_kernel_mesh(mesh):
            return apply_fn(variables, image1, image2)

    return jax.jit(
        traced,
        in_shardings=(rep, ispec, ispec),
        donate_argnums=(1, 2) if donate else (),
    )
