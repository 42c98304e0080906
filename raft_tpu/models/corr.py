"""Correlation volumes: all-pairs (materialized) and on-demand (windowed).

Two regimes, matching the reference's operator boundary:

* ``CorrBlock`` — materialize the 4D all-pairs volume in one MXU einsum and
  avg-pool it into a pyramid, then answer windowed lookups by bilinear
  sampling (reference ``core/corr.py:12-61``; canonical ``num_levels=4``
  restored — the fork's drifted default was 2).
* ``AlternateCorrBlock`` — never materialize the volume: recompute windowed
  correlations around the current flow estimate on demand, O(HW·(2r+1)²·L)
  memory (the ``alt_cuda_corr`` CUDA extension's role, reference
  ``core/corr.py:64-92`` + ``alt_cuda_corr/correlation_kernel.cu:19-119``).
  Backed by a fused Pallas gather-dot kernel on TPU with a jnp fallback;
  both satisfy the contract ``AlternateCorrBlock(...) == CorrBlock(...)``
  bit-for-bit in exact arithmetic, which the tests assert.

Window-ordering note (weight compatibility): the reference builds its delta
grid with ``meshgrid(dy, dx)`` and adds it to (x, y)-ordered centroids
(original RAFT ``corr.py``), so window position (i, j) samples offset
``(x + off_i, y + off_j)`` — the *first* window axis moves x. We replicate
that exactly; converted torch weights then consume identical channel order.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from raft_tpu.ops.sampling import (avg_pool2x2, bilinear_sampler,
                                   corr_precision,
                                   windowed_bilinear_matmul)


def all_pairs_correlation(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                          scale: bool = True) -> jnp.ndarray:
    """(B,H,W,C) x (B,H,W,C) → (B,H,W,H,W) correlation volume.

    One batched matmul on the MXU (reference ``core/corr.py:53-61``).
    Computed in float32 regardless of input dtype — the volume is the
    numerically sensitive object (mirrors the reference's autocast-exempt
    corr, ``core/raft.py:100-103``).
    """
    B, H, W, C = fmap1.shape
    a = fmap1.reshape(B, H * W, C).astype(jnp.float32)
    b = fmap2.reshape(B, H * W, C).astype(jnp.float32)
    corr = jnp.einsum("bnc,bmc->bnm", a, b,
                      preferred_element_type=jnp.float32,
                      precision=corr_precision())
    if scale:
        corr = corr / jnp.sqrt(jnp.float32(C))
    return corr.reshape(B, H, W, H, W)


def _window_delta(radius: int) -> jnp.ndarray:
    """(2r+1, 2r+1, 2) offsets; first axis moves x (see module docstring)."""
    off = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    ox, oy = jnp.meshgrid(off, off, indexing="ij")
    return jnp.stack([ox, oy], axis=-1)


def build_corr_pyramid(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                       num_levels: int = 4, scale: bool = True,
                       storage_dtype=jnp.float32):
    """All-pairs volume → avg-pooled pyramid, each level
    ``(B*H*W, H/2^l, W/2^l)`` (reference ``core/corr.py:18-27``).

    Levels are 3D — a trailing singleton channel would be padded to a full
    128-lane tile by TPU layout, inflating HBM footprint and every read.

    ``storage_dtype``: dtype the levels are *stored* in between refinement
    iterations (see ``RAFTConfig.corr_dtype``). The matmul and the pooling
    chain always run in float32; bfloat16 storage halves the HBM footprint
    and read traffic of the framework's dominant memory object.
    """
    B, H, W, _ = fmap1.shape
    corr = all_pairs_correlation(fmap1, fmap2, scale=scale)
    # Cast level 0 BEFORE pooling so the float32 volume dies at the cast —
    # pooling from the float32 original would keep both copies live in HBM.
    # Each pool still accumulates in float32.
    corr = corr.reshape(B * H * W, H, W).astype(storage_dtype)
    pyramid = [corr]
    for _ in range(num_levels - 1):
        corr = avg_pool2x2(corr.astype(jnp.float32)).astype(storage_dtype)
        pyramid.append(corr)
    return tuple(pyramid)


def pyramid_lookup(pyramid, coords: jnp.ndarray, radius: int,
                   rescale: bool = True) -> jnp.ndarray:
    """Windowed bilinear lookup into a materialized pyramid.

    ``coords``: (B, H, W, 2) pixel (x, y); per level the centroid is scaled
    by ``1/2^level`` (canonical RAFT). ``rescale=False`` reproduces the fork
    drift that dropped this rescale (reference ``core/corr.py:38-42``) —
    the semantics the sparse-keypoint ("ours") family was trained with.
    Returns (B, H, W, L*(2r+1)^2).

    TPU note: the window sample is expressed as two separable batched
    matmuls (``windowed_bilinear_matmul``) rather than gathers — gathers of
    scalar slices cost a full (8,128) HBM tile each on TPU, which measured
    ~80 GB of traffic per refinement iteration at Sintel resolution; the
    matmul form reads each pyramid level exactly once per lookup.
    """
    B, H, W, _ = coords.shape
    flat = coords.reshape(B * H * W, 2)
    out = []
    for lvl, corr in enumerate(pyramid):
        centroid = flat / (2 ** lvl) if rescale else flat
        sampled = windowed_bilinear_matmul(
            corr, centroid[:, 0], centroid[:, 1], radius)
        out.append(sampled.reshape(B, H, W, -1))
    return jnp.concatenate(out, axis=-1)


class CorrBlock:
    """Materialized all-pairs correlation pyramid with windowed lookup."""

    def __init__(self, fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                 num_levels: int = 4, radius: int = 4, scale: bool = True,
                 rescale: bool = True, storage_dtype=jnp.float32):
        self.radius = radius
        self.rescale = rescale
        self.pyramid = build_corr_pyramid(fmap1, fmap2, num_levels, scale,
                                          storage_dtype)

    def __call__(self, coords: jnp.ndarray) -> jnp.ndarray:
        return pyramid_lookup(self.pyramid, coords, self.radius,
                              self.rescale)


def windowed_correlation(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                         coords: jnp.ndarray, radius: int,
                         scale: bool = True) -> jnp.ndarray:
    """On-demand windowed correlation (jnp reference implementation).

    For each query pixel q, correlate ``fmap1[q]`` against bilinear samples
    of ``fmap2`` in a (2r+1)^2 window around ``coords[q]``. Linearity of the
    dot product makes this exactly equal to bilinearly sampling the
    materialized volume (what ``alt_cuda_corr``'s bilinear-scatter kernel
    computes, reference ``correlation_kernel.cu:92-114``).

    Args:
      fmap1: (B, H, W, C) query features (full resolution).
      fmap2: (B, H2, W2, C) target features (this pyramid level).
      coords: (B, H, W, 2) pixel coords *at the fmap2 level's scale*.
    Returns:
      (B, H, W, (2r+1)^2) correlation features.
    """
    B, H, W, C = fmap1.shape
    win = 2 * radius + 1
    delta = _window_delta(radius).reshape(1, 1, 1, win, win, 2)
    pts = coords[:, :, :, None, None, :] + delta          # (B,H,W,w,w,2)
    pts = pts.reshape(B, H, W, win * win, 2)
    # Sample fmap2 at every window point: (B,H,W,w*w,C)
    samples = bilinear_sampler(fmap2.astype(jnp.float32),
                               pts.reshape(B, H * W * win * win, 2))
    samples = samples.reshape(B, H, W, win * win, C)
    corr = jnp.einsum("bhwc,bhwkc->bhwk", fmap1.astype(jnp.float32),
                      samples, preferred_element_type=jnp.float32)
    if scale:
        corr = corr / jnp.sqrt(jnp.float32(C))
    return corr


def build_feature_pyramid(fmap2: jnp.ndarray, num_levels: int):
    """Pool target features for on-demand correlation
    (reference ``core/corr.py:69-73``)."""
    pyramid2 = [fmap2]
    for _ in range(num_levels - 1):
        pyramid2.append(avg_pool2x2(pyramid2[-1]))
    return tuple(pyramid2)


def _lookup_route(fmap1, pyramid2, radius: int, backend: str,
                  differentiable: bool):
    """Which engine a lookup over these operands runs: ``("pallas",
    None)`` (the fused kernel), ``("sharded", mesh)`` (the kernel under
    ``shard_map``) or ``("jnp", None)``; decided at trace time from
    shapes, the backend and the active mesh (see ``alternate_lookup``)."""
    if backend == "auto":
        # Experiment hook (e.g. the bf16-backward training A/B, which
        # must route CPU training through the kernel's interpret mode):
        # RAFT_CORR_BACKEND=jnp|pallas overrides the auto dispatch.
        backend = os.environ.get("RAFT_CORR_BACKEND", "auto")
    if backend not in ("auto", "jnp", "pallas"):
        raise ValueError(f"unknown correlation backend {backend!r} "
                         f"(want 'auto', 'jnp' or 'pallas')")
    from raft_tpu.ops.corr_pallas import fused_eligible
    shapes = [f2.shape[1:3] for f2 in pyramid2]
    channels = fmap1.shape[-1]
    dtype_bytes = jnp.dtype(pyramid2[0].dtype).itemsize
    eligible = fused_eligible(shapes, channels, dtype_bytes, radius,
                              differentiable=differentiable)
    if backend == "pallas" and not eligible:
        raise ValueError(
            "backend='pallas' but the pooled levels don't fit the "
            f"kernel's VMEM-resident layout (levels {list(shapes)}, "
            f"C={channels}); see corr_pallas.fused_eligible")
    use_pallas = backend == "pallas" or (
        backend == "auto" and eligible
        and jax.default_backend() == "tpu")
    if not use_pallas:
        return "jnp", None
    from raft_tpu.parallel.spatial import current_spatial_kernel_mesh
    mesh = current_spatial_kernel_mesh()
    if mesh is not None:
        from raft_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
        n_sp = mesh.shape.get(SPATIAL_AXIS, 1)
        n_dt = mesh.shape.get(DATA_AXIS, 1)
        if n_sp > 1 or n_dt > 1:
            if fmap1.shape[1] % n_sp or fmap1.shape[0] % n_dt:
                # The sharded composition needs rows % spatial and
                # batch % data to divide; without it the ONLY safe
                # engine under an active mesh is the jnp path (the
                # kernel's custom call is not auto-partitionable
                # under SPMD — lowering it unsharded here would
                # fail, not replicate). auto falls through to jnp;
                # an explicit pallas request gets a clear error
                # instead of an opaque lowering failure.
                if backend == "pallas":
                    raise ValueError(
                        "backend='pallas' under a spatial/data mesh "
                        f"({SPATIAL_AXIS}={n_sp}, {DATA_AXIS}="
                        f"{n_dt}) needs feature rows "
                        f"({fmap1.shape[1]}) divisible by the "
                        "spatial axis and batch "
                        f"({fmap1.shape[0]}) by the data axis; "
                        "use backend='auto'/'jnp' or adjust the "
                        "mesh")
                return "jnp", None
            return "sharded", mesh
    return "pallas", None


def alternate_operands(fmap1: jnp.ndarray, pyramid2, radius: int,
                       backend: str = "auto",
                       differentiable: bool = False,
                       rescale: bool = True):
    """``(fmap1, pyramid2)`` as every refinement iteration's
    ``alternate_lookup`` reads them, built once a pair: on the unsharded
    Pallas route ``(None, corr_pallas.lookup_operands(...))``, the
    kernel's own layout, so that no iteration pads or lays out the
    features again; else the two as they are."""
    route, _ = _lookup_route(fmap1, pyramid2, radius, backend,
                             differentiable)
    if route != "pallas":
        return fmap1, pyramid2
    from raft_tpu.ops.corr_pallas import lookup_operands
    return None, lookup_operands(fmap1, pyramid2, radius, rescale)


def alternate_lookup(fmap1: jnp.ndarray, pyramid2, coords: jnp.ndarray,
                     radius: int, scale: bool = True,
                     backend: str = "auto",
                     mxu_dtype: str = "float32",
                     differentiable: bool = False,
                     rescale: bool = True,
                     out_dtype=jnp.float32) -> jnp.ndarray:
    """On-demand windowed lookup over a pooled feature pyramid; numerically
    identical to ``pyramid_lookup`` over the materialized volume.

    ``auto`` picks the Pallas kernel only on TPU — off-TPU the kernel would
    run through the (slow) Pallas interpreter, so the vectorized jnp
    reference is the right default there. On the Pallas path all pyramid
    levels run in ONE fused kernel launch. The backends differ in one
    gradient contract: the Pallas kernel treats coordinates as
    non-differentiable (zero gradient — the reference extension's behavior,
    ``alt_cuda_corr/correlation_kernel.cu:307``), while the jnp path
    propagates bilinear-sampler coordinate gradients. RAFT stop-gradients
    coords before lookup, so the model is backend-agnostic.

    ``mxu_dtype``: operand dtype for the Pallas kernel's correlation
    matmuls (f32 accumulation; see ``RAFTConfig.corr_mxu_dtype``).
    Ignored by the jnp path, which always computes in float32.

    ``differentiable``: declare that this call may be differentiated
    (training). The kernel's backward keeps more VMEM resident than its
    forward (f32 df2 blocks + cotangent scratch), so the auto-dispatch
    eligibility gate budgets for the backward too instead of admitting
    a shape that compiles forward but fails VMEM allocation under grad.

    ``(fmap1, pyramid2)`` may also be what ``alternate_operands`` built
    once a pair: ``(None, LookupOperands)`` runs the kernel over them.
    """
    from raft_tpu.ops import corr_pallas
    if isinstance(pyramid2, corr_pallas.LookupOperands):
        # built by ``alternate_operands`` on the unsharded Pallas route
        return corr_pallas.windowed_lookup(
            pyramid2, coords, radius, scale=scale, mxu_dtype=mxu_dtype,
            rescale=rescale, out_dtype=out_dtype)
    route, mesh = _lookup_route(fmap1, pyramid2, radius, backend,
                                differentiable)
    if route == "sharded":
        return _sharded_fused_lookup(
            fmap1, tuple(pyramid2), coords, mesh, radius,
            scale, mxu_dtype, rescale, out_dtype)
    if route == "pallas":
        # out_dtype emitted from inside the kernel — bit-identical to a
        # post-hoc astype, but skips the convert+copy XLA would place at
        # the custom-call boundary (~2% of the b64 headline step).
        return corr_pallas.windowed_correlation_pallas_fused(
            fmap1, tuple(pyramid2), coords, radius, scale=scale,
            mxu_dtype=mxu_dtype, rescale=rescale, out_dtype=out_dtype)
    win = 2 * radius + 1
    out = []
    for lvl, f2 in enumerate(pyramid2):
        if f2.shape[1] == 0 or f2.shape[2] == 0:
            # Degenerate pooled level (a 1-row/col level pools to empty
            # under VALID 2x2): every bilinear sample is out of range →
            # exactly zero windows, matching the materialized pyramid's
            # empty-volume-level behavior (its matmul form contracts
            # over the empty axis). The gather-based sampler cannot
            # index an empty array, so short-circuit.
            b, h, w = fmap1.shape[0], coords.shape[1], coords.shape[2]
            out.append(jnp.zeros((b, h, w, win * win), jnp.float32))
            continue
        lvl_coords = coords / (2 ** lvl) if rescale else coords
        out.append(windowed_correlation(fmap1, f2, lvl_coords,
                                        radius, scale))
    return jnp.concatenate(out, axis=-1).astype(out_dtype)


def _sharded_fused_lookup(fmap1, pyramid2, coords, mesh, radius, scale,
                          mxu_dtype, rescale, out_dtype):
    """shard_map wrapper composing the fused kernel with spatial
    sharding (round 5, VERDICT r4 #2).

    Queries, coords and output are row-sharded (``spatial`` axis);
    the pooled target pyramid is declared replicated, so XLA inserts
    ONE all-gather per forward — loop-invariant to the refinement
    scan, and its autodiff transpose is the cross-shard psum the
    ``fmap2`` gradient needs. Each shard then runs a completely
    self-contained kernel call: coordinates are global level-0 pixels
    and each shard stages the FULL target levels, so arbitrary flow
    magnitudes stay exact (a halo exchange would not be — the memory
    regime this serves is the reference's
    ``alt_cuda_corr/correlation_kernel.cu:19-119``).

    The VMEM envelope per shard equals the unsharded kernel's
    (``fused_eligible`` gates on full levels either way); what spatial
    sharding buys is the 1/d split of every *activation* and of the
    query-side work. Returns None when the sharding doesn't divide the
    operands (caller falls back to the unsharded call, which XLA then
    runs replicated)."""
    from jax import shard_map

    from raft_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS

    n_sp = mesh.shape.get(SPATIAL_AXIS, 1)
    n_dt = mesh.shape.get(DATA_AXIS, 1)
    B, H = fmap1.shape[0], fmap1.shape[1]
    if H % max(n_sp, 1) or B % max(n_dt, 1):
        return None
    if n_sp <= 1 and n_dt <= 1:
        return None

    from jax.sharding import PartitionSpec as P

    qspec = P(DATA_AXIS, SPATIAL_AXIS, None, None)
    pspec = tuple(P(DATA_AXIS, None, None, None) for _ in pyramid2)

    def local(f1, pyr, c):
        from raft_tpu.ops.corr_pallas import (
            windowed_correlation_pallas_fused)
        return windowed_correlation_pallas_fused(
            f1, pyr, c, radius, scale=scale, mxu_dtype=mxu_dtype,
            rescale=rescale, out_dtype=out_dtype)

    return shard_map(local, mesh=mesh,
                     in_specs=(qspec, pspec, qspec),
                     out_specs=qspec, check_vma=False)(
        fmap1, pyramid2, coords)


def alternate_eval_eligible(cfg, image_hw,
                            differentiable: bool = False,
                            spatial_shards: int = 1,
                            batch: int = None,
                            data_shards: int = 1) -> bool:
    """Whether the fused on-demand kernel admits a canonical-RAFT run at
    this padded image size (stride-8 features, ``cfg.corr_levels`` pooled
    levels, bf16 features under the mixed-precision policy). Used by the
    ``corr_impl="auto"`` dispatch on both the eval path and (with
    ``differentiable=True``, which budgets the backward's VMEM) the
    training path — on-chip measurement made the on-demand kernel the
    preferred engine wherever it fits VMEM (BENCH r4: 93.7 vs 55.9
    pairs/s Sintel eval; train step +34%/+49% at chairs b4/b8,
    TPU_EXTRAS raft_train alt arms).

    ``spatial_shards > 1``: the sharded composition
    (``_sharded_fused_lookup``) additionally needs the feature rows
    divisible by the spatial axis so shard_map can split the query
    slab evenly; the VMEM envelope itself is unchanged (each shard
    stages the full pooled target levels).

    ``batch``/``data_shards``: the same divisibility story on the data
    axis — shard_map splits the batch over ``data_shards``, so a batch
    that doesn't divide makes the sharded composition unavailable and
    the dispatch must not pick the kernel (the custom call can't lower
    unsharded under an active mesh). Folded in here so
    ``corr_impl="auto"`` predicts exactly what the runtime dispatch in
    :func:`windowed_correlation_pyramid` will accept (ADVICE round 5).
    ``batch=None`` (unknown at choice time) skips the check."""
    from raft_tpu.ops.corr_pallas import fused_eligible
    h, w = image_hw
    h8, w8 = h // 8, w // 8
    if spatial_shards > 1 and h8 % spatial_shards:
        return False
    if (batch is not None and data_shards > 1
            and batch % data_shards):
        return False
    shapes = []
    for _ in range(cfg.corr_levels):
        # True pooled shapes, including degenerate 0-size levels (VALID
        # stride-2 pooling of a 1-row level) — fused_eligible rejects
        # those, so the dispatch prediction matches the runtime gate.
        shapes.append((h8, w8))
        h8, w8 = h8 // 2, w8 // 2
    dtype_bytes = 2 if cfg.mixed_precision else 4
    return fused_eligible(shapes, cfg.fnet_dim, dtype_bytes, cfg.radius,
                          differentiable=differentiable)


class AlternateCorrBlock:
    """Memory-efficient correlation: pool *features*, recompute windows on
    demand (reference ``core/corr.py:64-92``). ``backend='pallas'`` uses the
    fused TPU kernel; ``'jnp'`` the reference implementation."""

    def __init__(self, fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                 num_levels: int = 4, radius: int = 4, scale: bool = True,
                 backend: str = "auto", mxu_dtype: str = "float32",
                 differentiable: bool = False, rescale: bool = True,
                 out_dtype=jnp.float32):
        self.radius = radius
        self.scale = scale
        self.backend = backend
        self.mxu_dtype = mxu_dtype
        self.differentiable = differentiable
        self.rescale = rescale
        self.out_dtype = out_dtype
        self.fmap1 = fmap1
        self.pyramid2 = build_feature_pyramid(fmap2, num_levels)
        from raft_tpu.parallel.spatial import current_spatial_kernel_mesh
        mesh = current_spatial_kernel_mesh()
        if mesh is not None:
            # Hoist the pyramid's spatial replication OUT of the
            # refinement scan: the per-iteration lookup's shard_map
            # declares the pooled target levels replicated over the
            # spatial axis, and constraining them here (trace time,
            # before the scan) puts the ONE all-gather at pyramid build
            # instead of a gather per iteration inside the loop.
            from jax.sharding import NamedSharding, PartitionSpec as P

            from raft_tpu.parallel.mesh import DATA_AXIS
            rep = NamedSharding(mesh, P(DATA_AXIS, None, None, None))
            self.pyramid2 = tuple(
                jax.lax.with_sharding_constraint(f2, rep)
                for f2 in self.pyramid2)

    def __call__(self, coords: jnp.ndarray) -> jnp.ndarray:
        return alternate_lookup(self.fmap1, self.pyramid2, coords,
                                self.radius, self.scale, self.backend,
                                self.mxu_dtype, self.differentiable,
                                self.rescale, self.out_dtype)
