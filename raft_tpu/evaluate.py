"""Validation and leaderboard-submission harness.

Mirrors reference ``evaluate.py`` — Sintel/KITTI submission writers
(``:21-71``), FlyingChairs / Sintel / Sintel-occ / KITTI validation
(``:74-98``, ``:101-147``, ``:150-196``, ``:250-300``) — rebuilt around a
shape-bucketed jitted predictor: torch pads each sample and re-runs eager;
XLA wants static shapes, so ``FlowPredictor`` compiles once per padded
resolution bucket (Sintel has one bucket, KITTI a handful) and reuses the
executable across the whole epoch.

All functions operate on numpy at the edges (datasets produce numpy; flow
files are written with :mod:`raft_tpu.data.frame_utils`) and return plain
dicts of floats, the reference's interface for the periodic in-training
validation (reference ``train.py:402-409``).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import os.path as osp
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.data import datasets, frame_utils
from raft_tpu.families import FLOW_FAMILIES, family_of
from raft_tpu.utils.compile_count import xla_compile_count
from raft_tpu.utils.padder import InputPadder
from raft_tpu.utils.profiling import (host_timer, slow_unit_lines,
                                       unit_accounts)
from raft_tpu.utils.staging import StagingArena
from raft_tpu.utils.warm_start import forward_interpolate


class FlowPredictor:
    """Jitted ``test_mode`` forward with a per-resolution compile cache.

    Args:
      model: a flax module whose apply signature matches
        :class:`raft_tpu.models.raft.RAFT`.
      variables: the variable pytree ({'params': ..., ['batch_stats': ...]}).
      iters: refinement iterations (reference eval defaults: chairs/kitti 24,
        sintel 32 — ``evaluate.py:75,102,251``).
      batch_size: frames per forward. Defaults to 8 on TPU (batched eval
        amortizes dispatch and fills the MXU; tail batches are padded by
        repeating the last frame) and 1 elsewhere.
      corr_impl: ``"fixed"`` uses ``model`` as configured. ``"auto"``
        (canonical RAFT only; rejected for other families rather than
        silently ignored) picks the correlation engine per padded
        shape — including under spatially-sharded eval since round 5,
        where the kernel runs per-shard via shard_map when the feature
        rows divide the spatial axis: the fused on-demand Pallas
        kernel wherever its VMEM-resident layout admits the shape on TPU
        (:func:`raft_tpu.models.corr.alternate_eval_eligible` — measured
        1.5x faster than the materialized volume at Sintel eval, BENCH
        r4), the all-pairs pyramid otherwise — in both directions: an
        already-alternate model falls back to the materialized engine at
        ineligible shapes. Both engines share the same parameters;
        numerics agree to float accumulation order (golden-parity
        tested).

    The scan body's fused-kernel dispatches are trace-time env flags,
    not constructor knobs: ``RAFT_GRU_PALLAS`` (auto = fused Pallas
    SepConvGRU cell on TPU when eligible; see ``ops/gru_pallas.py``),
    ``RAFT_MOTION_PALLAS`` (same contract for the fused BasicMotion-
    Encoder chain; ``ops/motion_pallas.py``) and ``RAFT_STEP_PALLAS``
    (the fused ONE-launch iteration chaining both, plus the flow head
    where admissible; ``ops/step_pallas.py`` — where it applies it
    subsumes the two per-kernel flags) are read when each per-shape
    executable is traced, and the resolved modes are recorded on the
    predictor as ``gru_impl``/``motion_impl``/``step_impl`` at
    construction — both for observability and so a misspelled value
    fails at predictor build time, before the serving engine warms
    buckets against it.
    Flipping an env var after warmup would retrace (a compile the
    serving zero-compile contract forbids); set it before construction.
    """

    def __init__(self, model, variables, iters: int = 32,
                 batch_size: Optional[int] = None, mesh=None,
                 corr_impl: str = "fixed",
                 warm_iters: Optional[int] = None,
                 early_exit: Optional[Tuple[float, int]] = None):
        if corr_impl not in ("fixed", "auto"):
            raise ValueError(f"corr_impl must be 'fixed' or 'auto', "
                             f"got {corr_impl!r}")
        self.model = model
        self._engines = None          # (allpairs RAFT, alternate RAFT)
        if corr_impl == "auto":
            import dataclasses

            from raft_tpu.models.raft import RAFT
            if not isinstance(model, RAFT):
                raise ValueError(
                    "corr_impl='auto' applies to the canonical RAFT "
                    "family only (other families fix their correlation "
                    "semantics architecturally)")
            cfg = model.config
            # Engine siblings share params; per-engine config knobs that
            # the *other* engine's validator rejects are reset to "auto"
            # (corr_dtype only stores the materialized pyramid,
            # corr_mxu_dtype only feeds the on-demand kernel).
            self._engines = (
                model if not cfg.alternate_corr else RAFT(
                    dataclasses.replace(cfg, alternate_corr=False,
                                        corr_mxu_dtype="auto")),
                model if cfg.alternate_corr else RAFT(
                    dataclasses.replace(cfg, alternate_corr=True,
                                        corr_dtype="auto")))
        self.variables = variables
        self.iters = iters
        # Warm-frame iteration count for the streaming refine path
        # (None → same as iters). RAFT accuracy is near-monotone in GRU
        # iterations and a warm frame starts from the propagated
        # previous flow, so streams trade a few iterations for latency
        # without falling off a cliff (the paper's warm-start mode).
        # Part of the refine executable's cache key, so changing it
        # mid-run compiles a new executable rather than corrupting a
        # cached one.
        if warm_iters is not None and warm_iters < 1:
            raise ValueError(f"warm_iters must be >= 1, got {warm_iters}")
        self.warm_iters = warm_iters
        # Convergence early exit (tol, patience) for the PER-REQUEST-
        # ITERS dispatch path only (see :meth:`dispatch_batch`'s
        # ``iters=`` kwarg): when set, those executables thread
        # ``early_exit`` into the model's masked refine scan and return
        # a third ``(B,)`` per-sample iterations-used array. ``None``
        # (default) keeps every executable — including the iters path —
        # byte-identical to the pre-knob trace. Part of the cache key.
        if early_exit is not None:
            tol, patience = early_exit
            if not (tol > 0.0):
                raise ValueError(f"early_exit tol must be > 0, got {tol}")
            if int(patience) < 1:
                raise ValueError(
                    f"early_exit patience must be >= 1, got {patience}")
            early_exit = (float(tol), int(patience))
        self.early_exit = early_exit
        # Resolved RAFT_GRU_PALLAS / RAFT_MOTION_PALLAS /
        # RAFT_STEP_PALLAS modes ('auto'/'0'/'1') — validated here so
        # bad values fail at build time, recorded for observability
        # (bench/serving annotate payloads with them). The actual
        # dispatches happen at trace time inside
        # SepConvGRU/BasicUpdateBlock.__call__.
        from raft_tpu.ops import gru_pallas, motion_pallas, step_pallas
        self.gru_impl = gru_pallas.resolve_mode()
        self.motion_impl = motion_pallas.resolve_mode()
        self.step_impl = step_pallas.resolve_mode()
        # Optional sequence(spatial)-parallel execution: with a mesh the
        # forward runs through parallel.spatial.spatial_jit — image rows
        # sharded over the mesh's spatial axis, each device holding 1/d
        # of every activation and of the (HW)^2 correlation volume (the
        # multi-chip high-resolution eval path, BASELINE configs[4]).
        self.mesh = mesh
        # Batched eval is the TPU operating point (amortizes per-dispatch
        # overhead and fills the MXU); single-sample on CPU where compile
        # time dominates.
        if batch_size is None:
            batch_size = 8 if jax.default_backend() == "tpu" else 1
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.batch_size = batch_size
        # Donate the image buffers to the compiled executable (serving's
        # steady state re-stacks fresh host arrays every batch, so the
        # device copies are dead after dispatch). Off by default: eval
        # callers may reuse arrays, and CPU/older backends warn on
        # donation. The serving engine flips it on TPU. Cold flips only:
        # the flag is part of the executable cache key, so toggling it
        # mid-run recompiles rather than corrupting cached callables.
        self.donate_images = False
        self._cache: Dict = {}
        # Host staging buffers of the dataset pass (_predict_dataset):
        # they live as long as the predictor, so a second pass over the
        # same shape (Sintel clean, then final) starts warm.
        self.staging = StagingArena()

    def _pick_engine(self, shape, n_sp: int = 1, n_dt: int = 1):
        """corr_impl='auto' per-shape engine choice, shared by the
        sharded and unsharded paths: the fused on-demand kernel wherever
        its VMEM layout admits this padded shape on TPU (and, sharded,
        where feature rows divide the spatial axis AND the batch divides
        the data axis — a sharded-fused configuration the shard_map
        wrapper would reject must fall back to the materialized engine
        here, not surface as a lowering failure), else the materialized
        pyramid."""
        if self._engines is None:
            return self.model
        from raft_tpu.models.corr import alternate_eval_eligible
        allpairs, alternate = self._engines
        return (alternate
                if jax.default_backend() == "tpu"
                and alternate_eval_eligible(self.model.config,
                                            shape[1:3],
                                            spatial_shards=n_sp,
                                            batch=shape[0],
                                            data_shards=n_dt)
                else allpairs)

    def _fn(self, shape, warm: bool, wire: str = "float32") -> Callable:
        # Donation applies to the plain-jit path, warm included: only
        # the image buffers (argnums 1, 2) are donated — flow_init (arg
        # 3) is fresh host data each call and is left alone, so
        # donate+warm compose instead of silently disabling donation
        # (which blocked TPU-default configs from ever warm-starting).
        # Mesh dispatch never reaches here: ``__call__`` and
        # ``dispatch_batch`` route meshed predictors through
        # :meth:`sharded_dispatch` (the ("sharded", ...) cache family),
        # so the plain-jit families below are unsharded by construction.
        if self.mesh is not None:
            raise AssertionError(
                "_fn is the unsharded executable family; meshed "
                "predictors dispatch via sharded_dispatch()")
        donate = bool(self.donate_images)
        # ``wire`` is the image dtype the executable was traced for
        # (uint8 requests normalize on device — models/normalize.py);
        # keying on it keeps the zero-post-warmup-compile accounting
        # honest when uint8 and float32 traffic share one bucket shape.
        key = (shape, warm, self.iters, donate, wire)
        if key not in self._cache:
            model = self._pick_engine(shape)

            def run(variables, image1, image2, flow_init=None,
                    model=model):
                return model.apply(
                    variables, image1, image2, iters=self.iters,
                    flow_init=flow_init, test_mode=True)

            self._cache[key] = jax.jit(
                run, donate_argnums=(1, 2) if donate else ())
        return self._cache[key]

    def _sharded_fn(self, shape, mesh, warm: bool,
                    wire: str = "float32") -> Callable:
        """Spatially-sharded executable family (the multi-chip
        high-resolution latency path): image rows over ``mesh``'s
        spatial axis via :func:`raft_tpu.parallel.spatial.spatial_jit`.

        Cache keys are ``(shape, ("sharded", (n_data, n_spatial,
        device_ids), warm), donate)`` — the ``"sharded"`` tag tuple can
        never collide with the stateless ``warm`` bool, the
        ``("iters", ...)`` tuple, the ``"encode"`` tag, or the
        ``("refine", ...)`` tag, so one predictor (and every
        ``clone_with_variables`` clone) serves sharded AND unsharded
        buckets through the one shared cache. Donation composes the
        same way as the plain-jit families (image buffers only).

        Per-shape engine dispatch (round 5, VERDICT r4 #2) carries
        over: the banded kernel composes with the row-sharded forward
        via shard_map (models.corr._sharded_fused_lookup), whose stores
        go through the ops/layout.py boundary contract, so high-res
        multi-chip eval keeps the kernel wherever it fits VMEM and rows
        divide evenly. ``warm=True`` selects the warm-start executable:
        the low-res flow_init gets its own row-sharding spec
        (``spatial_jit(warm_init=True)``).

        ``shape`` must have rows divisible by the spatial axis —
        :meth:`sharded_dispatch` pre-pads indivisible heights.
        """
        from raft_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
        from raft_tpu.parallel.spatial import spatial_jit

        n_sp = mesh.shape[SPATIAL_AXIS]
        n_dt = mesh.shape.get(DATA_AXIS, 1)
        assert shape[1] % n_sp == 0, (shape, n_sp)
        donate = bool(self.donate_images)
        mesh_key = (n_dt, n_sp, tuple(d.id for d in mesh.devices.flat))
        key = (shape, ("sharded", mesh_key, bool(warm)), donate, wire)
        if key not in self._cache:
            model = self._pick_engine(shape, n_sp=n_sp, n_dt=n_dt)
            if warm:
                def run(variables, image1, image2, flow_init,
                        model=model):
                    return model.apply(
                        variables, image1, image2, iters=self.iters,
                        flow_init=flow_init, test_mode=True)
            else:
                def run(variables, image1, image2, model=model):
                    return model.apply(
                        variables, image1, image2, iters=self.iters,
                        test_mode=True)
            self._cache[key] = spatial_jit(
                run, mesh, donate=donate, warm_init=warm)
        return self._cache[key]

    def sharded_dispatch(self, images1, images2, flow_init=None,
                         mesh=None):
        """Non-blocking spatially-sharded batched forward: (B, H, W, 3)
        stacks → ``(flow_low, flow_up)`` *device* arrays, image rows
        sharded over the mesh's spatial axis — ONE request's (HW)²
        correlation volume split across chips, the latency lever for
        high-resolution pairs that cannot batch.

        ``mesh`` defaults to the predictor's own ``self.mesh``. The
        serving engine passes an explicit serving mesh instead, so a
        single predictor serves the unsharded batched buckets and the
        sharded high-res bucket side by side through the one executable
        cache (disjoint ``("sharded", ...)`` keys; see
        :meth:`_sharded_fn`).

        Heights whose rows do not divide the spatial axis are
        edge-padded (bottom rows, matching InputPadder's replicate
        policy) up to the least multiple of ``spatial_shards * 8`` and
        the flows lazily cropped back — the pad→forward→crop
        composition replaces the old hard ValueError on indivisible
        heights and keeps the /8 feature rows divisible too (the
        sharded banded kernel's own requirement). Shapes that already
        divide are passed through untouched (bit-identical to the
        round-5 path).

        ``flow_init`` (B, H/8, W/8, 2) warm-starts the refinement scan
        through the warm sharded executable — the init flow carries its
        own row-sharding spec, so ``--warm_start`` composes with
        ``--spatial_shards``.
        """
        mesh = self.mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError(
                "sharded_dispatch needs a mesh — construct the "
                "predictor with one (load_predictor(spatial_shards=N)) "
                "or pass mesh= explicitly")
        from raft_tpu.parallel.mesh import SPATIAL_AXIS
        n_sp = mesh.shape[SPATIAL_AXIS]
        images1 = np.asarray(images1)
        images2 = np.asarray(images2)
        rows = int(images1.shape[1])
        unit = n_sp * 8
        # Rows dividing the spatial axis pass through unpadded (the /8
        # feature rows may still be uneven — GSPMD handles that for the
        # stateless path and eligibility gating keeps the kernel off).
        # The warm path additionally needs the /8 init-flow rows even,
        # so it pads unless rows divide spatial_shards * 8.
        indivisible = (rows % n_sp != 0 or
                       (flow_init is not None and rows % unit != 0))
        extra = (-rows) % unit if indivisible else 0
        if extra:
            pad = ((0, 0), (0, extra), (0, 0), (0, 0))
            images1 = np.pad(images1, pad, mode="edge")
            images2 = np.pad(images2, pad, mode="edge")
            if flow_init is not None:
                flow_init = np.pad(
                    np.asarray(flow_init),
                    ((0, 0), (0, extra // 8), (0, 0), (0, 0)),
                    mode="edge")
        img1 = jnp.asarray(images1)
        img2 = jnp.asarray(images2)
        fn = self._sharded_fn(img1.shape, mesh, flow_init is not None,
                              str(img1.dtype))
        if flow_init is None:
            flow_low, flow_up = fn(self.variables, img1, img2)
        else:
            flow_low, flow_up = fn(self.variables, img1, img2,
                                   jnp.asarray(flow_init))
        if extra:
            # Lazy device crops: still async (the caller syncs), and the
            # tiny slice executables compile once per shape — during
            # serving warmup, which drives this same path.
            flow_low = flow_low[:, :rows // 8]
            flow_up = flow_up[:, :rows]
        return flow_low, flow_up

    def __call__(self, image1: np.ndarray, image2: np.ndarray,
                 flow_init: Optional[np.ndarray] = None):
        """image1/2: (H, W, 3) in [0, 255] — float32 or uint8 (the
        serving wire format; normalization happens inside the model,
        so integral inputs produce bit-identical flow either way),
        already padded to /8.

        Returns ``(flow_low, flow_up)`` numpy arrays, shapes
        ``(H/8, W/8, 2)`` and ``(H, W, 2)``.
        """
        if self.mesh is not None:
            init = (None if flow_init is None
                    else np.asarray(flow_init)[None])
            flow_low, flow_up = self.sharded_dispatch(
                np.asarray(image1)[None], np.asarray(image2)[None], init)
            return np.asarray(flow_low[0]), np.asarray(flow_up[0])
        img1 = jnp.asarray(image1)[None]
        img2 = jnp.asarray(image2)[None]
        init = None if flow_init is None else jnp.asarray(flow_init)[None]
        fn = self._fn(img1.shape, flow_init is not None, str(img1.dtype))
        flow_low, flow_up = fn(self.variables, img1, img2, init)
        return np.asarray(flow_low[0]), np.asarray(flow_up[0])

    def clone_with_variables(self, variables) -> "FlowPredictor":
        """A predictor serving ``variables`` through *this* predictor's
        compiled executables.

        Variables enter the jitted forward as a traced argument (never
        closed over), so a clone sharing ``_cache`` runs new weights
        with zero fresh XLA compiles — the property hot checkpoint
        reload stands on: the standby model canaries and then serves
        through the bucket executables the engine already warmed. The
        clone shares model/engines/mesh/cache (all weight-independent);
        ``variables`` must match the current pytree structure (same
        top-level keys — e.g. include ``batch_stats`` iff the current
        variables carry it) or the shared cache would retrace."""
        import copy

        if set(variables) != set(self.variables):
            raise ValueError(
                "clone_with_variables needs the same variable "
                f"collections as the current model ({sorted(self.variables)}), "
                f"got {sorted(variables)} — a structure change would "
                "force a recompile through the shared executable cache")
        clone = copy.copy(self)
        clone.variables = variables
        return clone

    def _iters_fn(self, shape, iters: int,
                  wire: str = "float32") -> Callable:
        """Per-request-iters executable: same forward as :meth:`_fn`'s
        stateless cold path but with an explicit GRU iteration count —
        the serving brownout ladder's compile unit. The cache key's
        second element is the tuple ``("iters", k, early_exit)``, which
        can never equal the stateless ``warm`` bool, the ``"encode"``
        tag, or the ``("refine", warm)`` tag — the four executable
        families stay disjoint in the one shared cache (clones included).
        With ``self.early_exit`` set, the executable returns
        ``(flow_low, flow_up, iters_used)``; otherwise the usual pair.
        """
        iters = int(iters)
        if iters < 1:
            raise ValueError(f"iters must be >= 1, got {iters}")
        if self.mesh is not None:
            raise ValueError(
                "per-request iters is not supported with spatially-"
                "sharded eval — degraded-quality buckets would need "
                "their own sharding specs")
        donate = bool(self.donate_images)
        ee = self.early_exit
        key = (shape, ("iters", iters, ee), donate, wire)
        if key not in self._cache:
            model = self._pick_engine(shape)

            def run(variables, image1, image2, flow_init=None,
                    model=model):
                return model.apply(
                    variables, image1, image2, iters=iters,
                    flow_init=flow_init, test_mode=True, early_exit=ee)

            self._cache[key] = jax.jit(
                run, donate_argnums=(1, 2) if donate else ())
        return self._cache[key]

    def dispatch_batch(self, images1: np.ndarray, images2: np.ndarray,
                       iters: Optional[int] = None):
        """Non-blocking batched forward: (B, H, W, 3) stacks →
        ``(flow_low, flow_up)`` *device* arrays, returned as soon as the
        computation is dispatched (JAX async dispatch). The caller syncs
        when it reads them (``np.asarray``), so host work — stacking the
        next batch, padding — overlaps device compute. This is the
        serving engine's pipelining primitive; :meth:`predict_batch` is
        the blocking wrapper.

        ``iters``: per-request GRU iteration count (the brownout
        ladder). ``None`` dispatches the default ``self.iters``
        executable — bit-identical to the pre-knob path. An explicit
        count routes through :meth:`_iters_fn`; with the predictor's
        ``early_exit`` set that path returns a third per-sample
        iterations-used array.

        Meshed predictors route the default-iters path through
        :meth:`sharded_dispatch` (rows over the spatial axis); explicit
        ``iters`` still refuses there (:meth:`_iters_fn`)."""
        if iters is None and self.mesh is not None:
            return self.sharded_dispatch(images1, images2)
        timer = host_timer()
        # predict.h2d is the host time of the two calls: no sync is
        # added, so a transfer the runtime only enqueues ends later
        with timer.span("predict.h2d") as span:
            img1 = jnp.asarray(images1)
            img2 = jnp.asarray(images2)
            span.nbytes = img1.nbytes + img2.nbytes
        with timer.span("predict.dispatch"):
            if iters is None:
                fn = self._fn(img1.shape, False, str(img1.dtype))
            else:
                fn = self._iters_fn(img1.shape, iters, str(img1.dtype))
            return fn(self.variables, img1, img2, None)

    def collect_batch(self, flows):
        """The blocking half of :meth:`predict_batch`: wait for the
        ``(flow_low, flow_up)`` device arrays :meth:`dispatch_batch`
        returned and copy them to numpy. The dataset pass calls the two
        halves apart, with the next batch's dispatch in between."""
        timer = host_timer()
        # the first np.asarray would block as long; waiting here tells
        # the device's time apart from the copy's
        with timer.span("predict.device_wait"):
            jax.block_until_ready(flows)
        with timer.span("predict.d2h") as span:
            flow_low, flow_up = (np.asarray(f) for f in flows)
            span.nbytes = flow_low.nbytes + flow_up.nbytes
        return flow_low, flow_up

    def predict_batch(self, images1: np.ndarray, images2: np.ndarray):
        """Batched forward: (B, H, W, 3) stacks → ((B, H/8, W/8, 2),
        (B, H, W, 2)) numpy."""
        return self.collect_batch(self.dispatch_batch(images1, images2))

    # ----- streaming (session) entry points -------------------------------
    # The stateless forward runs fnet twice per pair (twin-image trick).
    # For a temporally coherent stream, frame t's fmap2 IS frame t+1's
    # fmap1, so the session path splits the forward into two jitted
    # entry points: encode (fnet only) and refine (corr + cnet + scan,
    # fed precomputed fmaps) — one encoder pass per warm frame instead
    # of two, plus fewer GRU iterations when warm. Cache keys extend the
    # stateless (shape, warm, iters, donate, wire) convention so warm and
    # cold frames hit distinct pre-warmed executables (the serving
    # engine's zero-post-warmup-compile contract covers all three, in
    # both wire dtypes).

    def _require_session_path(self, what: str) -> None:
        from raft_tpu.models.raft import RAFT
        if not isinstance(self.model, RAFT):
            raise ValueError(
                f"the streaming {what} path applies to the canonical "
                "RAFT family only (other families have no split "
                "encode/refine entry point)")

    def _session_mesh(self, shape, what: str):
        """Resolve the session entry points' spatial-sharding context:
        ``(mesh_key, n_sp, n_dt)`` for a meshed predictor (the cached
        per-session feature maps get row-sharding specs like
        ``flow_init``'s — the round-6 refusal, closed), or ``(None, 1,
        1)`` unsharded. The /8 feature rows must divide the spatial
        axis — the same divisibility the warm sharded family already
        requires — so indivisible heights fail loudly here instead of
        surfacing as a GSPMD error mid-stream."""
        if self.mesh is None:
            return None, 1, 1
        from raft_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
        n_sp = self.mesh.shape[SPATIAL_AXIS]
        n_dt = self.mesh.shape.get(DATA_AXIS, 1)
        if int(shape[1]) % (n_sp * 8) != 0:
            raise ValueError(
                f"the streaming {what} path over spatially-sharded eval "
                f"needs padded rows divisible by spatial_shards*8 = "
                f"{n_sp * 8} (the cached fmaps are row-sharded at 1/8 "
                f"resolution), got H={shape[1]}")
        mesh_key = (n_dt, n_sp,
                    tuple(d.id for d in self.mesh.devices.flat))
        return mesh_key, n_sp, n_dt

    def _session_shardings(self, n_args: int):
        """``in_shardings`` for a meshed session executable: variables
        replicated, every array argument (images, fmaps, flow_init)
        row-sharded with the images' (data, spatial) spec — fmaps live
        at 1/8 resolution, same layout rationale as ``spatial_jit
        (warm_init=True)``'s flow_init spec."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from raft_tpu.parallel.spatial import image_spec
        ispec = NamedSharding(self.mesh, image_spec())
        rep = NamedSharding(self.mesh, P())
        return (rep,) + (ispec,) * n_args

    def encode_dispatch(self, images):
        """Non-blocking encoder-only forward: (B, H, W, 3) image stack →
        (B, H/8, W/8, C) *device* feature map (fnet, inference mode).
        The input stack is donated when ``donate_images`` is on (it is a
        fresh host buffer every call in the serving steady state); the
        returned fmap is NOT donated anywhere — the engine syncs and
        slices it into per-session host caches."""
        img = jnp.asarray(images)
        mesh_key, _, _ = self._session_mesh(img.shape, "encode")
        key = (img.shape, "encode" if mesh_key is None
               else ("encode", mesh_key), str(img.dtype))
        if key not in self._cache:
            self._require_session_path("encode")
            from raft_tpu.models.raft import RAFT
            donate = bool(self.donate_images) and self.mesh is None

            def run(variables, images):
                return self.model.apply(variables, images,
                                        method=RAFT.encode_features)

            if mesh_key is None:
                self._cache[key] = jax.jit(
                    run, donate_argnums=(1,) if donate else ())
            else:
                from raft_tpu.parallel.spatial import spatial_kernel_mesh
                mesh = self.mesh

                def traced(variables, images):
                    with spatial_kernel_mesh(mesh):
                        return run(variables, images)

                self._cache[key] = jax.jit(
                    traced, in_shardings=self._session_shardings(1))
        return self._cache[key](self.variables, img)

    def refine_dispatch(self, images1, fmap1, fmap2, flow_init=None,
                        warm: bool = False, iters: Optional[int] = None):
        """Non-blocking refine-only forward with precomputed feature
        maps: (B, H, W, 3) first images (cnet input), (B, H/8, W/8, C)
        fmaps → ``(flow_low, flow_up)`` device arrays.

        ``warm=True`` requires ``flow_init`` (B, H/8, W/8, 2) and runs
        ``warm_iters`` (→ ``iters`` when unset); cold refine takes no
        flow_init argument at all — a distinct executable, same contract
        as the stateless warm/cold split. ``iters`` overrides the
        iteration count for WARM refine only (the stream brownout
        ladder; cold/prime pairs keep the cold policy by contract) —
        it selects a distinct executable through the same cache-key
        slot the warm/cold split already uses, so no new key shapes.
        Donated when enabled: images1 and fmap1 (both fresh per-batch
        host buffers). fmap2 is NEVER donated — it is the encode output
        the engine syncs after this dispatch to seed the next frame's
        fmap1 caches."""
        if warm and flow_init is None:
            raise ValueError("warm refine requires flow_init")
        if not warm and flow_init is not None:
            raise ValueError("cold refine takes no flow_init (warm=True "
                             "selects the warm executable)")
        if iters is not None and not warm:
            raise ValueError("per-request iters applies to warm refine "
                             "only — cold/prime pairs keep the cold "
                             "policy")
        if iters is not None and int(iters) < 1:
            raise ValueError(f"iters must be >= 1, got {iters}")
        img1 = jnp.asarray(images1)
        fm1 = jnp.asarray(fmap1)
        fm2 = jnp.asarray(fmap2)
        if iters is not None:
            iters_used = int(iters)
        else:
            iters_used = (self.warm_iters if warm and self.warm_iters
                          else self.iters)
        donate = bool(self.donate_images) and self.mesh is None
        mesh_key, n_sp, n_dt = self._session_mesh(img1.shape, "refine")
        tag = (("refine", bool(warm)) if mesh_key is None
               else ("refine", bool(warm), mesh_key))
        key = (img1.shape, tag, iters_used, donate, str(img1.dtype))
        if key not in self._cache:
            self._require_session_path("refine")
            model = self._pick_engine(img1.shape, n_sp=n_sp, n_dt=n_dt)
            if warm:
                def run(variables, image1, fmap1, fmap2, flow_init,
                        model=model):
                    return model.apply(
                        variables, image1, None, iters=iters_used,
                        flow_init=flow_init, fmap1=fmap1, fmap2=fmap2,
                        test_mode=True)
            else:
                def run(variables, image1, fmap1, fmap2, model=model):
                    return model.apply(
                        variables, image1, None, iters=iters_used,
                        fmap1=fmap1, fmap2=fmap2, test_mode=True)
            if mesh_key is None:
                self._cache[key] = jax.jit(
                    run, donate_argnums=(1, 2) if donate else ())
            else:
                from raft_tpu.parallel.spatial import spatial_kernel_mesh
                mesh, inner = self.mesh, run

                def run(variables, *arrays, _inner=inner):
                    with spatial_kernel_mesh(mesh):
                        return _inner(variables, *arrays)

                self._cache[key] = jax.jit(
                    run, in_shardings=self._session_shardings(
                        4 if warm else 3))
        fn = self._cache[key]
        if warm:
            return fn(self.variables, img1, fm1, fm2,
                      jnp.asarray(flow_init))
        return fn(self.variables, img1, fm1, fm2)

    # ----- step-granular (continuous batching) entry points ---------------
    # The continuous serving scheduler (serving/contbatch.py) drives the
    # refinement loop in chunks over a fixed-slot device-resident carry
    # instead of one monolithic k-iteration executable per batch: admit
    # writes freshly initialized samples into freed slots (in-carry
    # scatter), step runs `s` masked update iterations for every
    # occupied slot at once, finalize reads the mask-computing last
    # iteration for retiring slots. One compile per (H, W, slots, s) —
    # the iters ladder, early exit, and mixed traffic all share it.
    # Cache keys use "stepcarry"/"stepadmit"/"step"/"stepfin" tags,
    # disjoint from every existing family in the one shared cache.

    def _require_step_path(self, what: str) -> None:
        from raft_tpu.models.raft import RAFT
        if self.mesh is not None:
            raise ValueError(
                f"the continuous {what} path is not supported with "
                "spatially-sharded eval — the slot carry has no "
                "sharding specs (serve sharded buckets through the "
                "monolithic path)")
        if not isinstance(self.model, RAFT):
            raise ValueError(
                f"the continuous {what} path applies to the canonical "
                "RAFT family only (other families have no step-granular "
                "refine entry point)")

    @staticmethod
    def _carry_shape(carry):
        """(slots, H, W) of a slot carry — net is (slots, H/8, W/8, C)."""
        net = carry["net"]
        return (int(net.shape[0]), int(net.shape[1]) * 8,
                int(net.shape[2]) * 8)

    def step_carry_dispatch(self, images1, images2):
        """Bootstrap one bucket's slot table: a full-width
        ``refine_init`` over ``(slots, H, W, 3)`` stacks → the
        device-resident carry dict. Called once per bucket at warmup
        (the zeros it computes are placeholder occupants; real requests
        overwrite their slots via :meth:`step_admit_dispatch`)."""
        img1 = jnp.asarray(images1)
        img2 = jnp.asarray(images2)
        key = (img1.shape, ("stepcarry",), str(img1.dtype))
        if key not in self._cache:
            self._require_step_path("bootstrap")
            from raft_tpu.models.raft import RAFT
            model = self._pick_engine(img1.shape)

            def run(variables, i1, i2, model=model):
                return model.apply(variables, i1, i2,
                                   method=RAFT.refine_init)

            self._cache[key] = jax.jit(run)
        return self._cache[key](self.variables, img1, img2)

    def step_admit_dispatch(self, images1, images2, idx, carry):
        """Admit ``m`` requests into slot rows ``idx`` of ``carry``:
        ONE fused executable runs ``refine_init`` over the ``(m, H, W,
        3)`` stacks and scatters the fresh per-sample state (context,
        coords, correlation payload, zeroed early-exit counters) into
        the donated slot table. ``m`` is the admission width — the
        scheduler pads to a power of two by repeating the last real
        admission (duplicate indices write identical values), so the
        family stays at ``log2(slots)+1`` executables per wire dtype.
        Returns the new carry (the old one's buffers are consumed when
        donation is on)."""
        img1 = jnp.asarray(images1)
        img2 = jnp.asarray(images2)
        idx = jnp.asarray(idx, jnp.int32)
        slots = int(carry["net"].shape[0])
        donate = bool(self.donate_images)
        key = (img1.shape, ("stepadmit", slots), donate,
               str(img1.dtype))
        if key not in self._cache:
            self._require_step_path("admit")
            from raft_tpu.models.raft import RAFT, scatter_carry
            model = self._pick_engine((slots, *img1.shape[1:]))

            def run(variables, i1, i2, idx, carry, model=model):
                fresh = model.apply(variables, i1, i2,
                                    method=RAFT.refine_init)
                return scatter_carry(carry, fresh, idx, slots)

            self._cache[key] = jax.jit(
                run, donate_argnums=(1, 2, 4) if donate else ())
        return self._cache[key](self.variables, img1, img2, idx, carry)

    def step_dispatch(self, carry, remaining, steps: int):
        """Run ``steps`` masked refinement iterations over the slot
        carry; ``remaining`` is the per-slot (slots,) int32 budget of
        mask-free iterations still owed (host-computed each launch — the
        brownout re-target is free host arithmetic, never a device
        scatter). Slots with no budget (or early-exited, with the
        predictor's ``early_exit`` set) are frozen in-executable.
        Returns ``(carry', remaining')`` device values; wire-agnostic
        (the carry's dtypes are fixed at bootstrap)."""
        slots, H, W = self._carry_shape(carry)
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        donate = bool(self.donate_images)
        ee = self.early_exit
        key = ((slots, H, W), ("step", steps, ee), donate)
        if key not in self._cache:
            self._require_step_path("step")
            from raft_tpu.models.raft import refine_chunk
            model = self._pick_engine((slots, H, W, 3))

            def run(variables, carry, remaining, model=model):
                return refine_chunk(model.config, variables, carry,
                                    remaining, steps, ee)

            self._cache[key] = jax.jit(
                run, donate_argnums=(1,) if donate else ())
        return self._cache[key](self.variables, carry,
                                jnp.asarray(remaining, jnp.int32))

    def step_finalize_dispatch(self, carry):
        """The mask-computing final iteration over ALL slots: one
        update + convex upsample, carry NOT consumed (co-resident slots
        keep stepping from it). Returns ``(flow_low, flow_up)`` device
        arrays at the slot width; the scheduler slices retiring slots
        host-side after sync. A request's ``k-1`` chunked iterations
        plus this call reproduce the monolithic two-call scan —
        per-request flow parity with ``dispatch_batch(iters=k)``."""
        slots, H, W = self._carry_shape(carry)
        key = ((slots, H, W), ("stepfin",))
        if key not in self._cache:
            self._require_step_path("finalize")
            from raft_tpu.models.raft import refine_finalize
            model = self._pick_engine((slots, H, W, 3))

            def run(variables, carry, model=model):
                return refine_finalize(model.config, variables, carry)

            self._cache[key] = jax.jit(run)
        return self._cache[key](self.variables, carry)


class _OpenBatch:
    """A padded shape's batch from its first frame to its last yield:
    its ``(idx, sample, padder)`` items and, for a batched predictor,
    the two ``(bs, H, W, C)`` staging buffers its frames are padded
    into, with how many of them the arena had to allocate; from its
    flush on its root span, and while it is dispatched ahead the
    predictor's device arrays. In the per-sample fallback an item
    carries its two padded frames instead."""

    __slots__ = ("shape", "items", "buffers", "fresh", "root", "flows")

    def __init__(self, shape, buffers=(), fresh=0):
        self.shape, self.items = shape, []
        self.buffers, self.fresh = buffers, fresh
        self.root = self.flows = None


def _predict_dataset(predictor, dataset, mode: Optional[str] = None):
    """Yield ``(idx, sample, flow_up)`` for every dataset element, running
    the model in fixed-size batches bucketed by padded shape.

    Batches are padded to ``predictor.batch_size`` by repeating the last
    frame (one compiled executable per (shape, batch) — partial final
    batches would otherwise each pay a fresh XLA compile). Falls back to
    per-sample ``__call__`` for predictors without ``predict_batch``.
    ``mode``: InputPadder mode, or None when the dataset needs no padding
    (FlyingChairs is already /8).

    **Order.** With a :class:`FlowPredictor` the pass is two batches
    deep, on this one thread: a full bucket is dispatched at once
    (``dispatch_batch``: H2D enqueued, the call queued behind the batch
    on the device), and only then is the batch dispatched before it
    collected (``collect_batch``: wait, D2H), its flows unpadded and
    yielded. So the device goes from one batch to the next with no host
    work in between, and the host fetches, pads and dispatches batch
    k+1, and unpads and yields batch k, while the device runs. Batches
    are collected in the order they were flushed: the consumer sees the
    sequence of the synchronous order, each batch one dispatch later;
    the last one is collected when the dataset is through. The pass
    pipelines only where ``predictor.predict_batch`` is
    ``FlowPredictor``'s own dispatch-then-collect. Any other
    ``predict_batch`` (a stand-in's, a wrapper set on the instance)
    promises one blocking call a batch, numpy in and numpy out, and
    gets that, in the synchronous order: stage, call, yield. If
    fetching or dispatching a batch raises, the batch before it is
    collected and yielded first, as the synchronous order had done.

    **Closing.** A consumer that closes the generator at a yield does
    not wait for the device: the batch dispatched ahead is abandoned
    (root closed with ``complete`` 0, staging pair dropped, device
    arrays freed whenever the runtime is done with them).

    **Staging.** Batches are staged through an arena
    (``predictor.staging``, else one of this pass's own): a bucket's
    first sample acquires two ``(bs, H, W, C)`` buffers of the sample's
    dtype, every sample is padded once, straight into its slot, and the
    pair stays with its batch until that batch's own outputs are ready:
    only then may a transfer no longer read it, and it goes back to the
    arena. Pipelined, two pairs a shape are alive (one on the device,
    one filling), allocated by a shape's first two batches; from the
    third on the frames land in warm pages and nothing is allocated. A
    pair whose batch failed or was abandoned is dropped, not pooled. No
    consumer sees arena memory: a yielded ``sample`` is the dataset's
    own and a yielded flow is a view of the predictor's output.

    **Spans.** Every call is one pass in the process host timer
    (:func:`raft_tpu.utils.profiling.host_timer`): a root span
    ``pass.batch`` from the batch's first fetch (the first after the
    previous flush) to its last yield (``unit``: the batch's sequence
    number in the pass), so two roots are open at a time, overlapping,
    closed in ``unit`` order; every child is opened under its own
    batch's root explicitly: ``pass.fetch`` / ``pass.pad`` per sample
    (``pass.pad``: the one copy of both frames into their slots; a
    bucket's first also takes the buffers from the arena),
    ``pass.stack`` once a batch (what is left of stacking: the tail
    slots' fill and the hand-off), the predictor's ``predict.*`` spans
    and ``pass.unpad`` per yielded sample. The root reads ``complete``
    1 once all its pairs were yielded, also where the consumer closes
    the generator at that yield; ``arena_fresh``: how many of the
    batch's two buffers had to be allocated; ``ahead``: 1 where the
    batch was dispatched while an earlier one's outputs were pending
    (0 for a pass's first batch and in the synchronous order);
    ``compiles`` where a call into the predictor for it compiled;
    ``consume_us``: how long the pass stood suspended at the batch's
    yields, the consumer's time; and, at its close, what its thread
    has used (``HostStageTimer.close_root``). For the length of the
    pass the cyclic collector's passes are spans too
    (``HostStageTimer.collector_spans``).
    """
    timer = host_timer()
    bs = getattr(predictor, "batch_size", 1)
    batched = hasattr(predictor, "predict_batch") and bs > 1
    pipelined = batched and FlowPredictor.predict_batch is getattr(
        predictor.predict_batch, "__func__", None)
    arena = getattr(predictor, "staging", None) or StagingArena()
    units = itertools.count()
    roots = []             # open, in unit order
    buckets: Dict = {}
    pending = None         # dispatched ahead: its flows are device arrays

    def open_root():
        # detached: the next batch's root opens while this one is open
        roots.append(timer.span("pass.batch", unit=next(units), complete=0,
                                ahead=0, consume_us=0).detach())
        return roots[-1]

    @contextlib.contextmanager
    def calling(root):
        """A call into the predictor for ``root``'s batch: its spans
        fall under that root, its compiles are counted there."""
        before = xla_compile_count()
        try:
            with timer.under(root):
                yield
        finally:
            compiles = xla_compile_count() - before
            if compiles:
                root.args["compiles"] = root.args.get("compiles", 0) + compiles

    def open_bucket(shape, dtype):
        before = arena.allocated
        buffers = tuple(arena.acquire((bs,) + shape, dtype)
                        for _ in range(2))
        return _OpenBatch(shape, buffers, arena.allocated - before)

    def stage(batch, padder, images):
        slot = len(batch.items)
        for dst, image in zip(batch.buffers, images):
            if padder:
                padder.pad_into(dst[slot], image)
            else:
                dst[slot] = image

    def launch(batch, root):
        batch.root = root
        n = len(batch.items)
        root.args.update(pairs=n, padded_to=bs if batched else 1,
                         height=batch.shape[0], width=batch.shape[1])
        if batched:
            i1, i2 = batch.buffers
            root.args["arena_fresh"] = batch.fresh
            with timer.span("pass.stack", parent=root,
                            nbytes=i1.nbytes + i2.nbytes):
                if n < bs:      # every tail slot rewritten: no stale frame
                    i1[n:] = i1[n - 1]
                    i2[n:] = i2[n - 1]
        if pipelined:
            root.args["ahead"] = int(pending is not None)
            with calling(root):
                batch.flows = predictor.dispatch_batch(*batch.buffers)
        return batch

    def launched():
        """The pass's batches in flush order, each staged and, where
        the pass pipelines, dispatched."""
        root = None        # of the batch whose frames are being fetched
        for idx in range(len(dataset)):
            root = root or open_root()
            with timer.span("pass.fetch", parent=root):
                sample = dataset[idx]
            images = sample[0], sample[1]
            padder = InputPadder(images[0].shape, mode=mode) if mode else None
            shape = ((padder.padded_shape if padder else images[0].shape[:2])
                     + images[0].shape[2:])
            key = shape, images[0].dtype.str
            batch = buckets.get(key)
            if batched:
                with timer.span("pass.pad", parent=root):
                    if batch is None:
                        batch = buckets[key] = open_bucket(
                            shape, images[0].dtype)
                    stage(batch, padder, images)
                images = ()
            else:
                if batch is None:
                    batch = buckets[key] = _OpenBatch(shape)
                if padder:
                    with timer.span("pass.pad", parent=root):
                        images = padder.pad(*images)
            batch.items.append((idx, sample, padder, *images))
            if len(batch.items) == bs:
                yield launch(buckets.pop(key), root)
                root = None
        for key in list(buckets):
            yield launch(buckets.pop(key), root or open_root())
            root = None

    def collect(batch):
        if batch is None:
            return
        root, n = batch.root, len(batch.items)
        consumed_ns = 0    # the consumer's, between a yield and the resume
        if batched:
            with calling(root):
                _, up = (predictor.collect_batch(batch.flows) if pipelined
                         else predictor.predict_batch(*batch.buffers))
            # Only now may the pair be written again. jnp.asarray merely
            # enqueues the H2D copy and reads the host array until the
            # transfer is done; the outputs are ready, and the
            # executable wrote them after it had its inputs whole. A
            # batch that raised never gets here: its pair is dropped,
            # not pooled. The device arrays go now, not when the next
            # batch has been staged.
            arena.release(*batch.buffers)
            batch.buffers, batch.flows = (), None
        for j, (idx, sample, padder, *frames) in enumerate(batch.items):
            if batched:
                flow = up[j]
            else:
                with calling(root):
                    flow = predictor(*frames)[1]
            if padder:
                with timer.span("pass.unpad", parent=root):
                    flow = padder.unpad(flow)
            # counted before the yield: a consumer that closes the
            # generator at the batch's last yield never resumes it
            root.args["complete"] = int(j == n - 1)
            handed = time.perf_counter_ns()
            yield idx, sample, flow
            consumed_ns += time.perf_counter_ns() - handed
            root.args["consume_us"] = consumed_ns // 1000
        roots.remove(root)
        timer.close_root(root)

    def take_pending():
        nonlocal pending
        batch, pending = pending, None
        return batch

    stream = launched()
    with timer.collector_spans():
        try:
            while True:
                try:
                    batch = next(stream, None)
                except Exception:
                    # the synchronous order had yielded the batch before
                    # this one ahead of touching this one's first pair
                    yield from collect(take_pending())
                    raise
                if batch is None:
                    yield from collect(take_pending())
                    break
                if pipelined:   # one behind: what was dispatched before it
                    batch, pending = pending, batch
                yield from collect(batch)
        finally:
            # Given up at a yield, or failed. Nothing waits for the
            # device: a batch dispatched ahead keeps running, its root
            # closes with complete 0, and its pair, which a transfer may
            # still read, is dropped with it; so is the pair of a batch
            # that raised.
            for root in roots:
                timer.close_root(root)
            # buckets still filling: nothing of theirs is in flight, so
            # their buffers go back
            for batch in buckets.values():
                arena.release(*batch.buffers)


def _reported_pass(predictor, dataset, mode: Optional[str] = None):
    """:func:`_predict_dataset` for the ``validate_*`` entry points:
    prints where the pass's host time went once it is through, how many
    of its batches' staging buffers came from the arena warm, and how
    many batches were dispatched while the one before was on the
    device, and a line for every batch that took over three times the
    pass's median batch."""
    timer = host_timer()
    before, began = timer.summary(), time.perf_counter_ns()
    yield from _predict_dataset(predictor, dataset, mode)
    spans = [s for s in timer.spans() if s.start_ns >= began]
    roots = [s.args for s in spans if s.name == "pass.batch"]
    fresh = [r["arena_fresh"] for r in roots if "arena_fresh" in r]
    reuse = (f" | arena reuse: {1 - sum(fresh) / (2 * len(fresh)):.0%} of "
             f"{2 * len(fresh)} buffers" if fresh else "")
    ahead = (f" | dispatched ahead: {sum(r['ahead'] for r in roots)} of "
             f"{len(roots)} batches" if roots else "")
    print("host stages:", timer.report(since=before) + reuse + ahead)
    for line in slow_unit_lines(unit_accounts(spans, "pass.batch"), "batch"):
        print(line)


def _epe_map(flow: np.ndarray, flow_gt: np.ndarray) -> np.ndarray:
    return np.sqrt(np.sum((flow - flow_gt) ** 2, axis=-1))


def validate_chairs(predictor: FlowPredictor, root=None) -> Dict[str, float]:
    """FlyingChairs val-split EPE (reference ``evaluate.py:74-98``)."""
    val_dataset = datasets.FlyingChairs(split="validation", root=root)
    epe_list = []
    for _, sample, flow in _reported_pass(predictor, val_dataset):
        flow_gt = sample[2]
        epe_list.append(_epe_map(flow, flow_gt).reshape(-1))
    epe = float(np.mean(np.concatenate(epe_list)))
    print(f"Validation Chairs EPE: {epe:.6f}")
    return {"chairs": epe}


def validate_sintel(predictor: FlowPredictor, root=None) -> Dict[str, float]:
    """Sintel train-split clean+final EPE and pixel thresholds
    (reference ``evaluate.py:101-147``)."""
    results: Dict[str, float] = {}
    for dstype in ("clean", "final"):
        val_dataset = datasets.MpiSintel(split="training", dstype=dstype,
                                         root=root)
        epe_list = []
        for _, sample, flow in _reported_pass(predictor, val_dataset,
                                              mode="sintel"):
            flow_gt = sample[2]
            epe_list.append(_epe_map(flow, flow_gt).reshape(-1))

        epe_all = np.concatenate(epe_list)
        epe = float(np.mean(epe_all))
        px1 = float(np.mean(epe_all < 1))
        px3 = float(np.mean(epe_all < 3))
        px5 = float(np.mean(epe_all < 5))
        print(f"Validation ({dstype}) EPE: {epe:.6f}, 1px: {px1:.6f}, "
              f"3px: {px3:.6f}, 5px: {px5:.6f}")
        results[dstype] = epe
    return results


def validate_sintel_occ(predictor: FlowPredictor,
                        root=None) -> Dict[str, float]:
    """Sintel validation split by occluded / non-occluded pixels
    (reference ``evaluate.py:150-196``; the reference's own data path for
    this is broken fork drift — see ``MpiSintel.read_occlusion``)."""
    results: Dict[str, float] = {}
    for dstype in ("albedo", "clean", "final"):
        val_dataset = datasets.MpiSintel(split="training", dstype=dstype,
                                         occlusion=True, root=root)
        if len(val_dataset) == 0 or not val_dataset.occ_list:
            continue
        epe_list, occ_list, noc_list = [], [], []
        for val_id, sample, flow in _reported_pass(predictor, val_dataset,
                                                   mode="sintel"):
            flow_gt = sample[2]
            occ = val_dataset.read_occlusion(val_id)
            epe = _epe_map(flow, flow_gt)
            epe_list.append(epe.reshape(-1))
            occ_list.append(epe[occ])
            noc_list.append(epe[~occ])

        epe_all = np.concatenate(epe_list)
        epe = float(np.mean(epe_all))
        epe_occ = float(np.mean(np.concatenate(occ_list)))
        epe_noc = float(np.mean(np.concatenate(noc_list)))
        print(f"Validation ({dstype}) EPE: {epe:.6f}, "
              f"occ: {epe_occ:.6f}, noc: {epe_noc:.6f}")
        results[dstype] = epe
        results[f"{dstype}_occ"] = epe_occ
        results[f"{dstype}_noc"] = epe_noc
    return results


def validate_kitti(predictor: FlowPredictor, root=None) -> Dict[str, float]:
    """KITTI-2015 train-split EPE and F1-all (reference
    ``evaluate.py:250-300``; outlier rule ``epe > 3 && epe/mag > 0.05``,
    ``:285``)."""
    val_dataset = datasets.KITTI(split="training", root=root)
    epe_list, out_list = [], []
    for _, sample, flow in _reported_pass(predictor, val_dataset,
                                          mode="kitti"):
        _, _, flow_gt, valid_gt = sample

        epe = _epe_map(flow, flow_gt)
        mag = np.sqrt(np.sum(flow_gt ** 2, axis=-1))
        val = valid_gt >= 0.5
        out = ((epe > 3.0) & ((epe / np.maximum(mag, 1e-12)) > 0.05))
        epe_list.append(np.mean(epe[val]))
        out_list.append(out[val].reshape(-1))

    epe = float(np.mean(epe_list))
    f1 = 100 * float(np.mean(np.concatenate(out_list)))
    print(f"Validation KITTI: {epe:.6f}, {f1:.6f}")
    return {"kitti-epe": epe, "kitti-f1": f1}


def create_sintel_submission(predictor: FlowPredictor,
                             warm_start: bool = False,
                             output_path: str = "sintel_submission",
                             root=None) -> None:
    """Write Sintel leaderboard ``.flo`` files (reference
    ``evaluate.py:21-50``), optionally warm-starting each frame from the
    forward-splatted previous low-res flow (``:40-41``)."""
    for dstype in ("clean", "final"):
        test_dataset = datasets.MpiSintel(split="test", aug_params=None,
                                          dstype=dstype, root=root)
        flow_prev, sequence_prev = None, None
        for test_id in range(len(test_dataset)):
            image1, image2, (sequence, frame) = test_dataset[test_id]
            if sequence != sequence_prev:
                flow_prev = None
            padder = InputPadder(image1.shape)
            im1, im2 = padder.pad(image1, image2)
            flow_low, flow = predictor(im1, im2, flow_init=flow_prev)
            flow = padder.unpad(flow)
            if warm_start:
                flow_prev = forward_interpolate(flow_low)

            output_dir = osp.join(output_path, dstype, sequence)
            os.makedirs(output_dir, exist_ok=True)
            frame_utils.write_flo(
                osp.join(output_dir, "frame%04d.flo" % (frame + 1)), flow)
            sequence_prev = sequence


def create_kitti_submission(predictor: FlowPredictor,
                            output_path: str = "kitti_submission",
                            root=None) -> None:
    """Write KITTI leaderboard 16-bit PNGs (reference
    ``evaluate.py:53-71``)."""
    test_dataset = datasets.KITTI(split="testing", aug_params=None,
                                  root=root)
    os.makedirs(output_path, exist_ok=True)
    for test_id in range(len(test_dataset)):
        image1, image2, (frame_id,) = test_dataset[test_id]
        padder = InputPadder(image1.shape, mode="kitti")
        im1, im2 = padder.pad(image1, image2)
        _, flow = predictor(im1, im2)
        flow = padder.unpad(flow)
        frame_utils.write_flow_kitti(osp.join(output_path, frame_id), flow)


_VALIDATORS = {
    "chairs": validate_chairs,
    "sintel": validate_sintel,
    "sintel_occ": validate_sintel_occ,
    "kitti": validate_kitti,
}

# Repo-owned fixture root (assets/demo-frames, assets/golden) — the single
# definition; demo.py and tests import it from here.
ASSETS_DIR = osp.join(osp.dirname(osp.dirname(osp.abspath(__file__))),
                      "assets")


class _GoldenFixture:
    """Dataset-protocol view of the repo-owned golden fixtures
    (``assets/``, built by ``scripts/make_golden_fixtures.py``): each item
    is ``(image1, image2, flow_gt, flow_golden)`` where ``flow_golden`` is
    the stored canonical-torch output with the fixture weights.
    ``variant``: "large" (default) or "small" — separate weights and
    golden outputs per model size (BASELINE configs[0] vs [1])."""

    def __init__(self, root: str, variant: str = "large"):
        import json
        self.frames = osp.join(root, "demo-frames")
        self.golden = osp.join(root, "golden")
        with open(osp.join(self.golden, "manifest.json")) as f:
            self.manifest = json.load(f)
        if variant == "large":
            self.prefix, self.pairs = "flow_golden", self.manifest["pairs"]
        else:
            sub = self.manifest[variant]
            self.prefix, self.pairs = sub["prefix"], sub["pairs"]

    def __len__(self):
        return len(self.pairs)

    def __getitem__(self, idx):
        pair = self.pairs[idx]
        img1 = np.asarray(frame_utils.read_gen(
            osp.join(self.frames, pair["frame1"])), np.float32)
        img2 = np.asarray(frame_utils.read_gen(
            osp.join(self.frames, pair["frame2"])), np.float32)
        gt = frame_utils.read_flo(
            osp.join(self.golden, f"flow_gt_{idx:02d}.flo"))
        golden = np.load(osp.join(self.golden,
                                  f"{self.prefix}_{idx:02d}.npy"))
        return img1, img2, gt, golden


def validate_golden(predictor: FlowPredictor, root=None,
                    variant: str = "large") -> Dict[str, float]:
    """End-to-end golden check against the repo-owned fixtures — no
    external dataset or reference tree required.

    Two numbers per run through the SAME batched prediction path as the
    real datasets: ``golden_parity_epe`` (this build vs the stored
    canonical-torch outputs produced with identical weights — the
    cross-framework correctness claim, should be float-noise) and
    ``golden_gt_epe`` (vs the exact synthetic GT — exercises the EPE
    machinery; with the fixture's random weights this is large and only
    meaningful as a regression pin)."""
    # Guard every entry point (CLI, train --validation): a size-variant
    # mismatch doesn't crash (flows are full-res either way), it just
    # logs garbage parity numbers.
    model_cfg = getattr(predictor.model, "config", None)
    if model_cfg is not None and hasattr(model_cfg, "small"):
        if bool(model_cfg.small) != (variant == "small"):
            raise ValueError(
                f"golden variant {variant!r} vs model small="
                f"{model_cfg.small}: the goldens are recorded per model "
                "size (use golden_small with the small model)")
    root = root or ASSETS_DIR
    fixture = _GoldenFixture(root, variant=variant)
    want = fixture.manifest["iters"]
    if predictor.iters != want:
        print(f"WARNING: golden outputs recorded at iters={want}, "
              f"predictor runs iters={predictor.iters}; parity EPE is "
              f"only meaningful at the recorded count")
    parity, gt_epes = [], []
    for _, sample, flow in _reported_pass(predictor, fixture):
        parity.append(float(_epe_map(flow, sample[3]).mean()))
        gt_epes.append(float(_epe_map(flow, sample[2]).mean()))
    key = "golden" if variant == "large" else f"golden_{variant}"
    results = {f"{key}_parity_epe": float(np.mean(parity)),
               f"{key}_gt_epe": float(np.mean(gt_epes))}
    print(f"Validation Golden[{variant}]: parity EPE "
          f"{results[f'{key}_parity_epe']:.6f}, "
          f"GT EPE {results[f'{key}_gt_epe']:.4f}")
    return results


def validate_golden_small(predictor: FlowPredictor,
                          root=None) -> Dict[str, float]:
    """RAFT-small golden check (BASELINE configs[0]); the predictor must
    be built with ``small=True`` and ``assets/golden/weights_small.npz``."""
    return validate_golden(predictor, root=root, variant="small")


_VALIDATORS["golden"] = validate_golden
_VALIDATORS["golden_small"] = validate_golden_small


def run_validation(predictor: FlowPredictor, names) -> Dict[str, float]:
    """Dispatch by dataset name — the train loop's periodic validation hook
    (reference ``train.py:402-409``)."""
    results: Dict[str, float] = {}
    for name in names:
        results.update(_VALIDATORS[name](predictor))
    return results


def load_predictor(model_path: str, small: bool = False,
                   alternate_corr: bool = False,
                   mixed_precision: bool = False,
                   iters: int = 32,
                   model_family: str = "raft",
                   corr_dtype: Optional[str] = None,
                   spatial_shards: int = 1,
                   corr_impl: Optional[str] = None) -> FlowPredictor:
    """Build a :class:`FlowPredictor` from a checkpoint — torch ``.pth``
    (published reference weights, converted) or an orbax run directory
    (the reference ``evaluate.py:312-313`` model-loading path).

    ``model_path="random"`` skips checkpoint loading and uses randomly
    initialized weights — a pipeline smoke-test mode for hosts without
    downloaded checkpoints (outputs are meaningless flow).

    ``corr_impl=None`` resolves to ``"auto"`` for unsharded canonical-
    RAFT eval — the round-4 default flip (VERDICT r3 #4): the on-demand
    kernel measured faster than the materialized volume at every
    operating point (84.3 vs 56.1 pairs/s Sintel b24, 22.2 vs 18.4
    KITTI b1 — BASELINE.md), so eval picks it wherever the padded shape
    fits VMEM — including spatially-sharded eval (round 5: shard_map
    composition). Other families and explicit engine/storage selections
    (``alternate_corr``, ``corr_dtype``) resolve to ``"fixed"`` so
    those levers are honored as passed."""
    from raft_tpu import checkpoint as ckpt_lib
    from raft_tpu.config import RAFTConfig

    family = family_of(model_family)
    if family.tokens:
        raise ValueError(
            f"the {model_family} family trains only; a predictor needs "
            f"one of {FLOW_FAMILIES}")
    if corr_impl is None:
        # Mirror resolve_train_corr_engine: an explicit engine/storage
        # selection (--alternate_corr, --corr_dtype) pins "fixed" (use
        # the model exactly as configured) so the lever keeps its
        # meaning; only the no-selection default auto-dispatches.
        if alternate_corr or corr_dtype is not None:
            corr_impl = "fixed"
        else:
            # spatially-sharded eval auto-dispatches too since round 5:
            # the banded kernel composes with row sharding via shard_map
            # (falls back to the materialized engine per shape when rows
            # don't divide or VMEM doesn't admit the kernel)
            corr_impl = "auto" if family.raft_options else "fixed"
    if not family.raft_options:
        dropped = [name for name, on in _raft_only_selections(
            small, alternate_corr, corr_dtype) if on]
        if dropped:
            raise ValueError(
                f"{', '.join(dropped)} appl"
                f"{'ies' if len(dropped) == 1 else 'y'} to the canonical "
                f"RAFT family only; the {model_family} family is built "
                "from its own config and would silently ignore "
                f"{'it' if len(dropped) == 1 else 'them'}")
    if not family.torch_weights and model_path.endswith(
            (".pth", ".pt", ".npz")):
        raise ValueError(
            "torch-checkpoint conversion covers the canonical RAFT "
            f"family only (no published {model_family} weights "
            "exist); load this family from an orbax run directory")
    # the selections refused above stand at RAFTConfig's defaults
    model = family.build(RAFTConfig(
        small=small, alternate_corr=alternate_corr,
        mixed_precision=mixed_precision, corr_dtype=corr_dtype or "auto"))

    mesh = None
    if spatial_shards > 1:
        # sequence(spatial)-parallel eval: image rows over this many
        # chips (canonical family only — token-flattened families
        # partition pathologically over the spatial axis); the padded
        # height isn't known until the first frame, so divisibility is
        # checked per-shape in FlowPredictor._fn
        from raft_tpu.parallel import make_mesh
        from raft_tpu.parallel.mesh import validate_spatial_shards
        validate_spatial_shards(spatial_shards, model_family)
        mesh = make_mesh(n_data=1, n_spatial=spatial_shards,
                         devices=jax.devices()[:spatial_shards])

    if model_path == "random":
        rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, 64, 64, 3), jnp.float32)
        variables = model.init({"params": rng, "dropout": rng},
                               dummy, dummy, iters=1)
        return FlowPredictor(model, variables, iters=iters, mesh=mesh,
                             corr_impl=corr_impl)
    if model_path.endswith(".npz"):
        # torch-keyed npz archive (e.g. assets/golden/weights.npz) —
        # conversion without needing torch installed
        from raft_tpu.utils.torch_convert import convert_state_dict
        # fixture archives store fp16-rounded values; compute runs f32
        state = {k: np.asarray(v, np.float32)
                 for k, v in np.load(model_path).items()}
        variables = convert_state_dict(state)
        return FlowPredictor(model, variables, iters=iters, mesh=mesh,
                             corr_impl=corr_impl)
    params, batch_stats = ckpt_lib.load_params(model_path)
    variables = {"params": params}
    if batch_stats:
        variables["batch_stats"] = batch_stats
    return FlowPredictor(model, variables, iters=iters, mesh=mesh,
                             corr_impl=corr_impl)


def _raft_only_selections(small, alternate_corr, corr_dtype):
    """The single source of truth for options that configure only the
    canonical RAFT family: ``(name, non-default?)`` pairs.

    ``corr_dtype`` uses the explicit-selection convention: the CLIs (and
    :func:`load_predictor`) default it to ``None`` and resolve to "auto"
    only after this check, so an explicitly passed ``--corr_dtype
    float32`` on a non-RAFT family is rejected rather than silently
    treated as the default."""
    return (("small", small),
            ("alternate_corr", alternate_corr),
            ("corr_dtype", corr_dtype is not None))


def reject_raft_only_flags(parser, args) -> None:
    """Upfront CLI validation shared by train.py, evaluate.py and
    demo.py: flags that only configure the canonical RAFT family must
    not be silently dropped when another family builds from its own
    config.  ``--iters`` (``default=None`` in every CLI) is included —
    every non-raft family fixes its iteration count architecturally."""
    if family_of(args.model_family).raft_options:
        return
    for name, on in _raft_only_selections(args.small, args.alternate_corr,
                                          args.corr_dtype):
        if on:
            parser.error(f"--{name} applies to the canonical RAFT family "
                         f"only (the {args.model_family} family has no "
                         "small variant and fixed corr semantics)")
    if getattr(args, "iters", None) is not None:
        parser.error("--iters applies to the canonical RAFT family only "
                     f"(the {args.model_family} family's iteration count "
                     "is fixed by its architecture)")


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        description="Validate / create submissions (reference "
                    "evaluate.py:303-329).")
    parser.add_argument("--model", required=True,
                        help="torch .pth, orbax checkpoint dir, or 'random' "
                             "(pipeline smoke test, random weights)")
    parser.add_argument("--dataset", required=True,
                        choices=list(_VALIDATORS) + ["sintel_submission",
                                                     "kitti_submission"])
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--model_family", default="raft",
                        choices=list(FLOW_FAMILIES))
    parser.add_argument("--iters", type=int, default=None)
    parser.add_argument("--alternate_corr", action="store_true")
    parser.add_argument("--mixed_precision", action="store_true")
    parser.add_argument("--warm_start", action="store_true")
    parser.add_argument("--corr_dtype", default=None,
                        choices=["float32", "bfloat16", "auto"],
                        help="storage dtype of the correlation pyramid "
                             "(float32 = reference autocast semantics; "
                             "bfloat16 halves its HBM footprint)")
    parser.add_argument("--spatial_shards", type=int, default=1,
                        help="shard image rows over this many chips "
                             "(sequence-parallel eval for resolutions "
                             "whose correlation volume exceeds one "
                             "chip's HBM; canonical family only; "
                             "indivisible padded heights are edge-"
                             "padded to the least multiple of "
                             "spatial_shards*8 and cropped back; "
                             "composes with --warm_start — the init "
                             "flow carries its own row-sharding spec)")
    parser.add_argument("--corr_impl", default=None,
                        choices=["fixed", "auto"],
                        help="correlation engine for canonical-RAFT eval:"
                             " 'auto' (the default for unsharded "
                             "canonical-RAFT eval since the round-4 "
                             "measurements) picks the fused on-demand "
                             "Pallas kernel per padded shape wherever "
                             "it fits VMEM (measured 1.5x faster at "
                             "Sintel, 1.2x at KITTI on TPU v5e), "
                             "'fixed' honors --alternate_corr as given")
    parser.add_argument("--data_root", default=None)
    parser.add_argument("--output_path", default=None)
    args = parser.parse_args(argv)
    from raft_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    default_iters = {"chairs": 24, "kitti": 24, "sintel": 32,
                     "sintel_occ": 32, "sintel_submission": 32,
                     "kitti_submission": 24,
                     # fixture goldens are recorded at iters=12
                     # (assets/golden/manifest.json)
                     "golden": 12, "golden_small": 12}
    if args.dataset == "golden_small" and not args.small:
        parser.error("--dataset golden_small compares against RAFT-small "
                     "goldens; pass --small (and the small weights)")
    if args.dataset == "golden" and args.small:
        parser.error("--dataset golden compares against RAFT-large "
                     "goldens; use --dataset golden_small for --small")
    if args.warm_start and not family_of(args.model_family).flow_init:
        parser.error("--warm_start requires the canonical RAFT family "
                     f"(the {args.model_family} family does not support "
                     "flow_init)")
    reject_raft_only_flags(parser, args)   # incl. --iters
    iters = args.iters or default_iters[args.dataset]
    predictor = load_predictor(args.model, small=args.small,
                               alternate_corr=args.alternate_corr,
                               mixed_precision=args.mixed_precision,
                               iters=iters,
                               model_family=args.model_family,
                               corr_dtype=args.corr_dtype,
                               spatial_shards=args.spatial_shards,
                               corr_impl=args.corr_impl)
    if args.dataset == "sintel_submission":
        create_sintel_submission(
            predictor, warm_start=args.warm_start,
            output_path=args.output_path or "sintel_submission",
            root=args.data_root)
    elif args.dataset == "kitti_submission":
        create_kitti_submission(
            predictor, output_path=args.output_path or "kitti_submission",
            root=args.data_root)
    else:
        _VALIDATORS[args.dataset](predictor, root=args.data_root)


if __name__ == "__main__":
    main()
