"""Canonical RAFT (Teed & Deng, ECCV 2020) as a jittable flax module.

Semantics follow reference ``core/raft.py`` with the original (pre-fork)
dependencies restored: pixel-coordinate grids, 4-level correlation pyramid,
``extractor_origin`` encoders. The 12-iteration refinement loop is a single
``nn.scan`` (→ ``lax.scan``) with per-iteration gradient cut on the carried
coordinates — ``stop_gradient`` here corresponds to ``coords1.detach()`` at
reference ``core/raft.py:124``; gradients flow only through each iteration's
delta, which is a training-dynamics property, not an optimization.

TPU mapping: fnet/cnet and the all-pairs correlation pyramid are the
scan-invariant prologue (MXU matmuls), the scan body is the ConvGRU update;
everything is static-shaped, so XLA compiles one fused program. Inside the
scan body the per-iteration hot paths have Pallas kernels behind
trace-time env flags: the correlation lookup (``RAFT_CORR_BACKEND``,
``ops/corr_pallas.py``) and — for the non-small model — the SepConvGRU
cell (``RAFT_GRU_PALLAS``, ``ops/gru_pallas.py``), which fuses both GRU
steps into one launch so gate activations never round-trip HBM, and the
BasicMotionEncoder chain (``RAFT_MOTION_PALLAS``,
``ops/motion_pallas.py``), which fuses its five convs the same way and
hands the GRU its x input un-concatenated. ``RAFT_STEP_PALLAS``
(``ops/step_pallas.py``) goes one further and chains motion encoder →
SepConvGRU (→ flow head where admissible) into a SINGLE launch per
iteration with the [motion‖flow] handoff VMEM-resident — it subsumes
the two per-kernel flags where it admits, and falls back loudly to the
two-launch chain where it doesn't. The
flags are read when the scan body is traced, so a jitted executable bakes
one dispatch for all iterations (the serving warmup contract depends on
this — see ``serving/engine.py``); the hidden-state carry crosses the
kernel boundary in its own layout and dtype (``ops/layout.py``
invariant 4), keeping the scan free of per-iteration relayout copies.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.config import RAFTConfig
from raft_tpu.models import corr
from raft_tpu.models.extractor import BasicEncoder, SmallEncoder
from raft_tpu.models.normalize import normalize_image
from raft_tpu.models.update import BasicUpdateBlock, SmallUpdateBlock
from raft_tpu.ops.sampling import convex_upsample, coords_grid, upflow8


class _UpdateStep(nn.Module):
    """One refinement iteration, the ``lax.scan`` body
    (reference ``core/raft.py:123-140``).

    ``early_exit``: optional static ``(tol, patience)`` pair enabling
    per-sample convergence masking in the test_mode mask-free loop (see
    ``__call__``). ``None`` (the default) leaves the body byte-for-byte
    identical to the plain scan — the disabled path is not a runtime
    branch, the masking code is statically absent from the trace.
    """

    config: RAFTConfig
    early_exit: Optional[Tuple[float, int]] = None
    # Continuous-batching hook: when True, the mask-free test_mode
    # branch returns this iteration's float32 delta-flow as a scan
    # output instead of () — the step-granular scheduler computes its
    # convergence test OUTSIDE the module (refine_chunk), on exactly
    # the value the in-scan masked branch would have used, so the two
    # paths agree bit-for-bit on when a sample converged. Static field:
    # the default keeps every existing trace byte-identical.
    emit_delta: bool = False

    def setup(self):
        dtype = (jnp.bfloat16 if self.config.mixed_precision
                 else jnp.float32)
        if self.config.small:
            self.update_block = SmallUpdateBlock(self.config.hdim, dtype)
        else:
            self.update_block = BasicUpdateBlock(self.config.hdim, dtype)

    def __call__(self, carry, _tick, compute_up, corr_state, inp,
                 coords0):
        """``compute_up``: Python ``True`` (upsample this iteration —
        training, and the single final test_mode call) or ``None``
        (test_mode non-final iterations: the mask head and upsampling
        are statically ABSENT from the loop body — no ``nn.cond``, no
        mask in the carry; the round-5 two-call scan structure, see
        ``RAFT.__call__``). ``_tick`` is a dummy scanned input that
        sets the trip count (``nn.scan(length=None)``), letting ONE
        lifted scan instance — one parameter scope — serve both call
        lengths.

        With ``early_exit=(tol, patience)`` set, the mask-free test_mode
        branch carries ``(net, coords1, consec, done, used)`` instead of
        ``(net, coords1)``: every iteration still computes the update
        (the scan stays one static-shaped executable — the win is
        accounting and a stable numeric contract, not wall-clock on a
        dense batch), but a sample whose low-res delta-flow norm has sat
        below ``tol`` for ``patience`` consecutive iterations is frozen
        — its ``net``/``coords1`` stop advancing, so its result is the
        value it converged to, independent of how many further
        iterations the rest of the batch needs. ``used`` counts the
        iterations each sample actually consumed."""
        masked = (self.early_exit is not None and compute_up is None
                  and not self.is_initializing())
        if masked:
            net_prev, coords1_prev, consec, done, used = carry
        else:
            net_prev, coords1_prev = carry
        with jax.named_scope("coords"):
            coords1 = jax.lax.stop_gradient(coords1_prev)
        corr = _lookup(self.config, corr_state, coords1)
        corr = corr.astype(net_prev.dtype)
        with jax.named_scope("coords"):
            flow = (coords1 - coords0).astype(net_prev.dtype)
        net, up_mask, delta_flow = self.update_block(
            net_prev, inp, corr, flow, compute_mask=compute_up)
        with jax.named_scope("coords"):
            coords1 = coords1 + delta_flow.astype(jnp.float32)
            new_flow = coords1 - coords0

        if masked:
            tol, patience = self.early_exit
            # Per-sample mean L2 norm of this iteration's low-res delta
            # — the paper's convergence signal: RAFT's updates shrink
            # monotonically toward the fixed point, so a plateau below
            # tol is a stable stop criterion.
            delta32 = delta_flow.astype(jnp.float32)
            delta_norm = jnp.sqrt(
                jnp.mean(jnp.sum(delta32 * delta32, axis=-1),
                         axis=(1, 2)))
            below = delta_norm < jnp.float32(tol)
            consec = jnp.where(done, consec,
                               jnp.where(below, consec + 1, 0))
            keep = done[:, None, None, None]
            # Freeze on the PREVIOUS done flag: the iteration on which a
            # sample converges still applies its (sub-tol) update; only
            # later iterations are masked out.
            net = jnp.where(keep, net_prev, net)
            coords1 = jnp.where(keep, coords1_prev, coords1)
            used = used + jnp.where(done, 0, 1).astype(jnp.int32)
            done = done | (consec >= patience)
            return (net, coords1, consec, done, used), ()

        if compute_up is None and not self.is_initializing():
            # test_mode non-final: no mask, no upsample, no per-
            # iteration outputs (unless the continuous scheduler asked
            # for the delta — see emit_delta).
            if self.emit_delta:
                return (net, coords1), delta_flow.astype(jnp.float32)
            return (net, coords1), ()
        # Training / init / final test_mode iteration: upsampled flow
        # is a scan output (the sequence loss consumes all of them; the
        # test_mode caller takes the single stacked entry).
        with jax.named_scope("upsample"):
            if up_mask is None:
                flow_up = upflow8(new_flow)
            else:
                flow_up = convex_upsample(new_flow,
                                          up_mask.astype(jnp.float32))
        return (net, coords1), flow_up


def _build_corr_state(cfg: RAFTConfig, fmap1, fmap2, inference: bool):
    """Precompute the scan-invariant correlation state.

    All-pairs mode: the pooled 4D-volume pyramid (tuple of arrays).
    Alternate mode: fmap1 + the pooled fmap2 pyramid (tuple of arrays),
    or on the unsharded kernel route both in the kernel's own layout
    (``corr.alternate_operands``).
    Returned as plain pytrees so they can cross ``nn.scan`` as broadcast
    arguments. ``inference`` resolves both "auto" dtype levers (bf16
    volume storage / bf16 MXU operands are inference-only; training keeps
    the reference's autocast-exempt f32 correlation *computation* — the
    reference casts fmaps to f32 before either corr path,
    ``core/raft.py:103-104``). The lookup's *output handoff* dtype is a
    separate, numerics-neutral knob: under mixed precision the update
    block always cast the windows to bf16 anyway, so the kernel emits
    bf16 directly (bit-identical single rounding, training included) to
    skip the custom-call-boundary convert. The resolved MXU dtype, a
    differentiable flag (training → the kernel-dispatch gate budgets
    VMEM for the backward too) and the output dtype ride in the state
    tuple as static values alongside the "alt"/"allpairs" tag.
    """
    kind, meta = corr_state_meta(cfg, inference)
    with jax.named_scope("corr_build"):
        if kind == "alt":
            # the lookup's operands in the layout it reads them, once a
            # pair rather than once an iteration
            return (kind, meta, corr.alternate_operands(
                fmap1, corr.build_feature_pyramid(fmap2, cfg.corr_levels),
                cfg.radius, differentiable=meta[1]))
        return (kind, meta,
                corr.build_corr_pyramid(
                    fmap1, fmap2, cfg.corr_levels, cfg.corr_scale,
                    cfg.corr_storage(inference)))


def corr_state_meta(cfg: RAFTConfig, inference: bool):
    """The STATIC prefix of a correlation state tuple — ``(kind,
    (mxu_dtype, differentiable, out_dtype))`` — separated from the array
    payload so the step-granular dispatch family can keep only the
    payload device-resident in its carry (strings and bools can't cross
    a jit boundary) and rebuild the full state per executable."""
    if cfg.alternate_corr:
        # out dtype = the update block's compute dtype: the lookup's
        # consumer casts to it anyway (corr.astype(net.dtype)), and
        # emitting it from inside the kernel skips the convert+copy at
        # the custom-call boundary.
        out_dt = "bfloat16" if cfg.mixed_precision else "float32"
        return "alt", (cfg.corr_mxu(inference), not inference, out_dt)
    return "allpairs", ("float32", not inference, "float32")


def _lookup(cfg: RAFTConfig, corr_state, coords):
    kind, (mxu_dtype, differentiable, out_dt), payload = corr_state
    with jax.named_scope("corr_lookup"):
        if kind == "alt":
            fmap1, pyramid2 = payload
            return corr.alternate_lookup(fmap1, pyramid2, coords,
                                         cfg.radius, cfg.corr_scale,
                                         mxu_dtype=mxu_dtype,
                                         differentiable=differentiable,
                                         out_dtype=jnp.dtype(out_dt))
        return corr.pyramid_lookup(payload, coords, cfg.radius)


class RAFT(nn.Module):
    """Full RAFT model: encoders + correlation + scanned refinement.

    ``__call__`` mirrors reference ``core/raft.py:87-145``:
      images in [0, 255] NHWC uint8/float; returns all per-iteration
      upsampled flows ``(iters, B, 8H', 8W', 2)`` for training, or
      ``(flow_low, flow_up)`` when ``test_mode``.
    """

    config: RAFTConfig = RAFTConfig()

    def setup(self):
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        if cfg.small:
            self.fnet = SmallEncoder(128, "instance", cfg.dropout,
                                     dtype=dtype)
            self.cnet = SmallEncoder(cfg.hdim + cfg.cdim, "none", cfg.dropout,
                                     dtype=dtype)
        else:
            self.fnet = BasicEncoder(cfg.fnet_dim, "instance", cfg.dropout,
                                     dtype=dtype)
            self.cnet = BasicEncoder(cfg.hdim + cfg.cdim, "batch",
                                     cfg.dropout, dtype=dtype)

    def encode_features(self, image):
        """Feature-encoder (fnet) pass alone, inference mode: [0, 255]
        NHWC image → feature map at 1/8 resolution.

        The streaming serving path uses this as its own jitted entry
        point: for a temporally coherent stream, frame t's ``fmap2`` is
        frame t+1's ``fmap1``, so each warm frame needs exactly ONE
        encoder pass plus a cached map handed to ``__call__`` via the
        ``fmap1``/``fmap2`` kwargs. fnet uses instance norm (per-sample
        statistics), so encoding images separately is mathematically
        identical to the twin-image concatenated pass in ``__call__`` —
        parity is executable-level, not bit-exact, hence the tolerance
        tests in tests/test_streaming.py.
        """
        dtype = (jnp.bfloat16 if self.config.mixed_precision
                 else jnp.float32)
        x = normalize_image(image, dtype)
        return self.fnet(x, train=False, deterministic=True)

    def refine_init(self, image1, image2=None, fmap1=None, fmap2=None,
                    flow_init=None):
        """The scan-invariant prologue of the refinement loop as its own
        inference entry point: encoders + correlation state + context,
        returned as an ALL-ARRAY carry dict — the slot table of the
        continuous (step-granular) serving scheduler.

        Like :meth:`encode_features` this is a plain method (setup-built
        submodules only; ``__call__`` keeps the single ``@nn.compact``
        slot), so it composes under one ``model.apply``. The carry holds
        only array leaves — the correlation state's static ``(kind,
        meta)`` prefix is rebuilt per executable via
        :func:`corr_state_meta` — and crosses jit boundaries between
        launches under buffer donation. Keys: ``net``/``inp`` (context
        split), ``coords0``/``coords1`` (float32 pixel grids),
        ``corr`` (engine payload pytree), ``consec``/``done``/``used``
        (per-slot early-exit accounting, zeroed here)."""
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        if (fmap1 is None) != (fmap2 is None):
            raise ValueError("fmap1 and fmap2 must be given together")
        image1 = normalize_image(image1, dtype)
        if fmap1 is None:
            image2 = normalize_image(image2, dtype)
            fmaps = self.fnet(jnp.concatenate([image1, image2], axis=0),
                              train=False, deterministic=True)
            fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        else:
            fmap1 = fmap1.astype(dtype)
            fmap2 = fmap2.astype(dtype)
        corr_state = _build_corr_state(cfg, fmap1, fmap2, inference=True)
        cnet_out = self.cnet(image1, train=False, deterministic=True)
        net, inp = jnp.split(cnet_out, [cfg.hdim], axis=-1)
        net = jnp.tanh(net)
        inp = nn.relu(inp)
        B, H8, W8, _ = fmap1.shape
        coords0 = coords_grid(B, H8, W8)
        coords1 = coords0
        if flow_init is not None:
            coords1 = coords1 + flow_init
        return {
            "net": net,
            "inp": inp,
            "coords0": coords0,
            "coords1": coords1,
            "corr": corr_state[2],
            "consec": jnp.zeros((B,), jnp.int32),
            "done": jnp.zeros((B,), bool),
            "used": jnp.zeros((B,), jnp.int32),
        }

    @nn.compact
    def __call__(self, image1, image2, iters: Optional[int] = None,
                 flow_init=None, test_mode: bool = False,
                 train: bool = False, freeze_bn: bool = False,
                 fmap1=None, fmap2=None,
                 early_exit: Optional[Tuple[float, int]] = None):
        """``freeze_bn`` keeps BatchNorm in eval (running-average) mode
        while the rest trains — the reference's post-chairs freeze
        (``core/raft.py:60-63``, ``train.py:414-415``).

        ``fmap1``/``fmap2``: precomputed feature maps (both or neither,
        from :meth:`encode_features`). When given, the fnet pass is
        skipped entirely and ``image2`` may be ``None`` — the
        refine-only entry point of the streaming serving path.

        ``early_exit``: static ``(tol, patience)`` enabling per-sample
        convergence masking in the test_mode refine loop (see
        ``_UpdateStep``). test_mode-only; when set the return becomes
        ``(flow_low, flow_up, iters_used)`` with ``iters_used`` an
        ``(B,)`` int32 of refinement iterations each sample actually
        consumed (the final mask-computing iteration always runs and is
        included). ``None`` (default) leaves every code path and output
        byte-identical to before the knob existed."""
        cfg = self.config
        norm_train = train and not freeze_bn
        iters = iters if iters is not None else cfg.iters
        if iters < 1:
            # the two-call test_mode scan always runs the final
            # mask-computing iteration; iters=0 has no meaning in the
            # reference either (its range(iters) loop just never ran,
            # returning the uninitialized flow)
            raise ValueError(f"iters must be >= 1, got {iters}")
        if cfg.normalized_coords:
            # [0,1]-normalized grids serve the sparse-keypoint ("ours")
            # family; RAFT's correlation lookup and upsampling are
            # pixel-unit. Fail loudly rather than produce garbage.
            raise ValueError("normalized_coords is not supported by the "
                             "canonical RAFT path")

        if (fmap1 is None) != (fmap2 is None):
            raise ValueError("fmap1 and fmap2 must be given together")

        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        image1 = normalize_image(image1, dtype)

        if fmap1 is None:
            image2 = normalize_image(image2, dtype)
            # Twin-image trick: one fnet pass over both images
            # concatenated on the batch axis (reference
            # extractor_origin.py:168-171).
            fmaps = self.fnet(jnp.concatenate([image1, image2], axis=0),
                              train=norm_train, deterministic=not train)
            fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        else:
            fmap1 = fmap1.astype(dtype)
            fmap2 = fmap2.astype(dtype)

        corr_state = _build_corr_state(cfg, fmap1, fmap2,
                                       inference=bool(test_mode))

        cnet_out = self.cnet(image1, train=norm_train,
                             deterministic=not train)
        net, inp = jnp.split(cnet_out, [cfg.hdim], axis=-1)
        net = jnp.tanh(net)
        inp = nn.relu(inp)

        B, H8, W8, _ = fmap1.shape
        with jax.named_scope("coords"):
            coords0 = coords_grid(B, H8, W8)
            coords1 = coords0
            if flow_init is not None:
                coords1 = coords1 + flow_init

        # In test_mode only the last iteration computes the (expensive)
        # upsampling-mask head and convex upsampling; training needs every
        # intermediate upsampled flow for the sequence loss.
        last_only = test_mode and not self.is_initializing()
        if early_exit is not None and not test_mode:
            raise ValueError("early_exit is a test_mode-only knob")
        ee = early_exit if last_only else None
        carry = (net, coords1)
        # length=None: the trip count comes from the scanned dummy
        # tick, so the SAME lifted instance (one "update" parameter
        # scope) runs both the (iters-1)-long mask-free loop and the
        # single mask-computing final call in test_mode — statically,
        # with no nn.cond and no mask buffer in the carry (the round-4
        # structure cost ~1 ms/iteration of conditional plumbing at
        # b64, the round-5 profile's cond.2 row).
        scan = nn.scan(
            _UpdateStep,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                     nn.broadcast),
            out_axes=0,
            length=None,
        )(cfg, ee, name="update")

        if last_only:
            if ee is not None:
                consec = jnp.zeros((B,), jnp.int32)
                done = jnp.zeros((B,), bool)
                used = jnp.zeros((B,), jnp.int32)
                carry = (net, coords1, consec, done, used)
                if iters > 1:
                    carry, _ = scan(carry, jnp.zeros(iters - 1), None,
                                    corr_state, inp, coords0)
                net, coords1, consec, done, used = carry
                carry = (net, coords1)
                carry, flow_up = scan(carry, jnp.zeros(1), True,
                                      corr_state, inp, coords0)
                net, coords1 = carry
                flow_low = coords1 - coords0
                # The mask-computing final iteration runs for every
                # sample (one executable, one upsample), hence +1.
                return flow_low, flow_up[0], used + 1
            if iters > 1:
                carry, _ = scan(carry, jnp.zeros(iters - 1), None,
                                corr_state, inp, coords0)
            carry, flow_up = scan(carry, jnp.zeros(1), True,
                                  corr_state, inp, coords0)
            net, coords1 = carry
            flow_low = coords1 - coords0
            return flow_low, flow_up[0]

        carry, flow_predictions = scan(
            carry, jnp.zeros(iters), True, corr_state, inp, coords0)
        net, coords1 = carry
        if test_mode:
            # init-time test_mode (static path): all iterations upsample.
            return coords1 - coords0, flow_predictions[-1]
        return flow_predictions


# -- step-granular (continuous batching) refine family -------------------
#
# The monolithic test_mode loop runs all k iterations in ONE executable;
# the continuous serving scheduler instead drives the SAME update block
# in fixed-size chunks over a slot-table carry (refine_init's dict),
# masking each slot by its own remaining-iterations budget and its
# early-exit flag. These are module-level pure functions (not RAFT
# methods): they apply a standalone _UpdateStep against the
# ``variables["params"]["update"]`` subtree — structurally identical to
# the nn.scan-lifted "update" scope because ``variable_broadcast=
# "params"`` stores the body's params unstacked — so the scheduler never
# needs the full model apply (no fnet/cnet in the step executable).


def _update_variables(variables):
    """The refine body's own variable tree, sliced out of the full
    model's: the scan lift stores the update block's params unstacked
    under the broadcast "update" scope, so a standalone _UpdateStep
    apply accepts them as-is."""
    return {"params": variables["params"]["update"]}


def scatter_carry(full, fresh, idx, slots: int):
    """Write ``fresh`` (a refine_init carry over ``m`` admitted samples)
    into slot rows ``idx`` of ``full`` (the ``slots``-wide table).

    Leaf-wise ``.at[idx].set``; leaves whose leading dim folds batch
    with spatial rows (the all-pairs correlation pyramid levels are
    ``(B*H8*W8, h, w)``) are reshaped to expose the slot axis first.
    Duplicate indices in ``idx`` (tail-padded admissions repeat the
    last real one) write identical values, so the scatter stays
    deterministic."""
    m = int(idx.shape[0])

    def _scat(f, n):
        lead = f.shape[0]
        if lead == slots:
            return f.at[idx].set(n.astype(f.dtype))
        per = lead // slots
        fr = f.reshape(slots, per, *f.shape[1:])
        nr = n.reshape(m, per, *n.shape[1:])
        return fr.at[idx].set(nr.astype(f.dtype)).reshape(f.shape)

    return jax.tree_util.tree_map(_scat, full, fresh)


def refine_chunk(cfg: RAFTConfig, variables, carry, remaining,
                 steps: int, early_exit: Optional[Tuple[float, int]]):
    """Run ``steps`` masked refinement iterations over a slot carry.

    ``remaining`` is the per-slot (slots,) int32 budget of mask-free
    iterations still owed (a request served at ``iters=k`` owes ``k-1``
    here plus the one mask-computing :func:`refine_finalize` pass — the
    monolithic two-call scan structure, so flow parity holds per
    request). A slot is *active* while it has budget and isn't done;
    inactive slots are frozen exactly like the in-scan masked branch
    (the update is computed — one static executable — but not applied),
    so a retired slot's value is independent of how long it stays
    resident. Returns ``(carry', remaining')``.

    Ordering matches _UpdateStep's masked branch bit-for-bit: consec
    updates on this iteration's delta, freeze on the PREVIOUS done
    flag (here: the active mask), ``used`` ticks before ``done`` absorbs
    the patience test."""
    step = _UpdateStep(cfg, None, emit_delta=True)
    upd_vars = _update_variables(variables)
    kind, meta = corr_state_meta(cfg, inference=True)
    inp, coords0 = carry["inp"], carry["coords0"]
    corr_state = (kind, meta, carry["corr"])

    def body(c, _):
        net, coords1, consec, done, used, rem = c
        (net2, coords12), delta32 = step.apply(
            upd_vars, (net, coords1), jnp.zeros(()), None, corr_state,
            inp, coords0)
        active = jnp.logical_and(~done, rem > 0)
        if early_exit is not None:
            tol, patience = early_exit
            delta_norm = jnp.sqrt(
                jnp.mean(jnp.sum(delta32 * delta32, axis=-1),
                         axis=(1, 2)))
            below = delta_norm < jnp.float32(tol)
            consec = jnp.where(active,
                               jnp.where(below, consec + 1, 0), consec)
        keep = (~active)[:, None, None, None]
        net = jnp.where(keep, net, net2)
        coords1 = jnp.where(keep, coords1, coords12)
        tick = jnp.where(active, 1, 0).astype(jnp.int32)
        used = used + tick
        rem = rem - tick
        if early_exit is not None:
            done = done | (active & (consec >= patience))
        return (net, coords1, consec, done, used, rem), ()

    c0 = (carry["net"], carry["coords1"], carry["consec"],
          carry["done"], carry["used"],
          remaining.astype(jnp.int32))
    (net, coords1, consec, done, used, rem), _ = jax.lax.scan(
        body, c0, None, length=int(steps))
    out = dict(carry)
    out.update(net=net, coords1=coords1, consec=consec, done=done,
               used=used)
    return out, rem


def refine_finalize(cfg: RAFTConfig, variables, carry):
    """The mask-computing final iteration over ALL slots: one update +
    convex upsample, carry untouched (retiring slots read their result
    here while co-resident slots keep stepping). Returns ``(flow_low,
    flow_up)`` at the slot width. A request's full trajectory —
    ``k-1`` chunked iterations then this call — reproduces the
    monolithic two-call scan, so ``iters_used = carry["used"] + 1``."""
    step = _UpdateStep(cfg, None)
    upd_vars = _update_variables(variables)
    kind, meta = corr_state_meta(cfg, inference=True)
    corr_state = (kind, meta, carry["corr"])
    (net, coords1), flow_up = step.apply(
        upd_vars, (carry["net"], carry["coords1"]), jnp.zeros(()), True,
        corr_state, carry["inp"], carry["coords0"])
    return coords1 - carry["coords0"], flow_up
