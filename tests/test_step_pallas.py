"""Fused one-launch refine-iteration kernel suite (round-10 tentpole).

CPU interpret-mode parity for ``ops/step_pallas.py`` — the single
Pallas launch chaining motion encoder → SepConvGRU (→ flow head) — at
three levels:

* **vs the two-launch chain** (``motion_pallas.motion_encoder`` →
  ``gru_pallas.sepconv_gru``): BIT-exact at every row tile, both
  fusion depths. Same shifted-matmul taps, same masks, same cast
  points — fusing the handoff must not move a single bit.
* **vs the conv path** (``BasicUpdateBlock`` with all kernels off):
  within the ISSUE acceptance bounds (f32 forward ≤1e-5, grads ≤2e-4),
  forward and gradients, through the custom VJP and all three weight
  packers.
* **dispatch contract** (``RAFT_STEP_PALLAS``): '0' byte-identical,
  '1' forced (raises on TPU when inadmissible), auto fuses only on TPU
  with a LOUD logged fallback; plus the pinned VMEM admission table at
  the Sintel-eval operating point (phase-peak liveness model —
  bf16 admits TH=4 for 'mg' only; f32 admits nothing).
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops import gru_pallas, motion_pallas, step_pallas, vmem

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion/step kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret

B, H, W, CC = 2, 9, 7, 12
C = 128    # hidden/context channels
CO = 126   # motion fusing-conv width; handoff is [out(126) ‖ flow(2)]


def _pairs(params, *names):
    return tuple((params[n]["kernel"], params[n]["bias"]) for n in names)


def _packers(params):
    """(mmats, gmats, fmats) from a BasicUpdateBlock param tree — the
    same packers the fused dispatch path uses."""
    enc = params["encoder"]
    mmats = motion_pallas.pack_weights(*_pairs(
        enc, "convc1", "convc2", "convf1", "convf2", "conv"))
    gru = params["gru"]
    gmats = gru_pallas.pack_weights(
        _pairs(gru, "convz1", "convr1", "convq1"),
        _pairs(gru, "convz2", "convr2", "convq2"), C)
    fmats = step_pallas.pack_flow_head(*_pairs(
        params["flow_head"], "conv1", "conv2"))
    return mmats, gmats, fmats


@pytest.fixture(scope="module")
def update_setup():
    """Full BasicUpdateBlock + inputs at a deliberately awkward shape
    (odd W, H not a row-tile multiple, so every halo direction and the
    padded-row masks are live through the 9/11-row receptive field)."""
    from raft_tpu.models.update import BasicUpdateBlock

    model = BasicUpdateBlock()
    rng = np.random.default_rng(1)
    net = jnp.asarray(np.tanh(rng.standard_normal((B, H, W, C))),
                      jnp.float32)
    inp = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    corr = jnp.asarray(rng.standard_normal((B, H, W, CC)), jnp.float32)
    flow = jnp.asarray(3.0 * rng.standard_normal((B, H, W, 2)),
                       jnp.float32)
    vs = model.init(jax.random.PRNGKey(1), net, inp, corr, flow)
    return model, vs, net, inp, corr, flow


class TestForwardParity:
    @pytest.mark.parametrize("th", [4, 5, 8])
    @pytest.mark.parametrize("fh", [False, True])
    def test_fused_is_bitexact_vs_chained_kernels(self, update_setup,
                                                  th, fh):
        """The whole point of the fusion: identical arithmetic to the
        two-launch motion→GRU chain, with the handoff buffer gone. h2
        must not move a bit at ANY row tile (multi-neighbor halos at
        th=4 assemble ceil(11/4)=3 blocks per side for 'mgf')."""
        _, vs, net, inp, corr, flow = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        mot = motion_pallas.motion_encoder(flow, corr, mmats,
                                           interpret=True, th=th)
        want_h2 = gru_pallas.sepconv_gru(net, (inp, mot), gmats,
                                         interpret=True, th=th)
        out = step_pallas.fused_step(net, inp, corr, flow, mmats,
                                     gmats, fmats if fh else None,
                                     interpret=True, th=th)
        got_h2 = out[0] if fh else out
        np.testing.assert_array_equal(np.asarray(got_h2),
                                      np.asarray(want_h2))

    def test_mgf_delta_matches_conv_flow_head(self, update_setup):
        """The in-kernel flow head vs the flax FlowHead on the SAME h2
        (tap decomposition changes only the reduction order)."""
        from raft_tpu.models.update import FlowHead

        _, vs, net, inp, corr, flow = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        h2, delta = step_pallas.fused_step(net, inp, corr, flow, mmats,
                                           gmats, fmats, interpret=True)
        want = FlowHead(256).apply(
            {"params": vs["params"]["flow_head"]}, h2)
        np.testing.assert_allclose(np.asarray(delta), np.asarray(want),
                                   atol=1e-5, rtol=0)

    def test_reference_twin_matches_kernel(self, update_setup):
        """The pure-jnp twin (the VJP backward) reproduces the fused
        kernel — identical tap order/masks/cast points."""
        _, vs, net, inp, corr, flow = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        h2, delta = step_pallas.fused_step(net, inp, corr, flow, mmats,
                                           gmats, fmats, interpret=True)
        gm = gru_pallas.split_x_weights(gmats, (C, CO + 2))
        ref_h2, ref_delta = step_pallas.reference_step(
            (W, H), net.reshape(B, H * W, C), inp.reshape(B, H * W, C),
            flow.reshape(B, H * W, 2), corr.reshape(B, H * W, CC),
            mmats, gm, fmats)
        np.testing.assert_allclose(
            np.asarray(h2), np.asarray(ref_h2.reshape(B, H, W, C)),
            atol=1e-5, rtol=0)
        np.testing.assert_allclose(
            np.asarray(delta), np.asarray(ref_delta.reshape(B, H, W, 2)),
            atol=1e-5, rtol=0)

    @pytest.mark.parametrize("compute_mask", [True, None])
    def test_forced_matches_conv_path(self, update_setup, monkeypatch,
                                      compute_mask):
        """'1' through BasicUpdateBlock vs the all-conv path, both mask
        regimes: compute_mask=True runs the 'mg' depth (mask/flow heads
        stay XLA), None runs 'mgf' (delta in-kernel). f32 acceptance
        bound ≤1e-5."""
        model, vs, net, inp, corr, flow = update_setup
        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        want = model.apply(vs, net, inp, corr, flow,
                           compute_mask=compute_mask)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        got = model.apply(vs, net, inp, corr, flow,
                          compute_mask=compute_mask)
        for a, b in zip(got, want):
            if a is None and b is None:
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=0)

    def test_bf16_matches_conv_path(self, update_setup, monkeypatch):
        """bf16 compute dtype (the mixed-precision policy): both paths
        share the f32-accumulate → bf16-bias-add contract; the chain is
        ~11 convs deep, so allow a few bf16 ulp of the feature scale."""
        from raft_tpu.models.update import BasicUpdateBlock

        _, vs, net, inp, corr, flow = update_setup
        model16 = BasicUpdateBlock(dtype=jnp.bfloat16)
        args16 = tuple(a.astype(jnp.bfloat16)
                       for a in (net, inp, corr, flow))
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        want = model16.apply(vs, *args16, compute_mask=None)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        got = model16.apply(vs, *args16, compute_mask=None)
        for a, b in zip(got, want):
            if a is None and b is None:
                continue
            a32 = np.asarray(a, np.float32)
            b32 = np.asarray(b, np.float32)
            scale = float(np.max(np.abs(b32)))
            tol = 8 * float(jnp.finfo(jnp.bfloat16).eps) * max(scale, 1.0)
            np.testing.assert_allclose(a32, b32, atol=tol, rtol=0)


class TestGradParity:
    def test_input_grads_match_conv_path(self, update_setup,
                                         monkeypatch):
        """d(sum(h2)+sum(delta))/d{net, inp, corr, flow} through the
        custom VJP (recompute via the jnp twin) vs the conv path's
        autodiff — the ISSUE acceptance bound ≤2e-4."""
        model, vs, net, inp, corr, flow = update_setup

        def loss(n, i, c, f):
            h2, _, delta = model.apply(vs, n, i, c, f,
                                       compute_mask=None)
            return jnp.sum(h2) + jnp.sum(delta)

        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        g_conv = jax.grad(loss, argnums=(0, 1, 2, 3))(net, inp, corr,
                                                      flow)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        g_fused = jax.grad(loss, argnums=(0, 1, 2, 3))(net, inp, corr,
                                                       flow)
        for a, b in zip(g_conv, g_fused):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)

    def test_param_grads_flow_through_packers(self, update_setup,
                                              monkeypatch):
        """Gradients reach the flax param tree through all three weight
        packers (motion / GRU / flow head) — what training with the
        fused scan body relies on."""
        model, vs, net, inp, corr, flow = update_setup

        def loss(params):
            h2, _, delta = model.apply({"params": params}, net, inp,
                                       corr, flow, compute_mask=None)
            return jnp.sum(h2) + jnp.sum(delta)

        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        g_conv = jax.grad(loss)(vs["params"])
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        g_fused = jax.grad(loss)(vs["params"])
        for a, b in zip(jax.tree_util.tree_leaves(g_conv),
                        jax.tree_util.tree_leaves(g_fused)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)


class TestDispatch:
    def test_flag_off_is_bitexact(self, update_setup, monkeypatch):
        """RAFT_STEP_PALLAS=0 and unset-on-CPU (auto) both take the
        existing path through BasicUpdateBlock — bit-for-bit identical
        (the acceptance pin; the golden-EPE variant lives in
        test_golden.py)."""
        model, vs, net, inp, corr, flow = update_setup
        for f in ("RAFT_STEP_PALLAS", "RAFT_MOTION_PALLAS",
                  "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        auto = model.apply(vs, net, inp, corr, flow)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        off = model.apply(vs, net, inp, corr, flow)
        for a, b in zip(auto, off):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plan_fusion_modes(self, update_setup, monkeypatch):
        _, _, net, inp, corr, flow = update_setup
        plan = step_pallas.plan_fusion
        assert plan(net, inp, corr, flow, True, mode="0") is None
        # forced off-TPU: interpret-mode parity tooling, depth by need
        assert plan(net, inp, corr, flow, True, mode="1") == "mgf"
        assert plan(net, inp, corr, flow, False, mode="1") == "mg"
        # auto off-TPU: keep the XLA/chained path
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        assert plan(net, inp, corr, flow, True) is None

    def test_auto_on_tpu_steps_down_mgf_to_mg(self, monkeypatch):
        """Sintel-eval f32 on a (faked) TPU backend: the flow-head
        depth doesn't fit, so auto honestly steps down to 'mg' instead
        of rejecting fusion outright; Sintel bf16 admits 'mgf'."""
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c, dtype):
            return jax.ShapeDtypeStruct((1, 55, 128, c), dtype)

        args = tuple(sds(c, jnp.float32) for c in (C, C, 324, 2))
        assert step_pallas.plan_fusion(*args, True) == "mg"
        assert step_pallas.plan_fusion(*args, False) == "mg"
        half = tuple(sds(c, jnp.bfloat16) for c in (C, C, 324, 2))
        assert step_pallas.plan_fusion(*half, True) == "mgf"

    def test_forced_bad_shape_raises(self, update_setup):
        _, _, net, inp, corr, _ = update_setup
        bad_flow = jnp.zeros((B, H, W, 3), jnp.float32)
        with pytest.raises(ValueError, match="RAFT_STEP_PALLAS=1"):
            step_pallas.plan_fusion(net, inp, corr, bad_flow, True,
                                    mode="1")

    def test_forced_inadmissible_on_tpu_raises(self, monkeypatch):
        """'1' on a TPU backend must never silently degrade: when even
        the 'mg' depth fits no tile (a 1080p feature map), the forced
        arm dies loudly at trace time."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):
            return jax.ShapeDtypeStruct((1, 135, 240, c), jnp.float32)

        with pytest.raises(ValueError, match="admits no row tile"):
            step_pallas.plan_fusion(sds(C), sds(C), sds(324),
                                    sds(2), False, mode="1")

    def test_auto_fallback_is_logged_step(self, monkeypatch, caplog):
        """The satellite contract carried to the fused step: when auto
        on a TPU backend rejects a shape on the VMEM envelope, one loud
        structured warning names the flag, shape and budget — never a
        silent two-launch fallback."""
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):     # a 1080p feature map: too wide for any tile
            return jax.ShapeDtypeStruct((1, 135, 240, c), jnp.float32)

        with caplog.at_level(logging.WARNING,
                             logger="raft_tpu.ops.vmem"):
            assert step_pallas.plan_fusion(sds(C), sds(C), sds(324),
                                           sds(2), False) is None
        assert "RAFT_STEP_PALLAS=auto" in caplog.text
        assert "falling back to the XLA path" in caplog.text
        assert "H=135, W=240" in caplog.text
        assert "admission budget" in caplog.text

    @pytest.mark.multidevice
    @pytest.mark.parametrize("kernel", ["step", "motion", "gru", "msda"])
    def test_mesh_keeps_the_xla_path(self, kernel, monkeypatch, caplog):
        """GSPMD cannot partition a Mosaic kernel and the scan-body
        kernels have no shard_map wrapper: on a (faked) TPU backend
        under a kernel mesh of more than one device, auto keeps the XLA
        path and says so; a forced '1' raises; a one-device mesh and no
        mesh are unaffected."""
        from raft_tpu.parallel import make_mesh
        from raft_tpu.parallel.spatial import spatial_kernel_mesh
        for flag in ("RAFT_STEP_PALLAS", "RAFT_MOTION_PALLAS",
                     "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(flag, raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):
            return jax.ShapeDtypeStruct((2, 46, 62, c), jnp.bfloat16)

        def decide(mode=None):
            if kernel == "msda":
                from raft_tpu.ops import msda, msda_pallas
                if mode == "1":     # only auto consults the rule
                    raise ValueError("no shard_map wrapper")
                monkeypatch.setattr(msda_pallas, "ms_deform_attn_pallas",
                                    lambda *a, **k: "kernel")
                out = msda.ms_deform_attn(
                    jnp.zeros((1, 44 * 60, 8, 16)), ((44, 60),),
                    jnp.zeros((1, 256, 8, 1, 4, 2)),
                    jnp.zeros((1, 256, 8, 1, 4)))
                return isinstance(out, str)
            if kernel == "step":
                return step_pallas.plan_fusion(
                    sds(C), sds(C), sds(324), sds(2), True, mode=mode)
            if kernel == "motion":
                return motion_pallas.should_fuse(sds(2), sds(324),
                                                 mode=mode)
            return gru_pallas.should_fuse(sds(C), sds(2 * C), C, mode=mode)

        assert decide()                         # no mesh: the kernel
        with spatial_kernel_mesh(make_mesh(devices=jax.devices()[:1])):
            assert decide()                     # one device: the kernel
        with spatial_kernel_mesh(make_mesh(devices=jax.devices()[:2])):
            with caplog.at_level(logging.WARNING,
                                 logger="raft_tpu.parallel.spatial"):
                assert not decide()
            assert "no shard_map wrapper" in caplog.text
            with pytest.raises(ValueError, match="shard_map wrapper"):
                decide(mode="1")

    def test_bad_env_value_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("RAFT_STEP_PALLAS", "on")
        with pytest.raises(ValueError, match="RAFT_STEP_PALLAS"):
            step_pallas.resolve_mode()


class TestEligibility:
    def test_halos_compose_across_the_chain(self):
        """GRU ±4 (+flow head ±2) of valid x; motion inputs another ±5
        beyond wherever its output must be valid."""
        assert step_pallas.halos(False) == (4, 9)
        assert step_pallas.halos(True) == (6, 11)

    def test_sintel_admission_table(self):
        """The pinned envelope at Sintel-eval feature shapes (H=55,
        W=128, Ccorr=4*81=324) under the explicit 100 MiB scoped limit
        and the Mosaic-calibrated estimate: bf16 admits TH=8 at both
        depths (Mosaic takes up to 72.5 / 82.8 MiB there, 95 / 106 MiB
        at TH=16); f32 admits 'mg' at TH=4 only; at 1080p (W=240)
        nothing admits — auto steps down / falls back (logged) rather
        than OOM Mosaic."""
        assert step_pallas.choose_rows(55, 128, 324, 2) == 8
        assert step_pallas.choose_rows(55, 128, 324, 2,
                                       flow_head=True) == 8
        assert step_pallas.choose_rows(55, 128, 324, 4) == 4
        assert step_pallas.choose_rows(55, 128, 324, 4,
                                       flow_head=True) is None
        assert step_pallas.choose_rows(135, 240, 324, 2) is None

    @pytest.mark.parametrize("th,dtype_bytes,flow_head,mosaic_mib", [
        (4, 2, False, 45.40), (8, 2, False, 72.51),
        (8, 2, True, 82.75), (16, 2, True, 106.01),
        (4, 4, False, 83.47), (16, 4, False, 111.47)])
    def test_estimate_covers_what_mosaic_reported(self, th, dtype_bytes,
                                                  flow_head, mosaic_mib):
        """The phase-peak estimate admitted Sintel bf16 'mg' TH=4 at
        12.8 MiB under a 13 MiB budget; Mosaic needs 45.4 MiB. The
        calibrated estimate is at least what the compiler reported at
        every probed tile, and a tile Mosaic takes more than the limit
        for (bf16 'mgf' TH=16 at 106 MiB, f32 'mg' TH=16 at 111 MiB) is
        no longer admitted."""
        est = vmem.total_bytes(step_pallas.step_vmem_parts(
            128, th, dtype_bytes, flow_head=flow_head))
        assert est >= mosaic_mib * 2**20
        assert (est <= vmem.SCAN_LIMIT_BYTES) == (mosaic_mib < 100)

    def test_small_shapes_admit_deeper_fusion(self):
        """Smaller operating points ride the top rung at the 'mgf'
        depth — the serving brownout ladder's shapes stay fused."""
        assert step_pallas.choose_rows(30, 64, 324, 2) == 16
        assert step_pallas.choose_rows(30, 64, 324, 2,
                                       flow_head=True) == 16

    def test_fused_step_preflights_real_launches(self, update_setup):
        """fused_step(interpret=False) trips the itemized VMEM
        preflight before any pallas_call for an over-budget shape."""
        _, vs, *_ = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        rng = np.random.default_rng(2)
        net = jnp.asarray(rng.standard_normal((1, 55, 128, C)),
                          jnp.float32)
        inp = jnp.asarray(rng.standard_normal((1, 55, 128, C)),
                          jnp.float32)
        corr = jnp.asarray(rng.standard_normal((1, 55, 128, CC)),
                           jnp.float32)
        flow = jnp.asarray(rng.standard_normal((1, 55, 128, 2)),
                           jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            step_pallas.fused_step(net, inp, corr, flow, mmats, gmats,
                                   fmats, interpret=False)

    def test_generic_ladder_alignment_and_budget(self):
        """vmem.choose_rows (shared by motion/gru/step): misaligned
        (th*w) % 8 rungs are skipped even when they'd fit; every
        aligned rung over budget → None."""
        huge, tiny = {"x": 1 << 40}, {"x": 1 << 10}
        assert vmem.choose_rows(
            (16, 8, 4), 2,
            lambda th: tiny if th == 4 else huge) == 4
        assert vmem.choose_rows((16, 8, 4), 2, lambda th: huge) is None
        assert vmem.choose_rows((4,), 1, lambda th: tiny) is None


class TestPackFlowHead:
    def test_shapes(self, update_setup):
        _, vs, *_ = update_setup
        _, _, fmats = _packers(vs["params"])
        assert [m.shape for m in fmats] == [
            (9 * C, 256), (1, 256), (9 * 256, 2), (1, 2)]

    def test_rejects_wrong_geometry(self):
        k1 = jnp.zeros((3, 3, C, 256))
        b1 = jnp.zeros((256,))
        k2 = jnp.zeros((3, 3, 256, 2))
        b2 = jnp.zeros((2,))
        with pytest.raises(ValueError, match="HWIO"):
            step_pallas.pack_flow_head(
                (jnp.zeros((1, 5, C, 256)), b1), (k2, b2))
        with pytest.raises(ValueError, match="chain mismatch"):
            step_pallas.pack_flow_head(
                (k1, b1), (jnp.zeros((3, 3, 128, 2)), b2))
        with pytest.raises(ValueError, match="chain mismatch"):
            step_pallas.pack_flow_head(
                (k1, b1), (jnp.zeros((3, 3, 256, 3)), jnp.zeros((3,))))
