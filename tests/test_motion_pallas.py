"""Fused BasicMotionEncoder Pallas kernel suite (round-7 tentpole).

CPU interpret-mode parity against the flax ``BasicMotionEncoder`` —
forward and gradients — plus the dispatch contract
(``RAFT_MOTION_PALLAS``), the VMEM admission table at the Sintel-eval
operating point, the logged auto-fallback (satellite of this round, for
both kernel flags), and the weight-packing geometry checks.

Tolerances: like the GRU kernel, the tap decomposition changes the
reduction order vs ``lax.conv_general_dilated``, so f32 parity is
tight-tolerance (measured ~1e-6 max abs at these shapes; asserted at
1e-5 forward / 2e-4 gradients — the ISSUE acceptance bound), not
bit-exact. The flow passthrough channels ARE bit-exact (pure copy).
``RAFT_MOTION_PALLAS=0`` restores the conv path bit-for-bit; the
golden-fixture flag-off EPE identity lives in tests/test_golden.py.

Round 10 re-modeled the VMEM estimate as phase-peak liveness (the conv
phases run sequentially and reuse buffers, so the working set is the
largest phase plus cross-phase residents, not the sum) — the admission
table pinned below moved accordingly: Sintel bf16 now rides TH=16 and
f32 honestly admits a TH=4 tile.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops import gru_pallas, motion_pallas, vmem

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret

B, H, W, CC = 2, 9, 7, 12
CO = 126  # fusing conv width; output is [out(126) ‖ flow(2)]


def _pack_from_params(params):
    def pair(name):
        return (params[name]["kernel"], params[name]["bias"])

    return motion_pallas.pack_weights(
        pair("convc1"), pair("convc2"), pair("convf1"),
        pair("convf2"), pair("conv"))


@pytest.fixture(scope="module")
def motion_setup():
    """Flax BasicMotionEncoder + inputs at a deliberately awkward shape
    (odd W, H not a row-tile multiple); flow at ~3px magnitude so the
    7x7 conv sees realistic dynamic range."""
    from raft_tpu.models.update import BasicMotionEncoder

    model = BasicMotionEncoder()
    rng = np.random.default_rng(0)
    flow = jnp.asarray(3.0 * rng.standard_normal((B, H, W, 2)),
                       jnp.float32)
    corr = jnp.asarray(rng.standard_normal((B, H, W, CC)), jnp.float32)
    vs = model.init(jax.random.PRNGKey(0), flow, corr)
    mats = _pack_from_params(vs["params"])
    return model, vs, flow, corr, mats


@pytest.fixture(scope="module")
def update_setup():
    """Full BasicUpdateBlock for the dispatch tests — the fused path
    must also hand the GRU its x input as un-concatenated parts."""
    from raft_tpu.models.update import BasicUpdateBlock

    model = BasicUpdateBlock()
    rng = np.random.default_rng(1)
    net = jnp.asarray(rng.standard_normal((B, H, W, 128)), jnp.float32)
    inp = jnp.asarray(rng.standard_normal((B, H, W, 128)), jnp.float32)
    corr = jnp.asarray(rng.standard_normal((B, H, W, CC)), jnp.float32)
    flow = jnp.asarray(3.0 * rng.standard_normal((B, H, W, 2)),
                       jnp.float32)
    vs = model.init(jax.random.PRNGKey(1), net, inp, corr, flow)
    return model, vs, net, inp, corr, flow


class TestForwardParity:
    def test_reference_matches_flax(self, motion_setup, monkeypatch):
        """The pure-jnp shifted-matmul twin (the VJP backward and parity
        oracle) reproduces the five-conv chain + passthrough concat."""
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        model, vs, flow, corr, mats = motion_setup
        want = model.apply(vs, flow, corr)
        got2d = motion_pallas.reference_motion(
            (W, H), flow.reshape(B, H * W, 2),
            corr.reshape(B, H * W, CC), mats)
        np.testing.assert_allclose(got2d.reshape(B, H, W, CO + 2), want,
                                   atol=1e-5, rtol=0)

    @pytest.mark.parametrize("th", [4, 5, 8])
    def test_kernel_matches_flax_f32(self, motion_setup, monkeypatch,
                                     th):
        """Interpret-mode kernel vs flax at f32 across row tiles: th=4
        (the rung f32 Sintel now rides — halo 5 > th, so each side
        assembles ceil(5/4)=2 neighbor blocks), th=5 pads H 9→10
        (2 tiles, both halo directions live through the 3-conv
        receptive-field depth), th=8 pads to 16 (heavy padded-row
        masking)."""
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        model, vs, flow, corr, mats = motion_setup
        want = model.apply(vs, flow, corr)
        got = motion_pallas.motion_encoder(flow, corr, mats,
                                           interpret=True, th=th)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)

    def test_kernel_matches_flax_bf16(self, motion_setup, monkeypatch):
        """bf16 compute dtype (the mixed-precision policy): both paths
        share the f32-accumulate → bf16-bias-add contract. The chain is
        five convs deep, so allow a few bf16 ulp of the feature scale."""
        from raft_tpu.models.update import BasicMotionEncoder

        _, vs, flow, corr, mats = motion_setup
        model16 = BasicMotionEncoder(dtype=jnp.bfloat16)
        flow16 = flow.astype(jnp.bfloat16)
        corr16 = corr.astype(jnp.bfloat16)
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        want = model16.apply(vs, flow16, corr16)
        got = motion_pallas.motion_encoder(
            flow16, corr16, mats, dtype=jnp.bfloat16, interpret=True)
        assert got.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32),
            atol=4 * float(jnp.finfo(jnp.bfloat16).eps) * scale, rtol=0)

    def test_flow_passthrough_is_bitexact(self, motion_setup,
                                          monkeypatch):
        """Channels 126:128 are the untouched flow estimate — a pure
        copy in the kernel's output store, never a recompute."""
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        _, _, flow, corr, mats = motion_setup
        got = motion_pallas.motion_encoder(flow, corr, mats,
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(got[..., CO:]),
                                      np.asarray(flow))


class TestGradParity:
    def test_input_grads_match_flax(self, motion_setup):
        """d(sum(out))/d{flow, corr} through the custom VJP (recompute
        via the jnp twin) vs the conv path's autodiff."""
        model, vs, flow, corr, mats = motion_setup

        def loss_flax(fl, co):
            return jnp.sum(model.apply(vs, fl, co))

        def loss_kern(fl, co):
            return jnp.sum(motion_pallas.motion_encoder(
                fl, co, mats, interpret=True))

        g_flax = jax.grad(loss_flax, argnums=(0, 1))(flow, corr)
        g_kern = jax.grad(loss_kern, argnums=(0, 1))(flow, corr)
        for a, b in zip(g_flax, g_kern):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)

    def test_param_grads_flow_through_packing(self, motion_setup):
        """Gradients reach the flax param tree through pack_weights —
        what training with the fused path relies on."""
        model, vs, flow, corr, _ = motion_setup

        def loss_flax(params):
            return jnp.sum(model.apply({"params": params}, flow, corr))

        def loss_kern(params):
            return jnp.sum(motion_pallas.motion_encoder(
                flow, corr, _pack_from_params(params), interpret=True))

        g_flax = jax.grad(loss_flax)(vs["params"])
        g_kern = jax.grad(loss_kern)(vs["params"])
        for a, b in zip(jax.tree_util.tree_leaves(g_flax),
                        jax.tree_util.tree_leaves(g_kern)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)


class TestDispatch:
    def test_flag_off_is_bitexact(self, update_setup, monkeypatch):
        """RAFT_MOTION_PALLAS=0 and unset-on-CPU (auto) both take the
        conv path through BasicUpdateBlock — bit-for-bit identical (the
        acceptance criterion; the golden-EPE variant lives in
        test_golden.py)."""
        model, vs, net, inp, corr, flow = update_setup
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        auto = model.apply(vs, net, inp, corr, flow)
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        off = model.apply(vs, net, inp, corr, flow)
        for a, b in zip(auto, off):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_forced_matches_conv_path(self, update_setup, monkeypatch):
        """'1' routes the encoder through the kernel and the GRU's x
        arrives as (inp, [motion‖flow]) parts; net/mask/delta_flow stay
        within the acceptance tolerance of the conv path."""
        model, vs, net, inp, corr, flow = update_setup
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        want = model.apply(vs, net, inp, corr, flow)
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "1")
        got = model.apply(vs, net, inp, corr, flow)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)

    def test_forced_with_gru_kernel(self, update_setup, monkeypatch):
        """Both kernels forced: the motion kernel's [out‖flow] feeds the
        GRU kernel's multi-part x weights — the full concat-free chain
        of this round."""
        model, vs, net, inp, corr, flow = update_setup
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        want = model.apply(vs, net, inp, corr, flow)
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "1")
        monkeypatch.setenv("RAFT_GRU_PALLAS", "1")
        got = model.apply(vs, net, inp, corr, flow)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)

    def test_should_fuse_modes(self, motion_setup, monkeypatch):
        _, _, flow, corr, _ = motion_setup
        assert not motion_pallas.should_fuse(flow, corr, mode="0")
        assert motion_pallas.should_fuse(flow, corr, mode="1")
        # auto on CPU: conv path (interpret mode is a parity tool, not
        # a fast path)
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        assert not motion_pallas.should_fuse(flow, corr)

    def test_forced_bad_shape_raises(self, motion_setup):
        _, _, flow, corr, _ = motion_setup
        bad_flow = jnp.zeros((B, H, W, 3), jnp.float32)
        with pytest.raises(ValueError, match="RAFT_MOTION_PALLAS=1"):
            motion_pallas.should_fuse(bad_flow, corr, mode="1")

    def test_bad_env_value_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "on")
        with pytest.raises(ValueError, match="RAFT_MOTION_PALLAS"):
            motion_pallas.resolve_mode()


class TestEligibility:
    def test_interpret_admits_any_positive_shape(self):
        assert motion_pallas.motion_eligible(3, 5, 7, jnp.float32, True)
        assert not motion_pallas.motion_eligible(0, 5, 7, jnp.float32,
                                                 True)

    def test_sintel_admission_table(self):
        """The pinned envelope at Sintel-eval feature shapes (H=55,
        W=128, Ccorr=4*81=324) under the explicit 100 MiB scoped limit
        and the Mosaic-calibrated estimate: both dtypes ride the TH=16
        rung (Mosaic takes up to 52 / 66 MiB; 73 / 89 when convf1 was
        49 products); a 1080p-wide map steps down to TH=8 (60 MiB; 96
        at TH=16); a 4K-wide map fits no tile and falls back loudly
        (see the fallback-log test); a corr window wider than the
        calibrated 384 lanes keeps the conv path."""
        assert motion_pallas.choose_rows(55, 128, 324, 2) == 16
        assert motion_pallas.choose_rows(55, 128, 324, 4) == 16
        assert motion_pallas.choose_rows(135, 240, 324, 2) == 8
        assert motion_pallas.choose_rows(55, 512, 324, 4) is None
        assert motion_pallas.motion_eligible(55, 128, 324, jnp.bfloat16,
                                             False)
        assert motion_pallas.motion_eligible(55, 128, 324,
                                             jnp.float32, False)
        assert not motion_pallas.motion_eligible(55, 512, 324,
                                                 jnp.float32, False)
        assert not motion_pallas.motion_eligible(55, 128, 4 * 121,
                                                 jnp.bfloat16, False)

    @pytest.mark.parametrize("th,dtype_bytes,mosaic_mib", [
        (16, 2, 51.96), (8, 2, 33.22), (4, 2, 26.34), (8, 4, 41.70),
        (16, 4, 65.70)])
    def test_estimate_covers_what_mosaic_reported(self, th, dtype_bytes,
                                                  mosaic_mib):
        """The phase-peak estimate admitted the Sintel bf16 TH=16 tile
        at 11.7 MiB under a 13 MiB budget; Mosaic put 53.8 MiB on its
        stack and refused it under the 16 MiB default. The calibrated
        estimate is at least what the compiler reported (for the
        present body, compiled for a described v5e under a 1 GiB limit)
        at every probed tile, and the tile is admitted only because the
        launch carries the explicit limit."""
        est = vmem.total_bytes(
            motion_pallas.motion_vmem_parts(55, 128, 324, th, dtype_bytes))
        assert mosaic_mib * 2**20 <= est <= vmem.SCAN_LIMIT_BYTES
        assert est > vmem.LIMIT_BYTES

    def test_preflight_raises_itemized(self):
        """An inadmissible forced launch dies in the shared VMEM
        preflight with the requested-vs-budget breakdown, not a Mosaic
        scoped-VMEM OOM."""
        parts = motion_pallas.motion_vmem_parts(55, 512, 324, 8, 4)
        assert not vmem.fits(parts, vmem.SCAN_LIMIT_BYTES)
        with pytest.raises(ValueError, match="admission budget") as ei:
            vmem.preflight(parts, "fused motion encoder (test)",
                           vmem.SCAN_LIMIT_BYTES)
        assert "assembly_rows_live" in str(ei.value)

    def test_motion_encoder_preflights_real_launches(self, motion_setup):
        """motion_encoder(interpret=False) trips the preflight before
        any pallas_call for an over-budget shape."""
        *_, mats = motion_setup
        rng = np.random.default_rng(2)
        flow = jnp.asarray(rng.standard_normal((1, 8, 512, 2)),
                           jnp.float32)
        corr = jnp.asarray(rng.standard_normal((1, 8, 512, CC)),
                           jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            motion_pallas.motion_encoder(flow, corr, mats,
                                         interpret=False)

    def test_auto_fallback_is_logged_motion(self, monkeypatch, caplog):
        """The satellite contract: when auto on a TPU backend rejects a
        shape on the VMEM envelope, one loud structured warning names
        the flag, shape and budget — never a silent conv fallback."""
        monkeypatch.delenv("RAFT_MOTION_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # Sintel shapes admit TH=16, so the rejection shape is a
        # 4K-wide f32 map that overflows even the smallest tile.
        flow = jax.ShapeDtypeStruct((1, 55, 512, 2), jnp.float32)
        corr = jax.ShapeDtypeStruct((1, 55, 512, 324), jnp.float32)
        with caplog.at_level(logging.WARNING, logger="raft_tpu.ops.vmem"):
            assert not motion_pallas.should_fuse(flow, corr)
        assert "RAFT_MOTION_PALLAS=auto" in caplog.text
        assert "falling back to the XLA path" in caplog.text
        assert "H=55, W=512, Ccorr=324" in caplog.text
        assert "admission budget" in caplog.text

    def test_auto_fallback_is_logged_gru(self, monkeypatch, caplog):
        """Same hook for the round-6 kernel: an f32 rejection (a map
        too wide for even the smallest tile) is announced."""
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        h = jax.ShapeDtypeStruct((1, 55, 1024, 128), jnp.float32)
        x = jax.ShapeDtypeStruct((1, 55, 1024, 256), jnp.float32)
        with caplog.at_level(logging.WARNING, logger="raft_tpu.ops.vmem"):
            assert not gru_pallas.should_fuse(h, x, 128)
        assert "RAFT_GRU_PALLAS=auto" in caplog.text
        assert "falling back to the XLA path" in caplog.text


class TestPackWeights:
    def test_shapes(self, motion_setup):
        *_, mats = motion_setup
        c1, c2, f1, f2 = 256, 192, 128, 64
        assert [m.shape for m in mats] == [
            (CC, c1), (1, c1), (9 * c1, c2), (1, c2), (49 * 2, f1),
            (1, f1), (9 * f1, f2), (1, f2), (9 * c2, CO), (9 * f2, CO),
            (1, CO)]

    def test_rejects_wrong_kernel_geometry(self, motion_setup):
        model, vs, *_ = motion_setup
        p = vs["params"]

        def pair(name):
            return (p[name]["kernel"], p[name]["bias"])

        with pytest.raises(ValueError, match="HWIO"):
            motion_pallas.pack_weights(
                pair("convc2"), pair("convc2"), pair("convf1"),
                pair("convf2"), pair("conv"))
        bad_f1 = (jnp.zeros((7, 7, 3, 128)), jnp.zeros((128,)))
        with pytest.raises(ValueError, match="2-channel flow"):
            motion_pallas.pack_weights(
                pair("convc1"), pair("convc2"), bad_f1,
                pair("convf2"), pair("conv"))
        with pytest.raises(ValueError, match="channel mismatch"):
            motion_pallas.pack_weights(
                pair("convc1"), pair("convc2"), pair("convf1"),
                pair("convf2"), pair("convf2"))
