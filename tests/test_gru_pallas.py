"""Fused SepConvGRU Pallas kernel suite (round-6 tentpole).

CPU interpret-mode parity against the flax ``SepConvGRU`` — forward and
gradients — plus the dispatch contract (``RAFT_GRU_PALLAS``), the VMEM
admission machinery shared with the corr kernel, and the envflags
parsers that back every kernel toggle.

Tolerances: the kernel's tap decomposition changes the reduction order
vs ``lax.conv_general_dilated`` (per-tap partial sums), so f32 parity is
tight-tolerance (measured ~4e-7 max abs at these shapes; asserted at
1e-5), not bit-exact. bf16 compute is asserted within one bf16 ulp of
~1-magnitude outputs (measured bit-exact here — both paths round
through the same f32-accumulate → bf16 contract).
``RAFT_GRU_PALLAS=0`` restores the conv path bit-for-bit (asserted).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops import gru_pallas, vmem
from raft_tpu.utils import envflags

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret

B, H, W, C, CX = 2, 11, 7, 16, 24


def _pack_from_params(params, hidden_dim):
    def pair(name):
        return (params[name]["kernel"], params[name]["bias"])

    return gru_pallas.pack_weights(
        (pair("convz1"), pair("convr1"), pair("convq1")),
        (pair("convz2"), pair("convr2"), pair("convq2")), hidden_dim)


@pytest.fixture(scope="module")
def gru_setup():
    """Flax SepConvGRU + inputs at a deliberately awkward shape: odd W,
    H not a multiple of any row tile (exercises column masks, vertical
    edge masks and the padded-rows path)."""
    from raft_tpu.models.update import SepConvGRU

    model = SepConvGRU(hidden_dim=C)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, H, W, CX)), jnp.float32)
    vs = model.init(jax.random.PRNGKey(0), h, x)
    mats = _pack_from_params(vs["params"], C)
    return model, vs, h, x, mats


class TestForwardParity:
    def test_reference_matches_flax(self, gru_setup, monkeypatch):
        """The pure-jnp shifted-matmul twin (the VJP backward and parity
        oracle) reproduces the conv path."""
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        model, vs, h, x, mats = gru_setup
        want = model.apply(vs, h, x)
        got2d = gru_pallas.reference_gru(
            (W, H, None, None),
            h.reshape(B, H * W, C), x.reshape(B, H * W, CX), mats)
        np.testing.assert_allclose(got2d.reshape(B, H, W, C), want,
                                   atol=1e-5, rtol=0)

    @pytest.mark.parametrize("th", [4, 8])
    def test_kernel_matches_flax_f32(self, gru_setup, monkeypatch, th):
        """Interpret-mode kernel vs flax at f32, across row-tile sizes:
        th=4 pads H 11→12 (3 tiles, both halo directions live), th=8
        pads to 16 (2 tiles, heavy padded-row masking)."""
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        model, vs, h, x, mats = gru_setup
        want = model.apply(vs, h, x)
        got = gru_pallas.sepconv_gru(h, x, mats, interpret=True, th=th)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)

    def test_kernel_matches_flax_bf16(self, gru_setup, monkeypatch):
        """bf16 compute dtype (the mixed-precision policy): both paths
        share the f32-accumulate → bf16-bias-add contract, so they agree
        within one bf16 ulp of the ~1-magnitude hidden state."""
        from raft_tpu.models.update import SepConvGRU

        _, vs, h, x, mats = gru_setup
        model16 = SepConvGRU(hidden_dim=C, dtype=jnp.bfloat16)
        h16, x16 = h.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        want = model16.apply(vs, h16, x16)
        got = gru_pallas.sepconv_gru(h16, x16, mats,
                                     dtype=jnp.bfloat16, interpret=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32),
            atol=2 * float(jnp.finfo(jnp.bfloat16).eps), rtol=0)

    def test_single_tile_tiny_height(self, gru_setup, monkeypatch):
        """H < TH: one tile, everything below H is padded rows whose
        contributions the global-row masks must zero."""
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        model, vs, h, x, mats = gru_setup
        h3, x3 = h[:, :3], x[:, :3]
        want = model.apply(vs, h3, x3)
        got = gru_pallas.sepconv_gru(h3, x3, mats, interpret=True, th=8)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


class TestGradParity:
    def test_grads_match_flax(self, gru_setup, monkeypatch):
        """d(sum(out))/d{h, x, params} through the custom VJP vs the
        conv path's autodiff — gradients reach the flax param tree
        through pack_weights."""
        model, vs, h, x, _ = gru_setup

        def loss(params, hh, xx, env):
            monkeypatch.setenv("RAFT_GRU_PALLAS", env)
            return jnp.sum(model.apply({"params": params}, hh, xx))

        g_flax = jax.grad(loss, argnums=(0, 1, 2))(
            vs["params"], h, x, "0")
        g_kern = jax.grad(loss, argnums=(0, 1, 2))(
            vs["params"], h, x, "1")
        for a, b in zip(jax.tree_util.tree_leaves(g_flax),
                        jax.tree_util.tree_leaves(g_kern)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=0)


class TestDispatch:
    def test_flag_off_is_bitexact(self, gru_setup, monkeypatch):
        """RAFT_GRU_PALLAS=0 and unset-on-CPU (auto) both take the conv
        path — bit-for-bit identical (the acceptance criterion)."""
        model, vs, h, x, _ = gru_setup
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        auto = model.apply(vs, h, x)
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        off = model.apply(vs, h, x)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(off))

    def test_forced_dispatch_takes_kernel(self, gru_setup, monkeypatch):
        """'1' routes SepConvGRU.__call__ through the kernel: output
        matches the direct sepconv_gru call exactly."""
        model, vs, h, x, mats = gru_setup
        monkeypatch.setenv("RAFT_GRU_PALLAS", "1")
        via_model = model.apply(vs, h, x)
        direct = gru_pallas.sepconv_gru(h, x, mats, interpret=True)
        np.testing.assert_array_equal(np.asarray(via_model),
                                      np.asarray(direct))

    def test_should_fuse_modes(self, gru_setup, monkeypatch):
        _, _, h, x, _ = gru_setup
        assert not gru_pallas.should_fuse(h, x, C, mode="0")
        assert gru_pallas.should_fuse(h, x, C, mode="1")
        # auto on CPU: flax path (interpret mode is a parity tool, not a
        # fast path)
        monkeypatch.delenv("RAFT_GRU_PALLAS", raising=False)
        assert not gru_pallas.should_fuse(h, x, C)

    def test_forced_bad_shape_raises(self, gru_setup):
        _, _, h, x, _ = gru_setup
        with pytest.raises(ValueError, match="hidden state has shape"):
            gru_pallas.should_fuse(h, x, C + 1, mode="1")

    def test_bad_env_value_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("RAFT_GRU_PALLAS", "yes")
        with pytest.raises(ValueError, match="RAFT_GRU_PALLAS"):
            gru_pallas.resolve_mode()


class TestEligibility:
    def test_interpret_admits_any_positive_shape(self):
        assert gru_pallas.gru_eligible(3, 5, 7, 9, jnp.float32, True)
        assert not gru_pallas.gru_eligible(0, 5, 7, 9, jnp.float32, True)

    def test_hardware_requires_lane_aligned_channels(self):
        assert not gru_pallas.gru_eligible(55, 128, 64, 256,
                                           jnp.bfloat16, False)
        assert not gru_pallas.gru_eligible(55, 128, 128, 192,
                                           jnp.bfloat16, False)

    def test_sintel_admits_th16_under_explicit_limit(self):
        """The envelope at Sintel-eval feature shapes (W=128, C=128,
        Cx=256) under the explicit 100 MiB scoped limit: bf16 and f32
        both admit the largest tile (Mosaic takes up to 39.7 / 57.8 MiB
        — compiled for the chip in tests/test_chip_compile.py)."""
        assert gru_pallas.choose_rows(55, 128, 128, 256, 2) == 16
        assert gru_pallas.choose_rows(55, 128, 128, 256, 4) == 16
        assert gru_pallas.gru_eligible(55, 128, 128, 256,
                                       jnp.bfloat16, False)
        assert gru_pallas.gru_eligible(55, 128, 128, 256,
                                       jnp.float32, False)

    @pytest.mark.parametrize("th,dtype_bytes,mosaic_mib", [
        (8, 2, 17.88), (16, 2, 39.68), (16, 4, 57.81)])
    def test_estimate_covers_what_mosaic_reported(self, th, dtype_bytes,
                                                  mosaic_mib):
        """The old estimate admitted Sintel bf16 th=8 at 12.3 MiB under
        a 13 MiB budget; Mosaic refused it at 17.88 MiB, over its 16
        MiB default. The calibrated estimate is at least what the
        compiler reported at every probed tile (th=16: what it takes
        with no limit in its way), and inside the explicit limit the
        launch now passes."""
        est = vmem.total_bytes(
            gru_pallas.gru_vmem_parts(55, 128, 128, 256, th, dtype_bytes))
        assert mosaic_mib * 2**20 <= est <= vmem.SCAN_LIMIT_BYTES

    def test_preflight_raises_itemized(self):
        """An inadmissible forced launch dies in the shared VMEM
        preflight with the requested-vs-budget breakdown, not a Mosaic
        scoped-VMEM OOM."""
        parts = gru_pallas.gru_vmem_parts(64, 512, 512, 512, 4, 4)
        assert not vmem.fits(parts)
        with pytest.raises(ValueError, match="admission budget") as ei:
            vmem.preflight(parts, "fused GRU kernel (test)")
        assert "assembly_rows_live" in str(ei.value)

    def test_sepconv_gru_preflights_real_launches(self, gru_setup):
        """sepconv_gru(interpret=False) trips the preflight before any
        pallas_call for an over-budget shape."""
        rng = np.random.default_rng(1)
        h = jnp.asarray(rng.standard_normal((1, 8, 512, 512)),
                        jnp.float32)
        x = jnp.asarray(rng.standard_normal((1, 8, 512, 512)),
                        jnp.float32)
        *_, mats = gru_setup
        with pytest.raises(ValueError, match="VMEM"):
            gru_pallas.sepconv_gru(h, x, mats, interpret=False)

    def test_vmem_budget_constants(self):
        # The corr kernel's historic 13/16 MB split under Mosaic's
        # default limit, and the scan-body kernels' explicit one.
        assert vmem.LIMIT_BYTES == 16 * 2**20
        assert vmem.BUDGET_BYTES == 13 * 2**20
        assert vmem.SCAN_LIMIT_BYTES == 100 * 2**20
        params = vmem.scan_compiler_params()
        assert params.vmem_limit_bytes == vmem.SCAN_LIMIT_BYTES


class TestPackWeights:
    def test_shapes(self, gru_setup):
        *_, mats = gru_setup
        shapes = [m.shape for m in mats]
        assert shapes == [(5 * C, 2 * C), (5 * CX, 2 * C),
                          (5 * C, C), (5 * CX, C), (1, 2 * C), (1, C)] * 2

    def test_rejects_non_separable_kernel(self):
        k = jnp.zeros((3, 3, C + CX, C))
        b = jnp.zeros((C,))
        with pytest.raises(ValueError, match="separable kernel"):
            gru_pallas.pack_weights(((k, b),) * 3, ((k, b),) * 3, C)


class TestXParts:
    """Round-7 multi-part x: the fused motion encoder hands the GRU its
    x input as an un-concatenated tuple; ``split_x_weights`` re-slices
    the packed weights so per-part matmuls sum to the full-input matmul.
    Splitting the matmul reorders the f32 reduction, so multi-part is
    tolerance-parity vs the whole-x kernel (≤1e-5 here), while a
    single-part x is exactly the round-6 path."""

    def test_single_part_returns_mats_unchanged(self, gru_setup):
        *_, mats = gru_setup
        assert gru_pallas.split_x_weights(mats, (CX,)) is mats

    def test_split_rejects_mismatched_widths(self, gru_setup):
        *_, mats = gru_setup
        with pytest.raises(ValueError, match="split_x_weights"):
            gru_pallas.split_x_weights(mats, (10, 10))

    def test_two_part_matches_whole_and_flax(self, gru_setup):
        model, vs, h, x, mats = gru_setup
        want = model.apply(vs, h, x)
        whole = gru_pallas.sepconv_gru(h, x, mats, interpret=True)
        parts = gru_pallas.sepconv_gru(
            h, (x[..., :10], x[..., 10:]), mats, interpret=True)
        assert parts.shape == whole.shape
        np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                                   atol=1e-5, rtol=0)

    def test_flax_conv_path_accepts_tuple_x_bitexact(self, gru_setup,
                                                     monkeypatch):
        """The conv fallback concatenates tuple parts itself — same op
        as a pre-concatenated x, so bit-for-bit identical."""
        model, vs, h, x, _ = gru_setup
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        a = model.apply(vs, h, x)
        b = model.apply(vs, h, (x[..., :10], x[..., 10:]))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grads_flow_through_parts(self, gru_setup):
        """d(sum(out))/d(xa, xb) through the tuple path equals the
        whole-x gradient sliced at the same boundary."""
        _, _, h, x, mats = gru_setup

        def loss_whole(xx):
            return jnp.sum(gru_pallas.sepconv_gru(h, xx, mats,
                                                  interpret=True))

        def loss_parts(xa, xb):
            return jnp.sum(gru_pallas.sepconv_gru(h, (xa, xb), mats,
                                                  interpret=True))

        g_whole = jax.grad(loss_whole)(x)
        ga, gb = jax.grad(loss_parts, argnums=(0, 1))(
            x[..., :10], x[..., 10:])
        np.testing.assert_allclose(np.asarray(ga),
                                   np.asarray(g_whole[..., :10]),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(gb),
                                   np.asarray(g_whole[..., 10:]),
                                   atol=1e-5, rtol=0)


class TestEnvFlags:
    def test_env_bool(self, monkeypatch):
        monkeypatch.delenv("RAFT_T_B", raising=False)
        assert envflags.env_bool("RAFT_T_B", True) is True
        monkeypatch.setenv("RAFT_T_B", "")
        assert envflags.env_bool("RAFT_T_B", False) is False
        monkeypatch.setenv("RAFT_T_B", "1")
        assert envflags.env_bool("RAFT_T_B", False) is True
        monkeypatch.setenv("RAFT_T_B", "true")
        with pytest.raises(ValueError, match="RAFT_T_B must be '0' or '1'"):
            envflags.env_bool("RAFT_T_B", False)

    def test_env_enum(self, monkeypatch):
        monkeypatch.delenv("RAFT_T_E", raising=False)
        assert envflags.env_enum("RAFT_T_E", ("a", "b"), "a") == "a"
        monkeypatch.setenv("RAFT_T_E", "b")
        assert envflags.env_enum("RAFT_T_E", ("a", "b"), "a") == "b"
        monkeypatch.setenv("RAFT_T_E", "c")
        with pytest.raises(ValueError, match="must be one of"):
            envflags.env_enum("RAFT_T_E", ("a", "b"), "a")
        with pytest.raises(ValueError, match="not among choices"):
            envflags.env_enum("RAFT_T_E", ("a", "b"), "z")

    def test_env_int_choice(self, monkeypatch):
        monkeypatch.delenv("RAFT_T_I", raising=False)
        assert envflags.env_int_choice("RAFT_T_I", (0, 128), 0) == 0
        monkeypatch.setenv("RAFT_T_I", "128")
        assert envflags.env_int_choice("RAFT_T_I", (0, 128), 0) == 128
        monkeypatch.setenv("RAFT_T_I", "64")
        with pytest.raises(ValueError, match=r"got 64 \(lane\)"):
            envflags.env_int_choice("RAFT_T_I", (0, 128), 0, hint="lane")
        monkeypatch.setenv("RAFT_T_I", "big")
        with pytest.raises(ValueError, match="must be an integer"):
            envflags.env_int_choice("RAFT_T_I", (0, 128), 0)


class TestServingWarmupContract:
    def test_zero_compiles_after_warmup_with_kernel(self, monkeypatch):
        """The acceptance-criterion probe: with RAFT_GRU_PALLAS=1 the
        serving warmup compiles the kernel path once per bucket and
        steady-state load triggers ZERO further XLA compiles — the flag
        is trace-time, so the warmed executable has the kernel baked in.
        Non-small model (the small model's ConvGRU has no fused path)
        at a tiny bucket."""
        from raft_tpu.evaluate import load_predictor
        from raft_tpu.serving import (CompileWatch, ServingConfig,
                                      ServingEngine, loadgen)

        monkeypatch.setenv("RAFT_GRU_PALLAS", "1")
        pred = load_predictor("random", iters=2)
        assert pred.gru_impl == "1"
        eng = ServingEngine(pred, ServingConfig(
            max_batch=2, max_wait_ms=2.0, buckets=((36, 60),)))
        stats = eng.warmup()
        assert set(stats) == {(40, 64)}
        assert stats[(40, 64)]["compiles"] >= 1
        eng.start(warmup=False)
        frames = loadgen.make_frames([(36, 60), (33, 57)], per_shape=2,
                                     seed=5)
        try:
            with CompileWatch() as w:
                res = loadgen.run_load(eng, frames, n_requests=6,
                                       concurrency=2)
        finally:
            eng.close()
        assert res["completed"] == 6
        assert w.compiles == 0
        assert eng.metrics.compiles == 0
