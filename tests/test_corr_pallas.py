"""Parity + gradient tests for the Pallas on-demand correlation kernel.

Pattern follows the reference's kernel-testing strategy (SURVEY.md §4:
``core/ops/test.py`` keeps a pure-framework reference implementation and
asserts the native kernel matches it forward and backward) — here the
reference implementation is ``raft_tpu.models.corr.windowed_correlation``
(jnp), itself already parity-tested against the materialized ``CorrBlock``.

On CPU the kernel runs in Pallas interpreter mode; the identical code path
compiles on TPU.
"""


import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.models.corr import (AlternateCorrBlock, CorrBlock,
                                  build_feature_pyramid, windowed_correlation)
from raft_tpu.ops.corr_pallas import windowed_correlation_pallas

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("radius", [1, 3, 4])
@pytest.mark.parametrize("shape", [
    # (H, W) query grid == (H2, W2) target unless split below
    (6, 9),          # W2 far from a lane multiple → exercises padding
    (8, 16),
])
def test_forward_matches_jnp_reference(rng, radius, shape):
    H, W = shape
    B, C = 2, 32
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    # Coords both in-bounds and straddling the border (zero-padding path).
    coords = jnp.asarray(
        rng.uniform(-2.0, max(H, W) + 1.0, (B, H, W, 2)), jnp.float32)

    ref = windowed_correlation(f1, f2, coords, radius)
    got = windowed_correlation_pallas(f1, f2, coords, radius, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_different_target_resolution(rng):
    # Pyramid levels use a pooled fmap2 smaller than the query grid.
    B, C, H, W = 1, 16, 8, 12
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H // 2, W // 2, C)
    coords = jnp.asarray(rng.uniform(0, 5, (B, H, W, 2)), jnp.float32)
    ref = windowed_correlation(f1, f2, coords, 3)
    got = windowed_correlation_pallas(f1, f2, coords, 3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_noscale_variant(rng):
    B, C, H, W = 1, 8, 5, 7
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 5, (B, H, W, 2)), jnp.float32)
    ref = windowed_correlation(f1, f2, coords, 2, scale=False)
    got = windowed_correlation_pallas(f1, f2, coords, 2, scale=False,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_reference(rng):
    B, C, H, W, r = 1, 16, 6, 10, 2
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 6, (B, H, W, 2)), jnp.float32)
    cot = _rand(rng, B, H, W, (2 * r + 1) ** 2)

    def loss_ref(a, b):
        return jnp.sum(windowed_correlation(a, b, coords, r) * cot)

    def loss_pl(a, b):
        return jnp.sum(
            windowed_correlation_pallas(a, b, coords, r, interpret=True) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    g_pl = jax.grad(loss_pl, argnums=(0, 1))(f1, f2)
    for a, b in zip(g_ref, g_pl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_coords_gradient_is_zero(rng):
    # Contract of the reference extension: coords_grad allocated, never
    # written (alt_cuda_corr/correlation_kernel.cu:307).
    B, C, H, W, r = 1, 8, 4, 6, 1
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(1, 3, (B, H, W, 2)), jnp.float32)

    g = jax.grad(lambda c: jnp.sum(
        windowed_correlation_pallas(f1, f2, c, r, interpret=True)))(coords)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_alternate_block_pallas_matches_materialized(rng):
    # End-to-end: AlternateCorrBlock(pallas) == CorrBlock over the pyramid.
    B, C, H, W = 1, 32, 8, 12
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 8, (B, H, W, 2)), jnp.float32)

    dense = CorrBlock(f1, f2, num_levels=3, radius=3)(coords)

    pyr = build_feature_pyramid(f2, 3)
    from raft_tpu.models.corr import alternate_lookup
    ondemand = alternate_lookup(f1, pyr, coords, radius=3, backend="pallas")
    np.testing.assert_allclose(np.asarray(ondemand), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_under_jit_and_vmapless_batching(rng):
    B, C, H, W, r = 3, 16, 6, 6, 2
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 5, (B, H, W, 2)), jnp.float32)

    fn = jax.jit(lambda a, b, c: windowed_correlation_pallas(
        a, b, c, r, interpret=True))
    got = fn(f1, f2, coords)
    ref = windowed_correlation(f1, f2, coords, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _jnp_multilevel(f1, pyr, coords, radius, scale=True):
    ref = [windowed_correlation(f1, f2, coords / (2 ** l), radius, scale)
           for l, f2 in enumerate(pyr)]
    return jnp.concatenate(ref, axis=-1)


def test_fused_multilevel_matches_jnp(rng):
    # The fused single-launch kernel over a 4-level pyramid == per-level
    # jnp reference with coords/2^l (the alternate_lookup contract).
    B, C, H, W, r = 2, 32, 16, 24, 4
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(
        rng.uniform(-2.0, max(H, W) + 1.0, (B, H, W, 2)), jnp.float32)
    pyr = build_feature_pyramid(f2, 4)

    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    got = windowed_correlation_pallas_fused(f1, pyr, coords, r,
                                            interpret=True)
    ref = _jnp_multilevel(f1, pyr, coords, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_band_skipping_is_exact(rng):
    # The dynamic y-band skips rows whose hat weights are identically
    # zero — band on/off must agree bit-for-bit even with coords far
    # outside the image (empty band => all-zero windows).
    B, C, H, W, r = 1, 16, 8, 16, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    pyr = build_feature_pyramid(f2, 2)
    # one compile a band mode, run on each of the three coordinate ranges
    banded_fn, static_fn, full_fn = (jax.jit(functools.partial(
        windowed_correlation_pallas_fused, radius=r, interpret=True,
        band=mode)) for mode in ("dynamic", "static", "off"))
    for lo, hi in ((-3.0, H + 2.0), (100.0, 200.0), (-50.0, -20.0)):
        coords = jnp.asarray(rng.uniform(lo, hi, (B, H, W, 2)), jnp.float32)
        banded, static, full = (fn(f1, pyr, coords)
                                for fn in (banded_fn, static_fn, full_fn))
        np.testing.assert_array_equal(np.asarray(banded), np.asarray(full))
        np.testing.assert_array_equal(np.asarray(static), np.asarray(full))
        ref = _jnp_multilevel(f1, pyr, coords, r)
        np.testing.assert_allclose(np.asarray(banded), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_band_mode_gradients_agree(rng):
    # All three band modes (dynamic / masked-static / off) must produce
    # bit-identical df1/df2 — the masked-static mode predicates the same
    # chunk work behind pl.when instead of a traced loop bound, and the
    # backward's df1 now accumulates in scratch rather than a loop carry.
    B, C, H, W, r = 1, 16, 8, 12, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(-2, 10, (B, H, W, 2)), jnp.float32)
    pyr = build_feature_pyramid(f2, 2)
    cot = _rand(rng, B, H, W, 2 * (2 * r + 1) ** 2)
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused

    def grads(mode):
        def loss(a, b):
            out = windowed_correlation_pallas_fused(
                a, build_feature_pyramid(b, 2), coords, r,
                interpret=True, band=mode)
            return jnp.sum(out * cot)
        return jax.grad(loss, argnums=(0, 1))(f1, f2)

    g_dyn = grads("dynamic")
    g_sta = grads("static")
    g_off = grads("off")
    for a, b, c in zip(g_dyn, g_sta, g_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_band_resolve(monkeypatch):
    from raft_tpu.ops import corr_pallas as cp
    # env resolution
    monkeypatch.delenv("RAFT_CORR_BAND", raising=False)
    assert cp._resolve_band(None) == "dynamic"
    monkeypatch.setenv("RAFT_CORR_BAND", "static")
    assert cp._resolve_band(None) == "static"
    monkeypatch.setenv("RAFT_CORR_BAND", "0")
    assert cp._resolve_band(None) == "off"
    assert cp._resolve_band(True) == "dynamic"
    assert cp._resolve_band(False) == "off"
    with pytest.raises(ValueError):
        cp._resolve_band("banded")


def test_fused_multilevel_gradients(rng):
    B, C, H, W, r, L = 1, 16, 8, 12, 3, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 8, (B, H, W, 2)), jnp.float32)
    cot = _rand(rng, B, H, W, L * (2 * r + 1) ** 2)
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused

    def loss_ref(a, b):
        pyr = build_feature_pyramid(b, L)
        return jnp.sum(_jnp_multilevel(a, pyr, coords, r) * cot)

    def loss_pl(a, b):
        pyr = build_feature_pyramid(b, L)
        return jnp.sum(windowed_correlation_pallas_fused(
            a, pyr, coords, r, interpret=True) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    g_pl = jax.grad(loss_pl, argnums=(0, 1))(f1, f2)
    for a, b in zip(g_ref, g_pl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_bf16_mxu_operands_close_to_f32(rng):
    # bf16 MXU operands (f32 accumulation) stay within bf16 rounding of
    # the f32 kernel — forward and gradients.
    B, C, H, W, r = 1, 32, 8, 12, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 8, (B, H, W, 2)), jnp.float32)
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    pyr = build_feature_pyramid(f2, 2)
    f32 = windowed_correlation_pallas_fused(f1, pyr, coords, r,
                                            interpret=True)
    b16 = windowed_correlation_pallas_fused(f1, pyr, coords, r,
                                            mxu_dtype="bfloat16",
                                            interpret=True)
    # dot of C=32 bf16 products: relative error ~ C_eps ≈ 1e-2
    np.testing.assert_allclose(np.asarray(b16), np.asarray(f32),
                               rtol=0.05, atol=0.05)

    g16 = jax.grad(lambda a, b: jnp.sum(windowed_correlation_pallas_fused(
        a, build_feature_pyramid(b, 2), coords, r, mxu_dtype="bfloat16",
        interpret=True)), argnums=(0, 1))(f1, f2)
    gf = jax.grad(lambda a, b: jnp.sum(windowed_correlation_pallas_fused(
        a, build_feature_pyramid(b, 2), coords, r,
        interpret=True)), argnums=(0, 1))(f1, f2)
    for a, b in zip(gf, g16):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0.1, atol=0.1)


def test_fused_eligibility_gate(rng):
    from raft_tpu.ops.corr_pallas import fused_eligible

    # eval-scale pyramids fit (bf16 features = the mixed-precision policy)
    sintel = [(55, 128), (27, 64), (13, 32), (6, 16)]
    assert fused_eligible(sintel, 256, dtype_bytes=2)
    kitti = [(48, 156), (24, 78), (12, 39), (6, 19)]
    assert fused_eligible(kitti, 256, dtype_bytes=2)
    # an unpooled full-resolution level does not
    assert not fused_eligible([(440, 1024)], 256, dtype_bytes=4)

    # The forward launch names the diagonal sweep's band scratch (32 rows
    # of the widest level at the worst tile); it rides on top of the
    # default budget, so float32 features at Sintel, which fitted without
    # it, still do. The backward launch has none, nor has a pyramid too
    # short to hold one block of diagonals with its 2r+1 rows.
    from raft_tpu.ops import vmem
    from raft_tpu.ops.corr_pallas import corr_vmem_parts
    parts = corr_vmem_parts(sintel, 256, dtype_bytes=2)
    assert parts["band_corr_f32"] == 32 * 128 * 256 * 4
    assert vmem.total_bytes(parts) - parts["band_corr_f32"] \
        <= vmem.BUDGET_BYTES
    assert fused_eligible(sintel, 256, dtype_bytes=4)
    assert "band_corr_f32" not in corr_vmem_parts(sintel, 256, 2,
                                                  differentiable=True)
    assert "band_corr_f32" not in corr_vmem_parts([(8, 16), (4, 8)], 16)

    # forced pallas on ineligible levels is a clear error, not a Mosaic
    # failure; auto on an INELIGIBLE level must fall back to the jnp
    # path bit-for-bit on any backend (an eligible level would dispatch
    # to the kernel on TPU hosts and defeat the comparison)
    from raft_tpu.models.corr import alternate_lookup
    f1 = _rand(rng, 1, 4, 6, 8)
    big = jnp.zeros((1, 800, 800, 8), jnp.float32)   # ~20 MB > VMEM cap
    assert not fused_eligible([(800, 800)], 8, dtype_bytes=4)
    coords = jnp.zeros((1, 4, 6, 2), jnp.float32)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="VMEM"):
        alternate_lookup(f1, (big,), coords, 2, backend="pallas")
    a = alternate_lookup(f1, (big,), coords, 2, backend="auto")
    b = alternate_lookup(f1, (big,), coords, 2, backend="jnp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rescale_false_matches_materialized(rng):
    # The fork drift (rescale=False: every pooled level sampled at
    # UN-rescaled coords, core/corr.py:38-42) must hold across the
    # materialized pyramid, the jnp on-demand path, and the fused
    # Pallas kernel — including coords that land outside the pooled
    # levels' extent (where all paths must produce zeros).
    from raft_tpu.models.corr import (AlternateCorrBlock, CorrBlock,
                                      alternate_lookup,
                                      build_feature_pyramid)
    B, C, H, W, r, L = 1, 16, 12, 16, 3, 2
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(-1.0, max(H, W), (B, H, W, 2)),
                         jnp.float32)
    want = CorrBlock(f1, f2, num_levels=L, radius=r,
                     rescale=False)(coords)
    pyr = build_feature_pyramid(f2, L)
    got_jnp = alternate_lookup(f1, pyr, coords, r, backend="jnp",
                               rescale=False)
    got_pallas = AlternateCorrBlock(f1, f2, num_levels=L, radius=r,
                                    backend="pallas",
                                    rescale=False)(coords)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pallas), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_degenerate_pooled_level_matches_materialized(rng):
    # A 1-row level pools to EMPTY under VALID 2x2 (tiny inputs — e.g.
    # the multichip dryrun's shapes). The materialized pyramid yields
    # all-zero windows there (matmul over the empty axis); the on-demand
    # path must match instead of crashing the gather-based sampler, and
    # the kernel-eligibility gate must reject the shape.
    from raft_tpu.models.corr import (CorrBlock, alternate_lookup,
                                      build_feature_pyramid)
    from raft_tpu.ops.corr_pallas import fused_eligible
    B, C, H, W, r, L = 1, 8, 1, 6, 2, 2
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(0, 4, (B, H, W, 2)), jnp.float32)
    want = CorrBlock(f1, f2, num_levels=L, radius=r,
                     rescale=False)(coords)
    pyr = build_feature_pyramid(f2, L)
    assert pyr[1].shape[1] == 0
    assert not fused_eligible([p.shape[1:3] for p in pyr], C, 4, r)
    got = alternate_lookup(f1, pyr, coords, r, rescale=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_tout_bitexact(rng, monkeypatch):
    """The transposed output store (RAFT_CORR_TOUT, default on) must be
    BIT-identical to the query-minor store + external swapaxes, forward
    and gradients — it only moves the transpose from an XLA copy at the
    custom-call boundary into the kernel's final store."""
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    B, C, H, W, r = 2, 16, 8, 12, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(-2, 10, (B, H, W, 2)), jnp.float32)

    def run():
        def loss(a, b):
            out = windowed_correlation_pallas_fused(
                a, build_feature_pyramid(b, 2), coords, r,
                interpret=True)
            return jnp.sum(out * out), out
        (l, out), g = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(f1, f2)
        return out, g

    monkeypatch.setenv("RAFT_CORR_TOUT", "1")
    out_t, g_t = run()
    monkeypatch.setenv("RAFT_CORR_TOUT", "0")
    out_q, g_q = run()
    np.testing.assert_array_equal(np.asarray(out_t), np.asarray(out_q))
    np.testing.assert_array_equal(np.asarray(g_t[0]), np.asarray(g_q[0]))
    np.testing.assert_array_equal(np.asarray(g_t[1]), np.asarray(g_q[1]))


def test_out_dtype_bitexact_vs_external_cast(rng):
    # out_dtype=bfloat16 emitted from inside the kernel must be
    # BIT-identical to casting the float32 kernel output afterwards
    # (same single rounding of the f32 accumulator), forward and
    # backward — the lever only removes the XLA convert+copy at the
    # custom-call boundary, never changes numerics.
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    B, C, H, W, r = 1, 16, 8, 12, 3
    f1 = _rand(rng, B, H, W, C)
    f2 = _rand(rng, B, H, W, C)
    coords = jnp.asarray(rng.uniform(-2, 10, (B, H, W, 2)), jnp.float32)
    pyr = build_feature_pyramid(f2, 2)

    direct = windowed_correlation_pallas_fused(
        f1, pyr, coords, r, interpret=True, out_dtype=jnp.bfloat16)
    external = windowed_correlation_pallas_fused(
        f1, pyr, coords, r, interpret=True).astype(jnp.bfloat16)
    assert direct.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(direct.astype(jnp.float32)),
                                  np.asarray(external.astype(jnp.float32)))

    cot = _rand(rng, B, H, W, 2 * (2 * r + 1) ** 2).astype(jnp.bfloat16)

    def grads(out_dtype):
        def loss(a, b):
            out = windowed_correlation_pallas_fused(
                a, build_feature_pyramid(b, 2), coords, r,
                interpret=True, out_dtype=out_dtype)
            return jnp.sum(out.astype(jnp.float32)
                           * cot.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1))(f1, f2)

    g_bf = grads(jnp.bfloat16)
    g_f32 = grads(jnp.float32)
    for a, b in zip(g_bf, g_f32):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def _tiled_case(rng, name):
    """(f1, pyramid, coords, radius, rescale, tiling) of one named case
    of the 2-D query tiles: a 16 x 64 grid of 8 x 16 tiles, each reading
    a window of 32 (radius 4: 40) of level 0's 64 columns and 24 of
    level 1's 32, or a width of 62 (tiles padded to 64)."""
    from raft_tpu.ops import corr_pallas as cp
    H, W, C, L = 16, 62 if name == "width62" else 64, 8, 2
    radius = 4 if name == "f32" else 2
    dtype = jnp.bfloat16 if name == "bf16" else jnp.float32
    # Integer features keep every product's sum exact: XLA:CPU's order of
    # summation in a bfloat16 dot follows the operands' shapes, which the
    # two tilings differ in (the chip check holds real data).
    draw = ((lambda *s: rng.integers(-4, 5, s)) if name == "bf16"
            else lambda *s: rng.standard_normal(s))
    f1 = jnp.asarray(draw(1, H, W, C), dtype)
    pyr = build_feature_pyramid(jnp.asarray(draw(1, H, W, C), dtype), L)
    ys, xs = np.meshgrid(np.arange(H, dtype=np.float32),
                         np.arange(W, dtype=np.float32), indexing="ij")
    flow = np.stack([2.0 * np.sin(xs / 9.0) - 1.0,
                     1.3 * np.cos(xs / 7.0 + ys / 5.0)], -1)
    if name == "wild":          # the right half spreads past any window
        flow = np.where((xs >= 32)[..., None],
                        rng.uniform(-20, 20, (H, W, 2)), flow)
    coords = jnp.asarray((np.stack([xs, ys], -1) + flow)[None], jnp.float32)
    levels = cp._level_geometry([f2.shape[1:3] for f2 in pyr])
    rescale = name != "no_rescale"
    return (f1, pyr, coords, radius, rescale,
            cp._tiling(8, 16, levels, radius, rescale))


@pytest.mark.parametrize("name", ["f32", "bf16", "no_rescale", "width62",
                                  "wild", "one_row"])
def test_query_tiles_bitexact_vs_raster(rng, name):
    # A 2-D query tile reads a window of each level's columns; the
    # columns it leaves out carry weight exactly 0 and each output sums
    # at most two nonzero terms a side, so the result is the raster
    # launch's bit for bit, windowed tiles, whole-width tiles and all.
    from raft_tpu.ops import corr_pallas as cp
    if name == "one_row":
        # a one- or two-row grid keeps raster tiles, however wide
        for h in (1, 2):
            levels = cp._level_geometry([(h, 4096), (h // 2, 2048)])
            assert cp.choose_query_tile(h, 4096, levels, 4, 256) is None
        return
    f1, pyr, coords, radius, rescale, tiling = _tiled_case(rng, name)
    assert tiling.windows[0] == (8, 40 if radius == 4 else 32)
    mxu = "bfloat16" if name == "bf16" else "float32"
    run = functools.partial(cp._fused, radius=radius, scale=True,
                            mxu_dtype=mxu, interpret=True,
                            rescale=rescale, out_dtype=jnp.float32)
    want = np.asarray(run(f1, pyr, coords, band=None, tiling=None))
    # the wild case in all three band modes: "off" folds every diagonal
    for band in (("dynamic", "static", "off") if name == "wild"
                 else (None,)):
        got = np.asarray(run(f1, pyr, coords, band=band, tiling=tiling))
        np.testing.assert_array_equal(got, want, err_msg=str(band))
    if rescale:
        stats = cp.sweep_stats(np.asarray(coords),
                               [f2.shape[1:3] for f2 in pyr], radius, tiling)
        windowed = stats["levels"][0]["tiles_windowed"]
        if name == "wild":      # some tiles read windows, some whole rows
            assert 0 < windowed < stats["tiles"]
        else:
            assert windowed == stats["tiles"]
    if name == "f32":
        ref = _jnp_multilevel(f1, pyr, coords, radius)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                   atol=1e-5)


def test_query_tiles_gradients_match_raster(rng):
    # The backward runs raster tiles whatever tile the forward took: the
    # gradients are the raster launch's bit for bit, and coordinates get
    # zero, as ever.
    from raft_tpu.ops import corr_pallas as cp
    f1, pyr, coords, radius, _, tiling = _tiled_case(rng, "wild")
    f2 = _rand(rng, *f1.shape)
    cot = _rand(rng, *f1.shape[:3], 2 * (2 * radius + 1) ** 2)

    def grads(tiling):
        def loss(a, b, c):
            out = cp._fused(a, build_feature_pyramid(b, 2), c, radius, True,
                            "float32", True, None, True, jnp.float32, tiling)
            return jnp.sum(out * cot)
        return jax.grad(loss, argnums=(0, 1, 2))(f1, f2, coords)

    tiled = grads(tiling)
    for got, want in zip(tiled, grads(None)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(tiled[2]).any()
