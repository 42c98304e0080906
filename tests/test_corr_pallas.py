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
    for lo, hi in ((-3.0, H + 2.0), (100.0, 200.0), (-50.0, -20.0)):
        coords = jnp.asarray(rng.uniform(lo, hi, (B, H, W, 2)), jnp.float32)
        banded = windowed_correlation_pallas_fused(
            f1, pyr, coords, r, interpret=True, band="dynamic")
        static = windowed_correlation_pallas_fused(
            f1, pyr, coords, r, interpret=True, band="static")
        full = windowed_correlation_pallas_fused(
            f1, pyr, coords, r, interpret=True, band="off")
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


# ---------------------------------------------------------------------------
# The diagonal y-sweep (the forward kernel folds a target row into a
# y-offset accumulator only where the tile's hat weight can be nonzero)
# against the dense sweep it replaced, kept here as a numpy oracle.

def _dense_fwd_kernel(cx_ref, cy_ref, f1_ref, *refs, radius, scale, levels,
                      mxu_dtype, band, rescale, tout=False, band_rows=0):
    """The forward kernel as it swept before the diagonal rule, kept as
    the oracle: every row of every 8-row chunk product is folded into all
    ``2r+1`` y-offset accumulators in order of ``y`` (no band, no
    skipping), then the same x-side contraction and store. Run in the
    kernel's place under the same interpreter, so equality is bit for
    bit: same terms, same order, same float32 expressions."""
    from jax.experimental import pallas as pl
    from raft_tpu.ops import corr_pallas as cp
    from raft_tpu.ops import layout as klayout
    nl = len(levels)
    f2_refs, out_ref, t1_ref = refs[:nl], refs[nl], refs[nl + 1]
    win = 2 * radius + 1
    f1 = f1_ref[0].astype(cp._mxu(mxu_dtype))
    tq, c = f1.shape
    cx0 = cx_ref[0].astype(jnp.float32)
    cy0 = cy_ref[0].astype(jnp.float32)
    level_rows = []
    for l, (_, h2lp, w2pl) in enumerate(levels):
        lscale = (1.0 / 2 ** l) if rescale else 1.0
        cx, cy = cx0 * lscale, cy0 * lscale
        t1_ref[0:win * w2pl, :] = jnp.zeros((win * w2pl, tq), jnp.float32)

        def body(yc, carry, l=l, w2pl=w2pl, cy=cy):
            f2c = f2_refs[l][0, pl.ds(yc * (8 * w2pl), 8 * w2pl), :]
            corr = jax.lax.dot_general(
                f2c.astype(f1.dtype), f1, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            y0f = (yc * 8).astype(jnp.float32)
            for r_i in range(8):
                row = corr[r_i * w2pl:(r_i + 1) * w2pl, :]
                for i in range(win):
                    wy = cp._hat(y0f + r_i - (cy + (i - radius)))
                    t1_ref[i * w2pl:(i + 1) * w2pl, :] += wy * row
            return carry

        jax.lax.fori_loop(0, h2lp // 8, body, 0)
        xi = cp._x_iota(w2pl, tq)
        for a in range(win):
            vx = cp._hat(xi - (cx + (a - radius)))
            for b in range(win):
                t1_b = t1_ref[b * w2pl:(b + 1) * w2pl, :]
                level_rows.append(jnp.sum(t1_b * vx, axis=0, keepdims=True))
    out = jnp.concatenate(level_rows, axis=0)
    if scale:
        out = out * (1.0 / (c ** 0.5))
    klayout.boundary_store(out_ref, out, transpose=tout)


@functools.lru_cache(maxsize=None)
def _fused_interpret(radius, band, rescale, which="kernel"):
    """The fused lookup in interpret mode under ``jax.jit``, one compile
    per (radius, band, rescale) shared by the cases below. ``which`` only
    keys the cache: the oracle's entry is traced while ``_fwd_kernel`` is
    patched, the scratch-limited one while ``_BAND_ROWS`` is."""
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    return jax.jit(lambda f1, pyr, coords: windowed_correlation_pallas_fused(
        f1, pyr, coords, radius, interpret=True, band=band, rescale=rescale))


def _sweep_case(rng, name):
    """(f1, pyramid, coords, radius, rescale) of one named case: a 24 x 64
    grid (a query tile is four raster rows) over levels of 24 and 12
    rows; the second is not a multiple of the 8-row chunk."""
    B, C, H, W, L = 1, 8, 24, 64, 2
    radius = 3 if name.endswith("r3") else 4
    f1 = _rand(rng, B, H, W, C)
    pyr = build_feature_pyramid(_rand(rng, B, H, W, C), L)
    ys, xs = np.meshgrid(np.arange(H, dtype=np.float32),
                         np.arange(W, dtype=np.float32), indexing="ij")
    grid = np.stack([xs, ys], -1)[None]
    kind = name.rsplit("_", 1)[0]
    if kind in ("smooth", "no_rescale", "small_scratch"):
        flow = np.stack([1.7 * np.sin(xs / 5.0), 0.8 * np.cos(ys / 3.0)], -1)
    elif kind == "wild":        # every diagonal of every level is live
        flow = rng.uniform(-H, H, (B, H, W, 2))
    elif kind == "integer_cy":  # the neighbour diagonals' weight is exactly 0
        flow = np.stack([0.3 + 0 * xs, np.round(2 * np.sin(xs / 4.0))], -1)
    else:                       # above / below / straddling the image
        dy = {"above": -3.0 * H, "below": 3.0 * H, "straddle_top": -H + 1.5,
              "straddle_bottom": H - 2.5}[kind]
        flow = np.stack([0.4 * np.sin(ys), dy + 0.6 * np.cos(xs / 2.0)], -1)
    coords = jnp.asarray(grid + flow, jnp.float32)
    return f1, pyr, coords, radius, kind != "no_rescale"


@pytest.mark.parametrize("name", [
    "smooth_r4", "smooth_r3", "wild_r4", "wild_r3", "integer_cy_r4",
    "above_r3", "below_r4", "straddle_top_r4", "straddle_bottom_r3",
    "no_rescale_r3", "small_scratch_r4"])
def test_diagonal_sweep_bitexact_vs_dense_oracle(rng, name, monkeypatch):
    # The forward kernel leaves out exactly the (row, offset) pairs whose
    # hat weight is 0 for the whole query tile, so every accumulator gets
    # the same nonzero terms in the same order: bit-identical to the
    # dense sweep, in all three band modes ("off" sweeps every diagonal).
    # ``small_scratch``: a band scratch of 16 rows, so that the tiles in
    # the middle of the 24-row level take the dense sweep and the others
    # the diagonal one, inside one launch.
    from raft_tpu.ops import corr_pallas
    f1, pyr, coords, radius, rescale = _sweep_case(rng, name)
    with monkeypatch.context() as m:
        m.setattr(corr_pallas, "_fwd_kernel", _dense_fwd_kernel)
        want = np.asarray(_fused_interpret(radius, "off", rescale, "oracle")(
            f1, pyr, coords))
    which = "kernel"
    levels = corr_pallas._level_geometry([f2.shape[1:3] for f2 in pyr])
    if name.startswith("small_scratch"):
        monkeypatch.setattr(corr_pallas, "_BAND_ROWS", 16)
        which = "small_scratch"
        chunks = [corr_pallas._band_chunks(cy, radius, 24, 3) for cy in
                  coords[0, ..., 1].reshape(-1, 256)]
        fits = [int(hi - lo) <= 2 for lo, hi in chunks]
        assert any(fits) and not all(fits)
    else:           # the whole 24-row level is parked: no dense sweep
        assert corr_pallas._band_scratch_rows(levels, radius) == 24
    for band in ("dynamic", "static", "off"):
        got = _fused_interpret(radius, band, rescale, which)(f1, pyr, coords)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=band)
    if "above" in name or "below" in name:
        assert not want.any()       # no row of the image is in reach
    else:
        ref = jnp.concatenate([
            windowed_correlation(f1, f2, coords / (2 ** l if rescale else 1),
                                 radius) for l, f2 in enumerate(pyr)], -1)
        np.testing.assert_allclose(want, np.asarray(ref), rtol=1e-5,
                                   atol=1e-5)
        assert np.abs(want).max() > 0.1


def _brute_force_pairs(cy_tile, h2l, radius, held):
    """What the forward kernel folds for one tile at one level, by
    enumeration: ``dense`` is every offset of every row of the chunks
    ``_band_chunks`` gives; ``live`` the pairs among them that can carry
    a nonzero weight (a row of the image with ``floor(min cy) <= y - off
    <= ceil(max cy)``); ``diagonal`` every offset of every diagonal of
    the blocks the diagonal sweep steps through, or the dense count
    where the kernel keeps the dense sweep (band taller than the
    ``held`` chunks of scratch, or no fewer pairs by diagonals)."""
    from raft_tpu.ops import corr_pallas as cp
    c_lo, c_hi = (int(v) for v in cp._band_chunks(
        jnp.asarray(cy_tile), radius, h2l, -(-h2l // 8)))
    rows = [y for c in range(c_lo, c_hi) for y in range(c * 8, c * 8 + 8)]
    offs = range(-radius, radius + 1)
    dense = len(rows) * len(offs)
    d_lo = max(int(np.floor(cy_tile.min())), -radius)
    d_hi = min(int(np.ceil(cy_tile.max())), h2l - 1 + radius)
    live = sum(1 for y in range(h2l) for off in offs
               if rows and d_lo <= y - off <= d_hi)
    stepped = [d for d0 in range(d_lo, d_hi + 1, cp._DIAG_BLOCK)
               for d in range(d0, d0 + cp._DIAG_BLOCK)]
    by_diagonals = len(stepped) * len(offs)
    diagonal = (by_diagonals if c_hi - c_lo <= held and by_diagonals < dense
                else dense)
    return {"diagonal": diagonal, "dense": dense, "live": live,
            "tiles_diagonal": int(diagonal != dense)}


@pytest.mark.parametrize("case", ["two_rows", "spread", "outside", "r3"])
def test_sweep_stats_matches_enumeration(rng, case):
    from raft_tpu.ops.corr_pallas import sweep_stats
    radius = 3 if case == "r3" else 4
    H, W, tq = 16, 64, 128                  # a tile is two raster rows
    shapes = [(40, 64), (20, 32), (10, 16)]  # 40 > the 32 rows of scratch
    ys, xs = np.meshgrid(np.arange(H, dtype=np.float32),
                         np.arange(W, dtype=np.float32), indexing="ij")
    dy = {"two_rows": 0.25 + 0 * xs, "r3": 0.5 * np.sin(xs / 7.0),
          "spread": np.where(ys < 8, rng.uniform(-2, 2, (H, W)),
                             rng.uniform(-6, 26, (H, W))),
          "outside": 90.0 + np.cos(xs)}[case]
    coords = np.stack([xs, ys + dy], -1)[None].astype(np.float32)
    got = sweep_stats(coords, shapes, radius, tq)
    assert got["tiles"] == H * W // tq and got["tq"] == tq
    cy = coords[0, ..., 1].reshape(-1, tq)
    for l, (h2l, _) in enumerate(shapes):
        held = min(32, -(-h2l // 8) * 8) // 8
        want = [_brute_force_pairs(t * np.float32(1 / 2 ** l), h2l, radius,
                                   held) for t in cy]
        assert got["levels"][l] == {
            key: sum(w[key] for w in want) for key in want[0]}
        assert (got["levels"][l]["live"] <= got["levels"][l]["diagonal"]
                <= got["levels"][l]["dense"])
    for key in ("diagonal", "dense", "live"):
        assert got[key] == sum(v[key] for v in got["levels"])
    if case == "two_rows":
        # by hand, level 0: cy spans [y + .25, y + 1.25], so diagonals
        # y .. y + 2: two blocks of 2, 4 x 9 pairs a tile of which 3 x 9
        # are live (less the rows above the image: 4 + 3 + 2 at the
        # first tile, 2 + 1 at the second), where the dense sweep folds
        # 2 or 3 chunks: 144 or 216
        assert got["levels"][0]["diagonal"] == 8 * 36
        assert got["levels"][0]["live"] == 8 * 27 - (4 + 3 + 2) - (2 + 1)
        assert got["levels"][0]["tiles_diagonal"] == 8
    if case == "spread":        # the lower tiles' bands pass the scratch
        assert got["levels"][0]["tiles_diagonal"] == 4
    if case == "outside":       # below every level: nothing to fold
        assert got["diagonal"] == 0 and got["live"] == 0
