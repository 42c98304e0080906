"""Parity tests for the Pallas MSDA kernel (raft_tpu/ops/msda_pallas.py)
against the vectorized jnp reference core (raft_tpu/ops/msda.py) — the
reference-implementation-vs-kernel pattern of the reference's own op
harness (reference ``core/ops/test.py:32-86``), covering forward and all
three gradients (value, sampling locations, attention weights).

Runs in Pallas interpreter mode on the CPU test mesh; shapes are kept
tiny. Locations are sampled away from exact-integer pixel coordinates
(measure-zero kinks where the piecewise-linear bilinear gradient has two
valid subgradients; see the kernel module docstring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.msda import ms_deform_attn
from raft_tpu.ops.msda_pallas import ms_deform_attn_pallas, pallas_eligible

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret

SHAPES = [(6, 9), (3, 5)]          # two levels
B, M, D, P = 2, 4, 8, 3            # D*H sublane-aligned for both levels
S = sum(h * w for h, w in SHAPES)
LQ = 37                            # off lane-multiple: exercises padding


def _inputs(seed=0, lq=LQ):
    rng = np.random.RandomState(seed)
    value = rng.randn(B, S, M, D).astype(np.float32)
    # include out-of-range locations to exercise zeros-padding border
    loc = rng.uniform(-0.2, 1.2, (B, lq, M, len(SHAPES), P, 2))
    # nudge any near-integer pixel coordinate off the kink
    for lvl, (h, w) in enumerate(SHAPES):
        for axis, extent in ((0, w), (1, h)):
            px = loc[..., lvl, :, axis] * extent - 0.5
            frac = np.abs(px - np.round(px))
            loc[..., lvl, :, axis] += np.where(frac < 1e-3, 7e-3, 0.0)
    loc = loc.astype(np.float32)
    w = rng.rand(B, lq, M, len(SHAPES), P).astype(np.float32)
    w = w / w.sum(axis=(3, 4), keepdims=True)
    return jnp.asarray(value), jnp.asarray(loc), jnp.asarray(w)


def test_eligibility():
    assert pallas_eligible((B, S, M, D), SHAPES)
    # a level too large for the VMEM-resident layout is rejected
    assert not pallas_eligible((1, 512 * 512, 8, 32), [(512, 512)])


def test_forward_parity():
    value, loc, w = _inputs()
    ref = ms_deform_attn(value, SHAPES, loc, w)
    out = ms_deform_attn_pallas(value, SHAPES, loc, w)
    assert out.shape == ref.shape == (B, LQ, M * D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_forward_parity_three_level_pyramid():
    """Stride-8/16/32 style level pyramid (the encoder-family regime)."""
    shapes = [(8, 12), (4, 6), (2, 3)]
    s = sum(h * w for h, w in shapes)
    rng = np.random.RandomState(5)
    value = jnp.asarray(rng.randn(1, s, M, D).astype(np.float32))
    loc = jnp.asarray(
        rng.uniform(0.05, 0.95, (1, s, M, 3, P, 2)).astype(np.float32))
    w = rng.rand(1, s, M, 3, P).astype(np.float32)
    w = jnp.asarray(w / w.sum(axis=(3, 4), keepdims=True))
    ref = ms_deform_attn(value, shapes, loc, w)
    out = ms_deform_attn_pallas(value, shapes, loc, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_forward_parity_lane_multiple_queries():
    value, loc, w = _inputs(seed=3, lq=128)
    ref = ms_deform_attn(value, SHAPES, loc, w)
    out = ms_deform_attn_pallas(value, SHAPES, loc, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("argnum,name",
                         [(0, "value"), (1, "locations"), (2, "weights")])
def test_gradient_parity(argnum, name):
    value, loc, w = _inputs(seed=1)
    cot = jnp.asarray(
        np.random.RandomState(9).randn(B, LQ, M * D).astype(np.float32))

    def loss(fn):
        def f(*args):
            return jnp.sum(fn(args[0], SHAPES, args[1], args[2]) * cot)
        return f

    g_ref = jax.grad(loss(ms_deform_attn), argnums=argnum)(value, loc, w)
    g_ker = jax.grad(loss(ms_deform_attn_pallas), argnums=argnum)(
        value, loc, w)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               atol=2e-3, rtol=1e-3, err_msg=name)


def test_dispatch_validation():
    value, loc, w = _inputs(seed=7)
    with pytest.raises(ValueError, match="unknown MSDA backend"):
        ms_deform_attn(value, SHAPES, loc, w, backend="palas")
    # forced pallas on ineligible shapes is a clear error, not a Mosaic
    # failure: a 1024x1024 level's value block blows the VMEM budget
    big = [(1024, 1024)]
    s = 1024 * 1024
    bv = jnp.zeros((1, s, 1, 8), jnp.float32)
    bl = jnp.zeros((1, 4, 1, 1, 2, 2), jnp.float32)
    bw = jnp.ones((1, 4, 1, 1, 2), jnp.float32) / 2.0
    with pytest.raises(ValueError, match="VMEM"):
        ms_deform_attn(bv, big, bl, bw, backend="pallas")


def test_auto_dispatch_small_query_matches_jnp():
    """Below the dense-query threshold auto must take the jnp path
    bit-for-bit (it is the jnp path)."""
    value, loc, w = _inputs(seed=8)
    a = ms_deform_attn(value, SHAPES, loc, w, backend="auto")
    b = ms_deform_attn(value, SHAPES, loc, w, backend="jnp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_module_backend_parity():
    """MSDeformAttn(backend='pallas') == backend='jnp' through the flax
    module (value projection, offset/weight heads, output projection)."""
    from raft_tpu.models.deformable import MSDeformAttn

    rng = jax.random.PRNGKey(0)
    d_model, lq = 32, 23
    query = jax.random.normal(rng, (B, lq, d_model))
    value = jax.random.normal(jax.random.PRNGKey(1), (B, S, d_model))
    ref_pts = jax.random.uniform(jax.random.PRNGKey(2),
                                 (B, lq, len(SHAPES), 2))
    outs = {}
    for backend in ("jnp", "pallas"):
        mod = MSDeformAttn(d_model=d_model, n_levels=len(SHAPES),
                           n_heads=4, n_points=P, backend=backend)
        variables = mod.init(rng, query, ref_pts, value, SHAPES)
        out, weights = mod.apply(variables, query, ref_pts, value, SHAPES)
        outs[backend] = out
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["jnp"]),
                               atol=1e-4, rtol=1e-4)


def _sweep_inputs(shapes, m, d, lq, seed):
    """Off-kink inputs for arbitrary (levels, heads, channels)."""
    rng = np.random.RandomState(seed)
    s = sum(h * w for h, w in shapes)
    value = rng.randn(1, s, m, d).astype(np.float32)
    loc = rng.uniform(-0.2, 1.2, (1, lq, m, len(shapes), 3, 2))
    for lvl, (h, w) in enumerate(shapes):
        for axis, extent in ((0, w), (1, h)):
            px = loc[..., lvl, :, axis] * extent - 0.5
            frac = np.abs(px - np.round(px))
            loc[..., lvl, :, axis] += np.where(frac < 1e-3, 7e-3, 0.0)
    wts = rng.rand(1, lq, m, len(shapes), 3).astype(np.float32)
    wts = wts / wts.sum(axis=(3, 4), keepdims=True)
    return (jnp.asarray(value), jnp.asarray(loc.astype(np.float32)),
            jnp.asarray(wts))


# Reference core/ops/test.py:63-78 sweeps odd / non-power-of-2 / huge
# channel counts {30, 32, 64, 71, 1025, 2048, 3096}. Same sweep against
# the Pallas kernel; levels use h=8 rows so every d keeps the kernel's
# (d*h) % 8 == 0 layout eligible — shape generality of the ELIGIBLE gate
# is exactly what the dispatch threshold makes load-bearing (VERDICT r2
# #7). 2048/3096 are exercised via the eligibility predicate only (the
# interpreter-mode forward at those widths adds minutes for no new code
# path beyond 1025).
@pytest.mark.parametrize("m,d", [(2, 30), (2, 32), (4, 64), (2, 71),
                                 (2, 1025)])
def test_channel_sweep_forward_parity(m, d):
    shapes = [(8, 4), (8, 3)] if d <= 128 else [(8, 4)]
    value, loc, w = _sweep_inputs(shapes, m, d, lq=16, seed=d)
    assert pallas_eligible(value.shape, shapes)
    ref = ms_deform_attn(value, shapes, loc, w)
    out = ms_deform_attn_pallas(value, shapes, loc, w)
    assert out.shape == ref.shape == (1, 16, m * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-4, rtol=1e-4)


def test_channel_sweep_eligibility_boundaries():
    # huge-channel shapes from the reference sweep stay eligible while
    # they fit VMEM, and are rejected exactly at the budget, not by Mosaic
    assert pallas_eligible((1, 32, 2, 2048), [(8, 4)])
    assert pallas_eligible((1, 32, 2, 3096), [(8, 4)])
    assert not pallas_eligible((1, 64 * 64, 8, 3096), [(64, 64)])


@pytest.mark.parametrize("m,d", [(2, 30), (2, 71)])
def test_channel_sweep_gradient_parity(m, d):
    shapes = [(8, 4), (8, 3)]
    value, loc, w = _sweep_inputs(shapes, m, d, lq=8, seed=100 + d)
    cot = jnp.asarray(
        np.random.RandomState(d).randn(1, 8, m * d).astype(np.float32))

    def loss(fn):
        def f(*args):
            return jnp.sum(fn(args[0], shapes, args[1], args[2]) * cot)
        return f

    # one backward pass a path, for all three inputs
    g_ref = jax.jit(jax.grad(loss(ms_deform_attn), argnums=(0, 1, 2)))(
        value, loc, w)
    g_ker = jax.jit(jax.grad(loss(ms_deform_attn_pallas),
                             argnums=(0, 1, 2)))(value, loc, w)
    for ker, ref, name in zip(g_ker, g_ref,
                              ("value", "locations", "weights")):
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-3, rtol=1e-3,
                                   err_msg=f"d={d} {name}")


def test_unaligned_channel_level_clean_fallback():
    """A level whose (d*h) breaks sublane alignment (d=30, h=3) must be
    reported ineligible, make backend='pallas' raise a clear ValueError
    (not a Mosaic layout error), and leave backend='auto' numerically
    identical to the jnp core."""
    shapes = [(3, 5)]
    value, loc, w = _sweep_inputs(shapes, 2, 30, lq=8, seed=0)
    assert not pallas_eligible(value.shape, shapes)
    with pytest.raises(ValueError, match="pallas"):
        ms_deform_attn(value, shapes, loc, w, backend="pallas")
    a = ms_deform_attn(value, shapes, loc, w, backend="auto")
    b = ms_deform_attn(value, shapes, loc, w, backend="jnp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
