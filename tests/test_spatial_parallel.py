"""Compiler-partitioned spatial parallelism: the unmodified RAFT forward
jitted with row-sharded images must equal the replicated forward."""

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import RAFTConfig
from raft_tpu.models import RAFT
from raft_tpu.parallel.mesh import make_mesh
from raft_tpu.parallel.spatial import image_spec, spatial_jit


def _init(model, img1, img2):
    """The model's variables, initialised as one compiled program."""
    key = jax.random.PRNGKey(0)
    return jax.jit(lambda a, b: model.init(
        {"params": key, "dropout": key}, a, b, iters=1))(img1, img2)

def test_spatial_forward_matches_replicated(rng):
    cfg = RAFTConfig(small=True, iters=3)
    model = RAFT(cfg)
    B, H, W = 2, 32, 48
    img1 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    vs = _init(model, img1, img2)

    ref = jax.jit(lambda v, a, b: model.apply(v, a, b, test_mode=True)[1])(
        vs, img1, img2)

    mesh = make_mesh(n_data=2, n_spatial=4)
    fwd = spatial_jit(
        lambda v, a, b: model.apply(v, a, b, test_mode=True)[1], mesh)
    got = fwd(vs, img1, img2)

    # each device computes with halos; numerics identical up to reduction
    # order inside XLA collectives
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sharded_banded_lookup_matches_unsharded(rng):
    """_sharded_fused_lookup (shard_map composition, VERDICT r4 #2) must
    be bit-faithful to the unsharded fused kernel — forward AND both
    feature gradients (the pyramid all-gather's transpose must psum the
    per-shard df2 contributions exactly once)."""
    from raft_tpu.models.corr import (_sharded_fused_lookup,
                                      build_feature_pyramid)
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused

    B, H, W, C = 2, 8, 16, 32
    f1 = jnp.asarray(rng.normal(size=(B, H, W, C)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(B, H, W, C)), jnp.float32)
    coords = jnp.asarray(
        rng.uniform(-2, [H + 2, W + 2], (B, H, W, 2))[..., ::-1],
        jnp.float32)                                   # (x, y), off-grid
    pyr = build_feature_pyramid(f2, 2)
    mesh = make_mesh(n_data=2, n_spatial=4)

    def ref_loss(f1, pyr):
        out = windowed_correlation_pallas_fused(f1, pyr, coords, 3)
        return jnp.sum(out * out), out

    def sharded_loss(f1, pyr):
        out = _sharded_fused_lookup(f1, pyr, coords, mesh, 3, True,
                                    "float32", True, jnp.float32)
        return jnp.sum(out * out), out

    (ref_l, ref_out), ref_g = jax.value_and_grad(
        ref_loss, argnums=(0, 1), has_aux=True)(f1, pyr)
    with mesh:
        (sh_l, sh_out), sh_g = jax.jit(jax.value_and_grad(
            sharded_loss, argnums=(0, 1), has_aux=True))(f1, pyr)

    np.testing.assert_allclose(np.asarray(sh_out), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh_g[0]), np.asarray(ref_g[0]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(sh_g[1], ref_g[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_spatial_banded_engine_matches_replicated(rng, monkeypatch):
    """Full RAFT forward through the BANDED engine under spatial_jit
    (trace-time mesh context → shard_map around the kernel) must match
    the unsharded banded forward. RAFT_CORR_BACKEND=pallas forces the
    kernel (interpret mode on CPU) through the auto dispatch."""
    monkeypatch.setenv("RAFT_CORR_BACKEND", "pallas")
    cfg = RAFTConfig(small=True, iters=2, alternate_corr=True)
    model = RAFT(cfg)
    B, H, W = 2, 64, 96           # h8=8: no degenerate pooled level
    img1 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    vs = _init(model, img1, img2)

    ref = jax.jit(lambda v, a, b: model.apply(v, a, b, test_mode=True)[1])(
        vs, img1, img2)

    mesh = make_mesh(n_data=2, n_spatial=2)      # h8 = 4 rows, 2 shards
    fwd = spatial_jit(
        lambda v, a, b: model.apply(v, a, b, test_mode=True)[1], mesh)
    got = fwd(vs, img1, img2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_spatial_sharding_actually_partitions(rng):
    cfg = RAFTConfig(small=True, iters=2)
    model = RAFT(cfg)
    B, H, W = 1, 16, 32
    img = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    vs = _init(model, img, img)

    mesh = make_mesh(n_data=1, n_spatial=8)
    fwd = spatial_jit(
        lambda v, a, b: model.apply(v, a, b, test_mode=True)[1], mesh,
        shard_batch=False)
    out = fwd(vs, img, img)
    assert out.sharding.num_devices == 8
    assert out.shape == (B, H, W, 2)
