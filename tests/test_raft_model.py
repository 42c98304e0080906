"""RAFT model behavior tests (shapes, modes, config guards)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from raft_tpu.config import RAFTConfig
from raft_tpu.models import RAFT


@pytest.fixture(scope="module")
def small_model():
    cfg = RAFTConfig(small=True)
    m = RAFT(cfg)
    img = jnp.zeros((1, 64, 96, 3), jnp.float32)
    variables = jax.jit(lambda a, b: m.init(jax.random.PRNGKey(0), a, b,
                                            iters=1))(img, img)
    return m, variables


def test_param_counts_match_reference(small_model):
    """Reference RAFT-small ~0.99M params, RAFT ~5.26M."""
    _, variables = small_model
    n_small = sum(x.size for x in jax.tree.leaves(variables["params"]))
    assert n_small == 990_162

    m = RAFT(RAFTConfig())
    img = jnp.zeros((1, 64, 96, 3), jnp.float32)
    v = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), img, img,
                                      iters=1))      # shapes are enough
    n_large = sum(x.size for x in jax.tree.leaves(v["params"]))
    assert n_large == 5_257_536


def test_train_mode_returns_all_iterations(small_model):
    m, v = small_model
    img = jnp.zeros((2, 64, 96, 3), jnp.float32)
    out = m.apply(v, img, img, iters=3)
    assert out.shape == (3, 2, 64, 96, 2)


def test_test_mode_returns_low_and_up(small_model):
    m, v = small_model
    img = jnp.zeros((1, 64, 96, 3), jnp.float32)
    lo, up = m.apply(v, img, img, iters=2, test_mode=True)
    assert lo.shape == (1, 8, 12, 2)
    assert up.shape == (1, 64, 96, 2)


def test_flow_init_shifts_first_lookup(small_model):
    m, v = small_model
    rng = np.random.default_rng(3)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    lo0, _ = m.apply(v, img1, img2, iters=1, test_mode=True)
    init = jnp.ones((1, 8, 12, 2), jnp.float32) * 2.0
    lo1, _ = m.apply(v, img1, img2, iters=1, flow_init=init, test_mode=True)
    assert float(jnp.abs(lo1 - lo0).max()) > 0.1


def test_normalized_coords_rejected():
    m = RAFT(RAFTConfig(small=True, normalized_coords=True))
    img = jnp.zeros((1, 64, 96, 3), jnp.float32)
    with pytest.raises(ValueError, match="normalized_coords"):
        m.init(jax.random.PRNGKey(0), img, img, iters=1)


def test_mixed_precision_runs_and_outputs_f32(small_model):
    _, v = small_model
    m = RAFT(RAFTConfig(small=True, mixed_precision=True))
    img = jnp.full((1, 64, 96, 3), 128.0, jnp.float32)
    out = m.apply(v, img, img, iters=2)
    assert out.dtype == jnp.float32
    assert bool(jnp.isfinite(out).all())


def test_gradients_flow(small_model):
    """The per-iteration stop_gradient must still leave a nonzero grad
    path through every iteration's update."""
    m, v = small_model
    rng = np.random.default_rng(5)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)

    def loss(params):
        out = m.apply({"params": params}, img1, img2, iters=2)
        return jnp.abs(out).mean()

    g = jax.jit(jax.grad(loss))(v["params"])
    norms = [float(jnp.abs(x).max()) for x in jax.tree.leaves(g)]
    assert max(norms) > 0


def test_large_model_gated_test_mode_matches_training_path():
    """test_mode runs the mask head + convex upsampling only on the last
    iteration (round 5: two-call scan structure — (iters-1) statically
    mask-free iterations, then one mask-computing call); its output must
    equal the ungated training path's final prediction exactly."""
    cfg = RAFTConfig(iters=4)      # large model: mask head present
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(3)
    img1 = jax.random.uniform(rng, (1, 32, 48, 3)) * 255.0
    img2 = jax.random.uniform(jax.random.fold_in(rng, 1),
                              (1, 32, 48, 3)) * 255.0
    vs = model.init({"params": rng, "dropout": rng}, img1, img2, iters=1)

    preds = model.apply(vs, img1, img2)                 # ungated, all iters
    low, up = model.apply(vs, img1, img2, test_mode=True)   # gated
    np.testing.assert_allclose(np.asarray(up), np.asarray(preds[-1]),
                               rtol=1e-6, atol=1e-5)
    assert up.shape == (1, 32, 48, 2)


def test_bfloat16_corr_storage_close_to_float32():
    """corr_dtype='bfloat16' stores the correlation pyramid in half the
    bytes; outputs must stay within bfloat16 rounding of the float32 path
    (the volume is still computed and pooled in float32)."""
    rng = jax.random.PRNGKey(5)
    img1 = jax.random.uniform(rng, (1, 32, 48, 3)) * 255.0
    img2 = jax.random.uniform(jax.random.fold_in(rng, 1),
                              (1, 32, 48, 3)) * 255.0
    m32 = RAFT(RAFTConfig(iters=3))
    m16 = RAFT(RAFTConfig(iters=3, corr_dtype="bfloat16"))
    vs = m32.init({"params": rng, "dropout": rng}, img1, img2, iters=1)
    up32 = m32.apply(vs, img1, img2, test_mode=True)[1]
    up16 = m16.apply(vs, img1, img2, test_mode=True)[1]
    diff = float(jnp.abs(up32 - up16).max())
    scale = float(jnp.abs(up32).max()) + 1e-6
    assert diff / scale < 0.02, (diff, scale)
