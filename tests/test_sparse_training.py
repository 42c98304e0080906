"""The sparse ("ours") family through the train step, on one device and
data-parallel over the 8-device mesh. A file of its own: the two are a
tenth of tier-1's test time, and ``--dist loadfile`` hands a file to one
worker."""

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import TrainConfig
from raft_tpu.parallel import (create_train_state, make_mesh,
                               make_train_step, shard_batch)


def _tiny_batch(rng, B=2, H=64, W=64):
    return {
        "image1": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "image2": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "flow": jnp.asarray(rng.normal(size=(B, H, W, 2)) * 2, jnp.float32),
        "valid": jnp.ones((B, H, W), jnp.float32),
    }


def test_sparse_family_sharded_matches_single_device(rng):
    """The second model family is data-parallel-correct too: one sharded
    step over the 8-device mesh equals the single-device step."""
    from raft_tpu.config import OursConfig
    from raft_tpu.models import SparseRAFT

    H, W = 32, 48
    tcfg = TrainConfig(batch_size=8, image_size=(H, W), num_steps=10,
                       iters=2, model_family="sparse", sparse_lambda=0.1)
    cfg = OursConfig(base_channel=16, d_model=32, num_feature_levels=2,
                     outer_iterations=2, num_keypoints=4, n_heads=4,
                     n_points=2, dropout=0.0)
    model = SparseRAFT(cfg)
    batch = _tiny_batch(rng, B=8, H=H, W=W)
    key = jax.random.PRNGKey(1)

    state1 = create_train_state(jax.random.PRNGKey(0), model, tcfg, (H, W))
    _, m_single = make_train_step(tcfg, donate=False)(state1, batch, key)

    mesh = make_mesh()
    with mesh:
        state2 = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                    (H, W), mesh=mesh)
        _, m_shard = make_train_step(tcfg, mesh=mesh, donate=False)(
            state2, shard_batch(batch, mesh), key)
    np.testing.assert_allclose(float(m_single["loss"]),
                               float(m_shard["loss"]), rtol=2e-4)
    np.testing.assert_allclose(float(m_single["sparse_loss"]),
                               float(m_shard["sparse_loss"]), rtol=2e-4)


def test_sparse_family_train_step(rng):
    """One train step of the sparse ("ours") family — the fork's active
    trainer (reference train.py:19 → core/ours.py) — with the auxiliary
    sparse loss gated on."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import OursConfig, TrainConfig
    from raft_tpu.models import SparseRAFT
    from raft_tpu.parallel import create_train_state, make_train_step

    H, W = 32, 48
    tcfg = TrainConfig(batch_size=2, image_size=(H, W), num_steps=10,
                       iters=2, model_family="sparse", sparse_lambda=0.1,
                       lr=1e-4)
    cfg = OursConfig(base_channel=16, d_model=32, num_feature_levels=2,
                     outer_iterations=2, num_keypoints=4, n_heads=4,
                     n_points=2, dropout=0.0)
    model = SparseRAFT(cfg)
    state = create_train_state(jax.random.PRNGKey(0), model, tcfg, (H, W))
    params_before = jax.device_get(state.params)
    step_fn = make_train_step(tcfg)

    batch = {
        "image1": jnp.asarray(rng.uniform(0, 255, (2, H, W, 3)),
                              jnp.float32),
        "image2": jnp.asarray(rng.uniform(0, 255, (2, H, W, 3)),
                              jnp.float32),
        "flow": jnp.asarray(rng.standard_normal((2, H, W, 2)),
                            jnp.float32),
        "valid": jnp.ones((2, H, W), jnp.float32),
    }
    state2, metrics = step_fn(state, batch, jax.random.PRNGKey(1))
    assert jnp.isfinite(metrics["loss"])
    assert "sparse_loss" in metrics and jnp.isfinite(metrics["sparse_loss"])
    # params actually moved
    diff = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.abs(x).sum()),
        jax.tree_util.tree_map(jnp.subtract, jax.device_get(state2.params),
                               params_before),
        0.0)
    assert diff > 0
