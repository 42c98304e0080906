"""The sparse ("ours") family through the train step, on one device and
data-parallel over the 8-device mesh. A file of its own: its two
compiles are the longest of the family's tests, and ``--dist loadfile``
hands a file to one worker.

Both steps are lowered and compiled once, in a module fixture that the
first test sets up, the two compiles side by side (XLA compiles outside
Python's lock): this file has two tests, so ``--dist loadfile``, which
hands out the files with the most tests first, starts it last. The model
is the smallest that still carries the family's parts (two feature
levels, one outer iteration of the deformable decoder, the auxiliary
sparse loss). Its state is initialised under ``jit``: eagerly, the
family's init alone takes half a minute of this CPU."""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import OursConfig, TrainConfig
from raft_tpu.models import SparseRAFT
from raft_tpu.parallel import make_mesh, make_train_step, shard_batch
from raft_tpu.parallel.mesh import replicate

from conftest import jit_train_state

H, W = 32, 48
TCFG = TrainConfig(batch_size=8, image_size=(H, W), num_steps=10, iters=2,
                   model_family="sparse", sparse_lambda=0.1)
MODEL = SparseRAFT(OursConfig(base_channel=16, d_model=32,
                              num_feature_levels=2, outer_iterations=1,
                              num_keypoints=4, n_heads=4, n_points=2,
                              dropout=0.0))
KEY = jax.random.PRNGKey(1)


def _tiny_batch(B=8):
    rng = np.random.default_rng(0)
    return {
        "image1": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "image2": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "flow": jnp.asarray(rng.normal(size=(B, H, W, 2)) * 2, jnp.float32),
        "valid": jnp.ones((B, H, W), jnp.float32),
    }


@pytest.fixture(scope="module")
def steps():
    """The state before; the state and metrics after one single-device
    step; the metrics of the same step data-parallel over the mesh, from
    the same state and batch."""
    batch = _tiny_batch()
    state = jit_train_state(jax.random.PRNGKey(0), MODEL, TCFG, (H, W))
    single = make_train_step(TCFG, donate=False).lower(state, batch, KEY)
    mesh = make_mesh()
    with mesh:
        # what create_train_state(mesh=mesh) hands the step
        state8, batch8 = replicate(state, mesh), shard_batch(batch, mesh)
        sharded = make_train_step(TCFG, mesh=mesh, donate=False).lower(
            state8, batch8, KEY)
    with ThreadPoolExecutor(2) as pool:
        single, sharded = pool.map(lambda step: step.compile(),
                                   (single, sharded))
    after, metrics = single(state, batch, KEY)
    return state, after, metrics, sharded(state8, batch8, KEY)[1]


def test_sparse_family_train_step(steps):
    """One train step of the sparse ("ours") family — the fork's active
    trainer (reference train.py:19 → core/ours.py) — with the auxiliary
    sparse loss gated on."""
    state, after, metrics, _ = steps
    assert jnp.isfinite(metrics["loss"])
    assert "sparse_loss" in metrics and jnp.isfinite(metrics["sparse_loss"])
    # params actually moved
    diff = sum(float(np.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(jax.device_get(after.params)),
        jax.tree.leaves(jax.device_get(state.params))))
    assert diff > 0


def test_sparse_family_sharded_matches_single_device(steps):
    """The second model family is data-parallel-correct too: one sharded
    step over the 8-device mesh equals the single-device step."""
    _, _, m_single, m_shard = steps
    np.testing.assert_allclose(float(m_single["loss"]),
                               float(m_shard["loss"]), rtol=2e-4)
    np.testing.assert_allclose(float(m_single["sparse_loss"]),
                               float(m_shard["sparse_loss"]), rtol=2e-4)
