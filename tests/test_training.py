"""Tests for losses, optimizer schedules, and the sharded train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.losses import epe_metrics, sequence_loss
from raft_tpu.models.raft import RAFT
from raft_tpu.optim import (cosine_warmup_restarts_schedule, make_schedule,
                            onecycle_schedule, step_schedule)
from raft_tpu.parallel import (create_train_state, make_mesh, make_eval_step,
                               make_train_step, shard_batch)


class TestSequenceLoss:
    def test_matches_manual_numpy(self, rng):
        n, B, H, W = 3, 2, 8, 10
        preds = rng.normal(size=(n, B, H, W, 2)).astype(np.float32)
        gt = rng.normal(size=(B, H, W, 2)).astype(np.float32)
        valid = (rng.uniform(size=(B, H, W)) > 0.3).astype(np.float32)
        gamma = 0.8

        loss, metrics = sequence_loss(jnp.asarray(preds), jnp.asarray(gt),
                                      jnp.asarray(valid), gamma=gamma,
                                      normalization="valid")

        # Manual formula for the opt-in density-independent variant:
        # weight gamma**(n-i-1), L1 over channels, valid-count-normalized.
        expect = 0.0
        for i in range(n):
            w = gamma ** (n - i - 1)
            l1 = np.abs(preds[i] - gt).mean(axis=-1)
            expect += w * (l1 * valid).sum() / valid.sum()
        np.testing.assert_allclose(float(loss), expect, rtol=1e-5)

    @pytest.mark.parametrize("valid_frac", [1.0, 0.2])
    def test_torch_reference_parity(self, rng, valid_frac):
        """Default normalization reproduces the reference torch loss
        (train.py:60-70) exactly, on a dense mask AND a KITTI-style
        sparse one (~20% valid) where the two normalizations differ by
        the valid fraction."""
        import torch

        n, B, H, W = 3, 2, 10, 12
        gamma = 0.8
        preds = rng.normal(size=(n, B, H, W, 2)).astype(np.float32)
        gt = (rng.normal(size=(B, H, W, 2)) * 5).astype(np.float32)
        valid = (rng.uniform(size=(B, H, W)) < valid_frac).astype(np.float32)
        # a few GT pixels beyond MAX_FLOW to exercise the magnitude gate
        gt[0, 0, 0] = 500.0

        # Reference semantics, written in torch NCHW layout as the fork
        # computes it: mask = (valid >= 0.5) & (|gt| < max_flow), then
        # per-iteration  gamma**(n-i-1) * (mask[:, None] * |pred-gt|).mean()
        t_gt = torch.from_numpy(gt).permute(0, 3, 1, 2)
        t_valid = torch.from_numpy(valid)
        mag = torch.sum(t_gt ** 2, dim=1).sqrt()
        t_mask = ((t_valid >= 0.5) & (mag < 400.0)).float()
        t_loss = torch.zeros(())
        for i in range(n):
            t_pred = torch.from_numpy(preds[i]).permute(0, 3, 1, 2)
            i_loss = (t_pred - t_gt).abs()
            t_loss = t_loss + gamma ** (n - i - 1) * (
                t_mask[:, None] * i_loss).mean()

        loss, _ = sequence_loss(jnp.asarray(preds), jnp.asarray(gt),
                                jnp.asarray(valid), gamma=gamma,
                                normalization="all")
        np.testing.assert_allclose(float(loss), float(t_loss), rtol=1e-5)

        # the variants agree on a fully-valid mask and differ by exactly
        # the valid fraction on a sparse one
        loss_v, _ = sequence_loss(jnp.asarray(preds), jnp.asarray(gt),
                                  jnp.asarray(valid), gamma=gamma,
                                  normalization="valid")
        frac = ((valid >= 0.5) & (np.sqrt((gt ** 2).sum(-1)) < 400.0))
        np.testing.assert_allclose(float(loss),
                                   float(loss_v) * frac.mean(), rtol=1e-5)

    def test_bad_normalization_rejected(self):
        with pytest.raises(ValueError, match="normalization"):
            sequence_loss(jnp.zeros((1, 1, 2, 2, 2)),
                          jnp.zeros((1, 2, 2, 2)), jnp.ones((1, 2, 2)),
                          normalization="pixels")

    def test_max_flow_exclusion(self, rng):
        preds = jnp.zeros((1, 1, 4, 4, 2))
        gt = jnp.full((1, 4, 4, 2), 500.0)        # all beyond MAX_FLOW
        valid = jnp.ones((1, 4, 4))
        loss, metrics = sequence_loss(preds, gt, valid)
        assert float(loss) == 0.0

    def test_uniform_weighting_at_gamma1(self, rng):
        preds = jnp.asarray(rng.normal(size=(2, 1, 4, 4, 2)),
                            dtype=jnp.float32)
        gt = jnp.zeros((1, 4, 4, 2))
        valid = jnp.ones((1, 4, 4))
        loss, _ = sequence_loss(preds, gt, valid, gamma=1.0)
        l0, _ = sequence_loss(preds[:1].repeat(2, 0), gt, valid, gamma=1.0)
        l1, _ = sequence_loss(preds[1:].repeat(2, 0), gt, valid, gamma=1.0)
        np.testing.assert_allclose(float(loss), (float(l0) + float(l1)) / 2,
                                   rtol=1e-6)

    def test_epe_metrics(self):
        pred = jnp.zeros((1, 2, 2, 2))
        gt = jnp.stack([jnp.full((1, 2, 2), 2.0),
                        jnp.zeros((1, 2, 2))], axis=-1)   # epe = 2 everywhere
        m = epe_metrics(pred, gt, jnp.ones((1, 2, 2)))
        assert abs(float(m["epe"]) - 2.0) < 1e-6
        assert float(m["1px"]) == 0.0
        assert float(m["3px"]) == 1.0


class TestSchedules:
    def test_onecycle_shape(self):
        s = onecycle_schedule(4e-4, 1000)
        assert float(s(0)) == pytest.approx(4e-4 / 25, rel=1e-4)
        assert float(s(50)) == pytest.approx(4e-4, rel=1e-4)  # peak at 5%
        assert float(s(999)) < 4e-4 / 25

    def test_step_schedule(self):
        s = step_schedule(2e-4, 1000)
        assert float(s(0)) == pytest.approx(2e-4, rel=1e-4)
        assert float(s(799)) == pytest.approx(2e-4, rel=1e-4)
        assert float(s(801)) == pytest.approx(1e-4, rel=1e-4)

    def test_cosine_warmup_restarts(self):
        # warmup 10, cycle 100, restart multiplies peak by gamma
        s = cosine_warmup_restarts_schedule(1e-3, 100, warmup_steps=10,
                                            gamma=0.5)
        assert float(s(10)) == pytest.approx(1e-3, rel=1e-3)
        assert float(s(99)) < 1e-4                        # end of cycle
        assert float(s(110)) == pytest.approx(5e-4, rel=1e-3)  # restart peak

    def test_cosine_cycle_mult(self):
        s = cosine_warmup_restarts_schedule(1e-3, 100, cycle_mult=2.0,
                                            warmup_steps=10)
        # second cycle spans [100, 300); its warmup peak is at 110
        assert float(s(110)) == pytest.approx(1e-3, rel=1e-3)
        assert float(s(250)) < 1e-3

    def test_make_schedule_dispatch(self):
        for name in ("onecycle", "step", "cosine_warmup"):
            s = make_schedule(TrainConfig(scheduler=name, num_steps=100))
            assert np.isfinite(float(s(10)))


def _tiny_batch(rng, B=2, H=64, W=64):
    return {
        "image1": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "image2": jnp.asarray(
            rng.uniform(0, 255, size=(B, H, W, 3)), jnp.float32),
        "flow": jnp.asarray(rng.normal(size=(B, H, W, 2)) * 2, jnp.float32),
        "valid": jnp.ones((B, H, W), jnp.float32),
    }


class TestTrainStep:
    @pytest.fixture(scope="class")
    def setup(self):
        tcfg = TrainConfig(batch_size=2, image_size=(64, 64), num_steps=50,
                           iters=2, lr=1e-4)
        model = RAFT(RAFTConfig(small=True, iters=2))
        state = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                   (64, 64))
        return tcfg, model, state

    def test_loss_decreases_on_overfit(self, setup, rng):
        tcfg, model, state = setup
        # donate=False: the class-scoped fixture state is reused by later
        # tests, so its buffers must survive this loop.
        step_fn = make_train_step(tcfg, donate=False)
        batch = _tiny_batch(rng)
        key = jax.random.PRNGKey(0)
        first = None
        for i in range(8):
            state, metrics = step_fn(state, batch, key)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first

    def test_metrics_finite_and_step_advances(self, setup, rng):
        tcfg, model, state = setup
        step_fn = make_train_step(tcfg, donate=False)
        state2, metrics = step_fn(state, _tiny_batch(rng),
                                  jax.random.PRNGKey(1))
        assert int(state2.step) == int(state.step) + 1
        for k, v in metrics.items():
            assert np.isfinite(float(v)), k

    def test_eval_step(self, setup):
        tcfg, model, state = setup
        eval_fn = make_eval_step(iters=2)
        i1 = jnp.zeros((1, 64, 64, 3))
        flow_low, flow_up = eval_fn(state, i1, i1)
        assert flow_low.shape == (1, 8, 8, 2)
        assert flow_up.shape == (1, 64, 64, 2)


class TestBatchNormFreeze:
    """The canonical large model's cnet uses batch norm
    (reference ``core/raft.py:58``); verify update vs freeze semantics
    (``train.py:414-415``)."""

    @pytest.fixture(scope="class")
    def setup(self):
        tcfg = TrainConfig(batch_size=1, image_size=(64, 64), num_steps=50,
                           iters=1, lr=1e-4)
        model = RAFT(RAFTConfig(iters=1))
        state = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                   (64, 64))
        assert jax.tree_util.tree_leaves(state.batch_stats)
        return tcfg, state

    def test_bn_stats_update_when_training(self, setup, rng):
        tcfg, state = setup
        step_fn = make_train_step(tcfg, donate=False)
        state2, _ = step_fn(state, _tiny_batch(rng, B=1),
                            jax.random.PRNGKey(1))
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()),
            state.batch_stats, state2.batch_stats)
        assert max(jax.tree_util.tree_leaves(diffs)) > 0

    def test_freeze_bn_keeps_stats(self, setup, rng):
        tcfg, state = setup
        step_fn = make_train_step(tcfg, freeze_bn=True, donate=False)
        state2, _ = step_fn(state, _tiny_batch(rng, B=1),
                            jax.random.PRNGKey(1))
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            state.batch_stats, state2.batch_stats)


class TestShardedTrainStep:
    def test_eight_device_mesh(self, rng):
        assert len(jax.devices()) == 8
        mesh = make_mesh()
        tcfg = TrainConfig(batch_size=8, image_size=(64, 64), num_steps=50,
                           iters=2)
        model = RAFT(RAFTConfig(small=True, iters=2))
        with mesh:
            state = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                       (64, 64), mesh=mesh)
            step_fn = make_train_step(tcfg, mesh=mesh)
            batch = shard_batch(_tiny_batch(rng, B=8), mesh)
            state, metrics = step_fn(state, batch, jax.random.PRNGKey(1))
        assert np.isfinite(float(metrics["loss"]))

    def test_sharded_matches_single_device(self, rng):
        """Data-parallel must be a layout choice, not a semantics choice."""
        tcfg = TrainConfig(batch_size=8, image_size=(64, 64), num_steps=50,
                           iters=2)
        model = RAFT(RAFTConfig(small=True, iters=2))
        batch = _tiny_batch(rng, B=8)
        key = jax.random.PRNGKey(1)

        state1 = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                    (64, 64))
        _, m_single = make_train_step(tcfg, donate=False)(state1, batch, key)

        mesh = make_mesh()
        with mesh:
            state2 = create_train_state(jax.random.PRNGKey(0), model, tcfg,
                                        (64, 64), mesh=mesh)
            _, m_shard = make_train_step(tcfg, mesh=mesh, donate=False)(
                state2, shard_batch(batch, mesh), key)
        np.testing.assert_allclose(float(m_single["loss"]),
                                   float(m_shard["loss"]), rtol=2e-4)


def test_resolve_train_corr_engine():
    """The training-path corr_impl='auto' resolution: on-demand on TPU
    when the crop fits the backward budget; explicit --alternate_corr
    wins; an explicit bf16 volume-storage request pins the materialized
    engine; off-TPU (this suite) auto keeps the volume."""
    from unittest import mock

    from raft_tpu.train import resolve_train_corr_engine

    # auto never picks the kernel off-TPU (backend pinned, not assumed
    # from the host this suite happens to run on)
    with mock.patch("jax.default_backend", return_value="cpu"):
        assert resolve_train_corr_engine(
            "raft", None, False, None, False, True, (368, 496)) is False
    # on TPU at the benchmarked chairs crop, auto picks the kernel —
    # including under spatial sharding since round 5 (shard_map
    # composition), gated on the feature rows dividing the spatial axis
    with mock.patch("jax.default_backend", return_value="tpu"):
        assert resolve_train_corr_engine(
            "raft", None, False, None, False, True, (368, 496)) is True
        # 368/8 = 46 feature rows: divisible by 2 → kernel composes
        assert resolve_train_corr_engine(
            "raft", None, False, None, False, True, (368, 496),
            spatial_shards=2) is True
        # 46 rows NOT divisible by 4 → shard_map can't split evenly,
        # materialized engine pins
        assert resolve_train_corr_engine(
            "raft", None, False, None, False, True, (368, 496),
            spatial_shards=4) is False
    # explicit force-on always wins
    assert resolve_train_corr_engine(
        "raft", "fixed", True, None, False, True, (368, 496)) is True
    # explicit bf16 storage pins the materialized engine
    assert resolve_train_corr_engine(
        "raft", "auto", False, "bfloat16", False, True,
        (368, 496)) is False
    # non-raft families resolve fixed
    assert resolve_train_corr_engine(
        "sparse", None, False, None, False, True, (352, 480)) is False
