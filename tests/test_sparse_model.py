"""Tests for the sparse-keypoint ("ours") model family.

The MSDA sampling core is checked against a torch ``grid_sample`` reference
implementation — the reference repo's own kernel-testing pattern
(``core/ops/test.py`` vs ``ms_deform_attn_core_pytorch``,
``core/ops/functions/ms_deform_attn_func.py:41-61``); torch-cpu is a
host-side test dependency only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import OursConfig
from raft_tpu.models.ours import SparseRAFT
from raft_tpu.ops.msda import ms_deform_attn


def _torch_msda_reference(value, spatial_shapes, locations, weights):
    """Port of reference ``ms_deform_attn_core_pytorch`` (grid_sample)."""
    import torch
    import torch.nn.functional as F

    value = torch.from_numpy(value)
    locations = torch.from_numpy(locations)
    weights = torch.from_numpy(weights)
    N, S, M, D = value.shape
    _, Lq, _, L, P, _ = locations.shape
    value_list = value.split([h * w for h, w in spatial_shapes], dim=1)
    grids = 2 * locations - 1
    sampled = []
    for lid, (h, w) in enumerate(spatial_shapes):
        v = value_list[lid].flatten(2).transpose(1, 2).reshape(
            N * M, D, h, w)
        g = grids[:, :, :, lid].transpose(1, 2).flatten(0, 1)
        sampled.append(F.grid_sample(v, g, mode="bilinear",
                                     padding_mode="zeros",
                                     align_corners=False))
    weights = weights.transpose(1, 2).reshape(N * M, 1, Lq, L * P)
    out = (torch.stack(sampled, dim=-2).flatten(-2)
           * weights).sum(-1).view(N, M * D, Lq)
    return out.transpose(1, 2).contiguous().numpy()


@pytest.mark.parametrize("shapes", [[(6, 8), (3, 4)], [(5, 7)]])
def test_msda_matches_torch_reference(rng, shapes):
    N, M, D, Lq, P = 2, 4, 8, 9, 3
    L = len(shapes)
    S = sum(h * w for h, w in shapes)
    value = rng.standard_normal((N, S, M, D)).astype(np.float32)
    # locations straddle borders to exercise zero padding
    locations = rng.uniform(-0.2, 1.2,
                            (N, Lq, M, L, P, 2)).astype(np.float32)
    weights = rng.random((N, Lq, M, L, P)).astype(np.float32)
    weights /= weights.sum(axis=(-2, -1), keepdims=True)

    ref = _torch_msda_reference(value, shapes, locations, weights)
    got = ms_deform_attn(jnp.asarray(value), shapes,
                         jnp.asarray(locations), jnp.asarray(weights))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)


def test_msdeform_attn_module(rng):
    from raft_tpu.models.deformable import MSDeformAttn

    shapes = [(4, 6), (2, 3)]
    S = sum(h * w for h, w in shapes)
    B, Lq, Dm = 2, 5, 32
    attn = MSDeformAttn(d_model=Dm, n_levels=2, n_heads=4, n_points=2)
    q = jnp.asarray(rng.standard_normal((B, Lq, Dm)), jnp.float32)
    refp = jnp.asarray(rng.uniform(0, 1, (B, Lq, 2, 2)), jnp.float32)
    src = jnp.asarray(rng.standard_normal((B, S, Dm)), jnp.float32)
    params = attn.init(jax.random.PRNGKey(0), q, refp, src, shapes)
    out, w = attn.apply(params, q, refp, src, shapes)
    assert out.shape == (B, Lq, Dm)
    assert w.shape == (B, Lq, 4, 2, 2)
    # weights softmaxed over levels*points
    np.testing.assert_allclose(np.asarray(w.sum(axis=(-2, -1))), 1.0,
                               rtol=1e-5)
    # offset bias init is the directional ring, not zeros
    bias = params["params"]["sampling_offsets"]["bias"]
    assert float(jnp.abs(bias).max()) > 0.5


def test_decoder_layer_shapes(rng):
    from raft_tpu.models.deformable import DeformableTransformerDecoderLayer

    shapes = [(4, 4), (2, 2)]
    S = sum(h * w for h, w in shapes)
    B, N, Dm = 1, 7, 32
    layer = DeformableTransformerDecoderLayer(
        d_model=Dm, d_ffn=64, n_levels=2, n_heads=4, n_points=2,
        activation="gelu")
    tgt = jnp.asarray(rng.standard_normal((B, N, Dm)), jnp.float32)
    qp = jnp.asarray(rng.standard_normal((B, N, Dm)), jnp.float32)
    refp = jnp.asarray(rng.uniform(0, 1, (B, N, 2, 2)), jnp.float32)
    src = jnp.asarray(rng.standard_normal((B, S, Dm)), jnp.float32)
    sp = jnp.asarray(rng.standard_normal((1, S, Dm)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), tgt, qp, refp, src, sp,
                        shapes)
    out = layer.apply(params, tgt, qp, refp, src, sp, shapes)
    assert out.shape == (B, N, Dm)
    assert np.isfinite(np.asarray(out)).all()


def test_cnn_encoders(rng):
    from raft_tpu.models.sparse_extractor import CNNDecoder, CNNEncoder

    B, H, W = 1, 64, 96
    x = jnp.asarray(rng.uniform(-1, 1, (2 * B, H, W, 3)), jnp.float32)
    enc = CNNEncoder(base_channel=32)
    p = enc.init(jax.random.PRNGKey(0), x)
    x1, x2 = enc.apply(p, x)
    assert [f.shape for f in x1] == [
        (B, 16, 24, 48), (B, 8, 12, 64), (B, 4, 6, 96), (B, 2, 3, 128)]
    # the reference's X2[0]-quirk: level-0 of X2 is image1's features
    np.testing.assert_array_equal(np.asarray(x2[0]), np.asarray(x1[0]))

    dec = CNNDecoder(base_channel=32)
    variables = dec.init(jax.random.PRNGKey(0), x)
    (y1, y2, u1), _ = dec.apply(variables, x, train=True,
                                mutable=["batch_stats"])
    assert u1.shape == (B, 16, 24, 48)   # stride 4, up_dim = 1.5c


def test_sparse_raft_forward(rng):
    from raft_tpu.models.ours import SparseRAFT

    cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=2,
                     num_keypoints=16, n_heads=4, n_points=2)
    model = SparseRAFT(cfg)
    B, H, W = 1, 64, 96
    img = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    k = jax.random.PRNGKey(0)
    variables = model.init({"params": k, "dropout": k}, img, img)
    (flows, sparse), _ = model.apply(variables, img, img,
                                     mutable=["batch_stats"])
    assert len(flows) == 2 and len(sparse) == 2
    assert flows[0].shape == (B, H, W, 2)
    src_points, key_flow, masks, scores = sparse[-1]
    assert src_points.shape == (B, 16, 2)
    assert key_flow.shape == (B, 16, 2)
    assert masks.shape == (B, 16, H // 4, W // 4)
    assert scores.shape == (B, 16)
    for f in flows:
        assert np.isfinite(np.asarray(f)).all()

    # jits cleanly (static shapes; unrolled outer iterations)
    fn = jax.jit(lambda v, a, b: model.apply(v, a, b,
                                             mutable=["batch_stats"]))
    (flows2, _), _ = fn(variables, img, img)
    np.testing.assert_allclose(np.asarray(flows2[0]), np.asarray(flows[0]),
                               rtol=2e-4, atol=2e-4)


def test_sparse_raft_gradients_flow(rng):
    from raft_tpu.models.ours import SparseRAFT

    cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=1,
                     num_keypoints=9, n_heads=4, n_points=2, dropout=0.0)
    model = SparseRAFT(cfg)
    B, H, W = 1, 64, 64
    img = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    k = jax.random.PRNGKey(0)
    variables = model.init({"params": k, "dropout": k}, img, img)

    def loss(params):
        (flows, _), _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            img, img, train=True, rngs={"dropout": k},
            mutable=["batch_stats"])
        return sum(jnp.abs(f).mean() for f in flows)

    grads = jax.grad(loss)(variables["params"])
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


def test_sparse_test_mode_drives_shared_eval_harness(rng):
    """SparseRAFT must satisfy the (flow_low, flow_up) test_mode contract
    so FlowPredictor/validators drive both families."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import OursConfig
    from raft_tpu.evaluate import FlowPredictor
    from raft_tpu.models import SparseRAFT

    cfg = OursConfig(base_channel=16, d_model=32, num_feature_levels=2,
                     outer_iterations=2, num_keypoints=4, n_heads=4,
                     n_points=2, dropout=0.0)
    model = SparseRAFT(cfg)
    img = jnp.asarray(rng.uniform(0, 255, (1, 32, 48, 3)), jnp.float32)
    vs = model.init({"params": jax.random.PRNGKey(0),
                     "dropout": jax.random.PRNGKey(0)}, img, img, iters=1)
    pred = FlowPredictor(model, vs, iters=2, batch_size=1)
    low, up = pred(np.asarray(img[0]), np.asarray(img[0]))
    assert up.shape == (32, 48, 2) and low.shape == (4, 6, 2)
    assert np.isfinite(up).all()

    # warm start is a canonical-RAFT capability; the sparse family refuses
    with pytest.raises(ValueError):
        model.apply(vs, img, img, flow_init=jnp.zeros((1, 4, 6, 2)))


@pytest.mark.parametrize("channels", [5, 16])
def test_msda_gradcheck_channels(rng, channels):
    """Numerical gradient check across odd/even channel counts — the
    reference exercises its CUDA kernel the same way
    (``core/ops/test.py:63-78``, channels {30, 32, 71, ...})."""
    from jax.test_util import check_grads

    shapes = [(4, 5), (2, 3)]
    N, M, Lq, P = 1, 2, 3, 2
    L = len(shapes)
    S = sum(h * w for h, w in shapes)
    value = jnp.asarray(rng.standard_normal((N, S, M, channels)),
                        jnp.float32)
    locations = jnp.asarray(
        rng.uniform(0.1, 0.9, (N, Lq, M, L, P, 2)), jnp.float32)
    weights = jnp.asarray(rng.random((N, Lq, M, L, P)), jnp.float32)
    weights = weights / weights.sum(axis=(-2, -1), keepdims=True)

    check_grads(lambda v, w: ms_deform_attn(v, shapes, locations, w),
                (value, weights), order=1, modes=["rev"],
                atol=1e-2, rtol=1e-2)


def test_sparse_alternate_corr_matches_materialized(rng):
    """cfg.alternate_corr recomputes the one-shot center-grid correlation
    windows on demand (deleting the all-pairs volume + avg-pool chain the
    round-4 profile measured at ~17% of the train step) — outputs must
    match the materialized default to float accumulation order, and
    gradients must flow."""
    import dataclasses

    from raft_tpu.models.ours import SparseRAFT

    cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=1,
                     num_keypoints=16, n_heads=4, n_points=2)
    B, H, W = 1, 64, 96
    img1 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)), jnp.float32)
    k = jax.random.PRNGKey(0)
    dense = SparseRAFT(cfg)
    variables = dense.init({"params": k, "dropout": k}, img1, img2)
    ondemand = SparseRAFT(dataclasses.replace(cfg, alternate_corr=True))

    (flows_d, _), _ = dense.apply(variables, img1, img2,
                                  mutable=["batch_stats"])
    (flows_o, _), _ = ondemand.apply(variables, img1, img2,
                                     mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(flows_o[-1]),
                               np.asarray(flows_d[-1]),
                               rtol=2e-4, atol=2e-4)

    def loss(params):
        (flows, _), _ = ondemand.apply(
            {"params": params, **{k_: v for k_, v in variables.items()
                                  if k_ != "params"}},
            img1, img2, mutable=["batch_stats"])
        return jnp.mean(jnp.abs(flows[-1]))

    g = jax.grad(loss)(variables["params"])
    leaves = jax.tree_util.tree_leaves(g)
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)


# ``OursConfig.encoder_iterations``: the ours_07 encoder stacks

B, H, W = 1, 64, 96


@pytest.fixture(scope="module")
def images():
    rng = jax.random.PRNGKey(0)
    img1 = jax.random.uniform(rng, (B, H, W, 3)) * 255.0
    img2 = jnp.roll(img1, 2, axis=2)
    return img1, img2


class TestOurs07EncoderMode:
    def test_encoder_stacks_active(self, images):
        img1, img2 = images
        cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=2,
                         num_keypoints=9, n_heads=4, dropout=0.0,
                         encoder_iterations=2)
        m = SparseRAFT(cfg)
        rng = jax.random.PRNGKey(3)
        v = m.init({"params": rng, "dropout": rng}, img1, img2)
        names = set(v["params"].keys())
        assert {"encoder_0", "encoder_1", "context_encoder_0",
                "context_encoder_1", "encoder_pos_proj"} <= names
        fp, sp = m.apply(v, img1, img2)
        assert len(fp) == 2 and fp[-1].shape == (B, H, W, 2)
        assert bool(jnp.isfinite(fp[-1]).all())

    def test_default_has_no_encoder_params(self, images):
        img1, img2 = images
        cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=1,
                         num_keypoints=9, n_heads=4, dropout=0.0)
        m = SparseRAFT(cfg)
        rng = jax.random.PRNGKey(3)
        v = m.init({"params": rng, "dropout": rng}, img1, img2)
        assert not any(n.startswith("encoder_")
                       for n in v["params"].keys())
