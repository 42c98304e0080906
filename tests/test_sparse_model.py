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
    p = jax.jit(enc.init)(jax.random.PRNGKey(0), x)
    x1, x2 = jax.jit(enc.apply)(p, x)
    assert [f.shape for f in x1] == [
        (B, 16, 24, 48), (B, 8, 12, 64), (B, 4, 6, 96), (B, 2, 3, 128)]
    # the reference's X2[0]-quirk: level-0 of X2 is image1's features
    np.testing.assert_array_equal(np.asarray(x2[0]), np.asarray(x1[0]))

    dec = CNNDecoder(base_channel=32)
    variables = jax.jit(dec.init)(jax.random.PRNGKey(0), x)
    (y1, y2, u1), _ = jax.jit(lambda v, x: dec.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert u1.shape == (B, 16, 24, 48)   # stride 4, up_dim = 1.5c


# One sparse model, initialised once (under ``jit``: eagerly the family's
# init alone takes half a minute of this CPU), and its forward compiled
# once: the forward, gradient, test-mode and alternate-corr tests below
# all read them.

B, H, W = 1, 64, 96
CFG = OursConfig(base_channel=16, d_model=32, outer_iterations=2,
                 num_keypoints=16, n_heads=4, n_points=2, dropout=0.0)


@pytest.fixture(scope="module")
def images():
    rng = jax.random.PRNGKey(0)
    img1 = jax.random.uniform(rng, (B, H, W, 3)) * 255.0
    img2 = jnp.roll(img1, 2, axis=2)
    return img1, img2


@pytest.fixture(scope="module")
def sparse(images):
    """The model (``alternate_corr`` on, the default), its variables,
    and the outputs of its compiled forward on ``images``."""
    model = SparseRAFT(CFG)
    k = jax.random.PRNGKey(0)
    variables = jax.jit(lambda a, b: model.init(
        {"params": k, "dropout": k}, a, b))(*images)
    forward = jax.jit(lambda v, a, b: model.apply(v, a, b,
                                                  mutable=["batch_stats"]))
    (flows, sparse_out), _ = forward(variables, *images)
    return model, variables, flows, sparse_out


@pytest.fixture(scope="module")
def grads(sparse, images):
    """Gradients of the summed flow magnitudes in training mode, through
    the shared model's on-demand correlation."""
    model, variables, _, _ = sparse
    k = jax.random.PRNGKey(0)

    def loss(params):
        (flows, _), _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            *images, train=True, rngs={"dropout": k},
            mutable=["batch_stats"])
        return sum(jnp.abs(f).mean() for f in flows)

    return jax.tree.leaves(jax.jit(jax.grad(loss))(variables["params"]))


def test_sparse_raft_forward(sparse, images):
    model, variables, flows, sparse_out = sparse
    assert len(flows) == 2 and len(sparse_out) == 2
    assert flows[0].shape == (B, H, W, 2)
    src_points, key_flow, masks, scores = sparse_out[-1]
    assert src_points.shape == (B, 16, 2)
    assert key_flow.shape == (B, 16, 2)
    assert masks.shape == (B, 16, H // 4, W // 4)
    assert scores.shape == (B, 16)
    for f in flows:
        assert np.isfinite(np.asarray(f)).all()

    # jits cleanly (static shapes; unrolled outer iterations): the
    # compiled forward gives the eager one's flow
    (eager, _), _ = model.apply(variables, *images, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(flows[0]), np.asarray(eager[0]),
                               rtol=2e-4, atol=2e-4)


def test_sparse_raft_gradients_flow(grads):
    gnorm = sum(float(np.abs(np.asarray(g)).sum()) for g in grads)
    assert np.isfinite(gnorm) and gnorm > 0


def test_sparse_test_mode_drives_shared_eval_harness(sparse, images):
    """SparseRAFT must satisfy the (flow_low, flow_up) test_mode contract
    so FlowPredictor/validators drive both families."""
    from raft_tpu.evaluate import FlowPredictor

    model, variables, _, _ = sparse
    img = np.asarray(images[0][0])
    pred = FlowPredictor(model, variables, iters=2, batch_size=1)
    low, up = pred(img, img)
    assert up.shape == (H, W, 2) and low.shape == (H // 8, W // 8, 2)
    assert np.isfinite(up).all()

    # warm start is a canonical-RAFT capability; the sparse family refuses
    with pytest.raises(ValueError):
        model.apply(variables, *images,
                    flow_init=jnp.zeros((B, H // 8, W // 8, 2)))


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


def test_sparse_alternate_corr_matches_materialized(sparse, images,
                                                    grads):
    """cfg.alternate_corr recomputes the one-shot center-grid correlation
    windows on demand (deleting the all-pairs volume + avg-pool chain the
    round-4 profile measured at ~17% of the train step) — outputs must
    match the materialized path to float accumulation order, and
    gradients must flow (``grads``: through the on-demand model)."""
    import dataclasses

    _, variables, flows_o, _ = sparse
    materialized = SparseRAFT(dataclasses.replace(CFG, alternate_corr=False))
    (flows_d, _), _ = jax.jit(lambda v, a, b: materialized.apply(
        v, a, b, mutable=["batch_stats"]))(variables, *images)
    np.testing.assert_allclose(np.asarray(flows_o[-1]),
                               np.asarray(flows_d[-1]),
                               rtol=2e-4, atol=2e-4)

    assert any(float(np.abs(np.asarray(g)).max()) > 0 for g in grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


# ``OursConfig.encoder_iterations``: the ours_07 encoder stacks

class TestOurs07EncoderMode:
    def test_encoder_stacks_active(self, images):
        img1, img2 = images
        cfg = OursConfig(base_channel=16, d_model=32, outer_iterations=2,
                         num_keypoints=9, n_heads=4, dropout=0.0,
                         encoder_iterations=2)
        m = SparseRAFT(cfg)
        rng = jax.random.PRNGKey(3)
        v = jax.jit(lambda a, b: m.init({"params": rng, "dropout": rng},
                                        a, b))(img1, img2)
        names = set(v["params"].keys())
        assert {"encoder_0", "encoder_1", "context_encoder_0",
                "context_encoder_1", "encoder_pos_proj"} <= names
        fp, sp = jax.jit(m.apply)(v, img1, img2)
        assert len(fp) == 2 and fp[-1].shape == (B, H, W, 2)
        assert bool(jnp.isfinite(fp[-1]).all())

    def test_default_has_no_encoder_params(self, sparse):
        _, variables, _, _ = sparse          # encoder_iterations 0
        assert not any(n.startswith("encoder_")
                       for n in variables["params"].keys())
