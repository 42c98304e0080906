"""Tests for the auxiliary component families: DETR backbone, relative
attention, Hungarian matcher, feature extraction, flow segmentation
(reference core/backbone.py, core/relative.py, core/utils/matcher.py,
core/utils/feature_extraction.py, core/utils/flow_segmentor.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.utils.misc import (NestedTensor, accuracy, downsample_mask,
                                 get_total_grad_norm,
                                 nested_tensor_from_images)


def test_nested_tensor_padding_and_mask():
    imgs = [np.ones((4, 6, 3), np.float32), np.ones((3, 5, 3), np.float32)]
    nt = nested_tensor_from_images(imgs)
    assert nt.tensors.shape == (2, 4, 6, 3)
    assert not bool(nt.mask[0].any())           # first image fills fully
    assert bool(nt.mask[1, 3, :].all())         # padded row flagged
    assert bool(nt.mask[1, :, 5].all())         # padded col flagged
    small = downsample_mask(nt.mask, 2, 3)
    assert small.shape == (2, 2, 3) and small.dtype == jnp.bool_


def test_backbone_pyramid_shapes(rng):
    from raft_tpu.models.backbone import Backbone

    bb = Backbone()
    nt = NestedTensor(
        jnp.asarray(rng.standard_normal((1, 64, 96, 3)), jnp.float32),
        jnp.zeros((1, 64, 96), bool))
    vs = bb.init(jax.random.PRNGKey(0), nt)
    outs = bb.apply(vs, nt)
    assert [o.tensors.shape for o in outs] == [
        (1, 8, 12, 512), (1, 4, 6, 1024), (1, 2, 3, 2048)]
    assert [o.mask.shape for o in outs] == [
        (1, 8, 12), (1, 4, 6), (1, 2, 3)]
    assert bb.strides == [8, 16, 32]
    assert bb.num_channels == [512, 1024, 2048]


def test_frozen_batchnorm_cuts_gradients(rng):
    from raft_tpu.models.backbone import FrozenBatchNorm

    fbn = FrozenBatchNorm(4)
    x = jnp.asarray(rng.standard_normal((1, 3, 3, 4)), jnp.float32)
    vs = fbn.init(jax.random.PRNGKey(0), x)
    g = jax.grad(lambda p: fbn.apply({"params": p}, x).sum())(vs["params"])
    assert all(float(jnp.abs(v).max()) == 0.0
               for v in jax.tree_util.tree_leaves(g))


def test_joiner_positions(rng):
    from raft_tpu.models.backbone import build_backbone

    joiner = build_backbone(num_feature_levels=3, hidden_dim=64)
    nt = NestedTensor(
        jnp.asarray(rng.standard_normal((1, 32, 32, 3)), jnp.float32), None)
    vs = joiner.init(jax.random.PRNGKey(0), nt)
    feats, pos = joiner.apply(vs, nt)
    assert len(feats) == len(pos) == 3
    for f, p in zip(feats, pos):
        assert p.shape == f.tensors.shape[:3] + (64,)


def test_relative_decoder_layer(rng):
    from raft_tpu.models.relative import (MultiHeadAttentionLayer,
                                          RelativeTransformerDecoderLayer)

    B, H, W, C = 2, 4, 5, 32
    src = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    tgt = jnp.asarray(rng.standard_normal((B, H * W, C)), jnp.float32)
    layer = RelativeTransformerDecoderLayer(d_model=C, dim_feedforward=64,
                                            nhead=4,
                                            max_relative_position=3)
    vs = layer.init(jax.random.PRNGKey(0), tgt, src)
    out = layer.apply(vs, tgt, src)
    assert out.shape == (B, H * W, C)
    assert bool(jnp.isfinite(out).all())

    # relative bias must actually change attention: compare vs zeroed tables
    mha = MultiHeadAttentionLayer(C, 4, max_relative_position=3)
    mvs = mha.init(jax.random.PRNGKey(1), src, src, src)
    out1, _ = mha.apply(mvs, src, src, src)
    zeroed = jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x) if x.ndim == 2 and x.shape[0] == 7
        else x, mvs)
    out2, _ = mha.apply(zeroed, src, src, src)
    assert float(jnp.abs(out1 - out2).max()) > 1e-5


def test_hungarian_matcher_prefers_matching_masks():
    from raft_tpu.utils.matcher import HungarianMatcher

    Q, K, H, W = 3, 2, 4, 4
    masks = np.zeros((1, Q, H, W), np.float32)
    masks[0, 0, :2] = 8.0       # query 0 → top half
    masks[0, 1, 2:] = 8.0       # query 1 → bottom half
    masks[0, 2] = -8.0          # query 2 → nothing
    logits = np.zeros((1, Q, K), np.float32)

    t0 = np.zeros((2, H, W), np.float32)
    t0[0, 2:] = 1.0             # target 0 = bottom half → query 1
    t0[1, :2] = 1.0             # target 1 = top half → query 0
    targets = [{"labels": np.asarray([0, 1]), "masks": t0}]

    matcher = HungarianMatcher()
    (pred_idx, tgt_idx), = matcher(
        {"pred_logits": jnp.asarray(logits),
         "pred_masks": jnp.asarray(masks)}, targets)
    pairing = dict(zip(tgt_idx.tolist(), pred_idx.tolist()))
    assert pairing == {0: 1, 1: 0}


def test_feature_extractor_taps(rng):
    from raft_tpu.models.update import FlowHead
    from raft_tpu.utils.feature_extraction import (create_feature_extractor,
                                                   get_graph_node_names)

    fh = FlowHead(hidden_dim=8)
    x = jnp.asarray(rng.standard_normal((1, 4, 4, 8)), jnp.float32)
    vs = fh.init(jax.random.PRNGKey(0), x)
    names = get_graph_node_names(fh, vs, x)
    assert "conv1" in names and "conv2" in names

    extractor = create_feature_extractor(fh, ["conv1"])
    feats = extractor(vs, x)
    assert feats["conv1"].shape == (1, 4, 4, 8)

    with pytest.raises(KeyError):
        create_feature_extractor(fh, ["does_not_exist"])(vs, x)


def test_misc_accuracy_and_grad_norm():
    logits = jnp.asarray([[0.1, 0.9], [0.8, 0.2]])
    target = jnp.asarray([1, 0])
    (top1,) = accuracy(logits, target, (1,))
    assert float(top1) == 100.0
    norm = get_total_grad_norm({"a": jnp.asarray([3.0]),
                                "b": jnp.asarray([4.0])})
    assert abs(float(norm) - 5.0) < 1e-6


def test_flow_segmentor_masks():
    from raft_tpu.data.flow_segmentor import segment

    img = np.zeros((12, 12, 3), np.uint8)
    img[:, 6:] = 200            # two color regions
    masks = segment(img, min_size=4)
    assert masks.ndim == 3 and masks.shape[1:] == (12, 12)
    assert len(masks) == 2
    # masks partition the image
    assert bool((masks.sum(0) == 1).all())


def test_weight_decay_masks_frozen_batchnorm():
    """AdamW decay must not touch FrozenBatchNorm statistics (torch keeps
    them as buffers; here the optimizer masks them)."""
    from raft_tpu.optim import _decay_mask

    params = {
        "body": {"bn1": {"weight": np.ones(2), "bias": np.zeros(2),
                         "running_mean": np.zeros(2),
                         "running_var": np.ones(2)},
                 "conv1": {"kernel": np.ones((1, 1, 2, 2))}},
    }
    mask = _decay_mask(params)
    assert mask["body"]["conv1"]["kernel"] is True
    assert all(v is False for v in mask["body"]["bn1"].values())


def test_learned_position_embedding_exceeds_table_size(rng):
    """Levels wider than the 50-entry DETR table interpolate instead of
    crashing (stride-8 Sintel features are 128 wide)."""
    from raft_tpu.models.backbone import PositionEmbeddingLearned
    from raft_tpu.utils.misc import NestedTensor

    pe = PositionEmbeddingLearned(num_pos_feats=8)
    nt = NestedTensor(
        jnp.asarray(rng.standard_normal((1, 4, 128, 16)), jnp.float32),
        None)
    vs = pe.init(jax.random.PRNGKey(0), nt)
    pos = pe.apply(vs, nt)
    assert pos.shape == (1, 4, 128, 16)
    assert bool(jnp.isfinite(pos).all())


def test_kernel_census_counts_named_mosaic_kernels():
    from raft_tpu.ops.layout import kernel_census
    def call(op_name):
        return ('custom-call(%x), custom_call_target="tpu_custom_call", '
                'metadata={op_name="' + op_name + '"}')

    text = "\n".join([
        call("jit(run)/RAFT/while/body/update/raft_corr_fwd/pallas_call"),
        call("jit(run)/RAFT/while/body/update_block/raft_step/pallas_call"),
        call("jit(step)/transpose(jvp(raft_corr_bwd))/pallas_call"),
        call("jit(f)/pallas_call"),
        'fusion(%y), kind=kLoop, metadata={op_name="raft_gru"}'])
    assert kernel_census(text) == {"corr_fwd": 1, "step": 1, "corr_bwd": 1,
                                   "unnamed": 1}
    assert kernel_census("HloModule cpu_only") == {}
