"""Training losses and in-loss metrics.

``sequence_loss`` follows reference ``train.py:51-100``: an L1 loss over
every refinement iteration's upsampled flow, optionally exponentially
weighted by ``gamma**(n_predictions - i - 1)`` (original RAFT; the fork's
active trainer weighted iterations uniformly — both supported via
``gamma=1.0``), masked by validity (``valid & |flow| < max_flow``), plus an
optional auxiliary sparse-keypoint loss for the "ours" family
(reference ``train.py:71-83``).

All reductions are pure jnp so the loss jits into the train step; metric
aggregation across data-parallel replicas happens in the caller via
``jax.lax.pmean`` / sharded-sum (see ``raft_tpu.parallel``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MAX_FLOW = 400.0  # reference train.py:48


def epe_metrics(flow_pred: jnp.ndarray, flow_gt: jnp.ndarray,
                valid: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """End-point-error metrics of the final prediction
    (reference ``train.py:87-98``): mean EPE and 1/3/5-px accuracies over
    valid pixels.

    Args:
      flow_pred: ``(B, H, W, 2)``.
      flow_gt: ``(B, H, W, 2)``.
      valid: ``(B, H, W)`` boolean/0-1 mask.
    """
    epe = jnp.sqrt(jnp.sum((flow_pred - flow_gt) ** 2, axis=-1))
    v = valid.astype(jnp.float32)
    denom = jnp.maximum(v.sum(), 1.0)

    def masked_mean(x):
        return (x * v).sum() / denom

    return {
        "epe": masked_mean(epe),
        "1px": masked_mean((epe < 1.0).astype(jnp.float32)),
        "3px": masked_mean((epe < 3.0).astype(jnp.float32)),
        "5px": masked_mean((epe < 5.0).astype(jnp.float32)),
    }


@jax.named_scope("sequence_loss")
def sequence_loss(flow_preds: jnp.ndarray, flow_gt: jnp.ndarray,
                  valid: jnp.ndarray, gamma: float = 0.8,
                  max_flow: float = MAX_FLOW,
                  normalization: str = "all",
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Weighted multi-iteration L1 flow loss.

    Args:
      flow_preds: ``(iters, B, H, W, 2)`` stacked per-iteration predictions
        (the ``lax.scan`` output of :class:`raft_tpu.models.raft.RAFT`).
      flow_gt: ``(B, H, W, 2)`` ground truth.
      valid: ``(B, H, W)`` validity mask.
      gamma: per-iteration decay; ``gamma**(n-i-1)`` weighting as in original
        RAFT (``gamma=1`` reproduces the fork's uniform weighting,
        reference ``train.py:65-66``).
      max_flow: exclude pixels with GT magnitude above this
        (reference ``train.py:60-62``).
      normalization: ``"all"`` (default) reproduces the reference exactly —
        ``(valid * |pred - gt|).mean()`` over ALL pixels with invalid ones
        zeroed (reference ``train.py:70``), so on sparse datasets
        (KITTI/HD1K) the effective loss scales with the valid fraction.
        ``"valid"`` divides by the valid-pixel count instead — a
        density-independent variant (larger gradients on sparse stages;
        changes training dynamics vs the reference, opt in deliberately).

    Returns:
      scalar loss, metrics dict (computed on the final iteration).
    """
    if normalization not in ("all", "valid"):
        raise ValueError(f"normalization must be 'all' or 'valid', "
                         f"got {normalization!r}")
    n = flow_preds.shape[0]
    mag = jnp.sqrt(jnp.sum(flow_gt ** 2, axis=-1))
    v = (valid.astype(jnp.float32)
         * (mag < max_flow).astype(jnp.float32))          # (B,H,W)

    weights = gamma ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)
    l1 = jnp.abs(flow_preds - flow_gt[None])              # (n,B,H,W,2)
    masked = l1.mean(axis=-1) * v[None]                   # (n,B,H,W)
    if normalization == "all":
        # (valid[:, None] * i_loss).mean(): channel mean folded into
        # l1.mean(-1) above, remaining denominator is B*H*W.
        per_iter = masked.mean(axis=(1, 2, 3))
    else:
        per_iter = masked.sum(axis=(1, 2, 3)) / jnp.maximum(v.sum(), 1.0)
    loss = jnp.sum(weights * per_iter)

    metrics = epe_metrics(flow_preds[-1], flow_gt, v)
    metrics["loss"] = loss
    return loss, metrics


def sparse_keypoint_loss(sparse_preds, flow_gt: jnp.ndarray,
                         valid: jnp.ndarray,
                         max_flow: float = MAX_FLOW) -> jnp.ndarray:
    """Auxiliary keypoint-flow loss for the "ours" family
    (reference ``train.py:71-83``).

    Each outer iteration predicts reference points (normalized src coords)
    and per-keypoint flows; the loss is an L1 between each keypoint's flow
    and the ground-truth flow bilinearly read at its reference point.

    DELIBERATE DEVIATION from the reference: the fork reads GT at rounded
    keypoint coordinates through a flat gather whose index is computed as
    ``y * x`` instead of ``y * W + x`` (reference ``train.py:75-77``) — a
    real indexing bug that pairs keypoints with unrelated GT pixels.  No
    fork weights are published, so bit-parity with the bug is moot; this
    implementation samples the GT bilinearly at the exact (fractional)
    reference point, which is what the rounded-gather was evidently
    meant to do.

    Args:
      sparse_preds: sequence of ``(ref_points, key_flows)`` per iteration —
        ``ref_points``: ``(B, K, 2)`` in [0, 1] (x, y);
        ``key_flows``: ``(B, K, 2)`` pixel flow.
      flow_gt: ``(B, H, W, 2)``; valid: ``(B, H, W)``.
    """
    from raft_tpu.ops.sampling import bilinear_sampler

    B, H, W, _ = flow_gt.shape
    mag = jnp.sqrt(jnp.sum(flow_gt ** 2, axis=-1))
    vmask = (valid.astype(jnp.float32)
             * (mag < max_flow).astype(jnp.float32))[..., None]

    total = 0.0
    for ref_points, key_flows in sparse_preds:
        pix = jnp.stack([ref_points[..., 0] * (W - 1),
                         ref_points[..., 1] * (H - 1)], axis=-1)
        gt_at_kp = bilinear_sampler(flow_gt * vmask, pix)     # (B,K,2)
        v_at_kp = bilinear_sampler(vmask, pix)                # (B,K,1)
        l1 = jnp.abs(key_flows - gt_at_kp) * v_at_kp
        total = total + l1.sum() / jnp.maximum(v_at_kp.sum() * 2.0, 1.0)
    return total / max(len(sparse_preds), 1)


def token_cross_entropy(logits, tokens, segment_ids):
    """Mean next-token cross-entropy over the positions whose target
    lies in the same document.

    ``logits`` (B, S, V) float32 over the vocabulary held, ``tokens``
    and ``segment_ids`` (B, S): position ``t`` predicts ``tokens[t+1]``
    where ``segment_ids[t+1] == segment_ids[t]``; the last position of
    a sequence and the last of each document predict nothing. Returns
    ``(loss, metrics)``; ``metrics["tokens"]`` counts the positions
    that entered the mean.
    """
    with jax.named_scope("token_loss"):
        # rolled, not sliced: a (B, S-1, V) copy of the logits is 1 GB
        targets = jnp.roll(tokens, -1, axis=1)
        counted = (jnp.roll(segment_ids, -1, axis=1) == segment_ids) \
            & (jnp.arange(tokens.shape[1]) < tokens.shape[1] - 1)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]
        n = counted.sum()
        loss = jnp.where(counted, logz - picked, 0.0).sum() \
            / jnp.maximum(n, 1)
    return loss, {"loss": loss, "tokens": n.astype(jnp.int32)}


#: positions whose logits exist at once in the blocked loss (float32
#: logits of 16384 positions over 25024 rows are 1.6 GB; of 2048, 0.2)
LOSS_BLOCK = 2048


def blocked_token_cross_entropy(hidden, head, tokens, segment_ids, *,
                                dtype, block: int = LOSS_BLOCK):
    """:func:`token_cross_entropy` of ``hidden @ head`` without the
    logits of the whole step ever existing at once.

    ``hidden`` (B, S, d) float32 after the last norm, ``head`` (d, V)
    over the vocabulary held; the positions go through in blocks of
    ``block`` (the whole where it does not divide them): a block's
    logits (operands in ``dtype``, float32 accumulation) are made, used
    and, in the backward pass, made again, so neither pass holds more
    than one block of them. Same value and gradient as the whole loss,
    to the order of float32 sums.
    """
    b, s, d = hidden.shape
    rows = b * s
    if rows % block:
        block = rows
    targets = jnp.roll(tokens, -1, axis=1)
    counted = (jnp.roll(segment_ids, -1, axis=1) == segment_ids) \
        & (jnp.arange(s) < s - 1)
    n = counted.sum()

    @jax.checkpoint
    def nll_of(total, rows_of):
        hidden_b, target_b, counted_b = rows_of
        with jax.named_scope("lm_head"):
            logits = jnp.dot(hidden_b.astype(dtype), head.astype(dtype),
                             preferred_element_type=jnp.float32)
        with jax.named_scope("token_loss"):
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, target_b[:, None],
                                         axis=-1)[:, 0]
            return total + jnp.where(counted_b, logz - picked,
                                     0.0).sum(), None

    blocked = jax.tree.map(
        lambda a: a.reshape(rows // block, block, *a.shape[2:]),
        (hidden, targets, counted))
    total, _ = jax.lax.scan(nll_of, jnp.zeros((), jnp.float32), blocked)
    with jax.named_scope("token_loss"):
        loss = total / jnp.maximum(n, 1)
    return loss, {"loss": loss, "tokens": n.astype(jnp.int32)}
