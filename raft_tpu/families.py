"""The model families: one row a ``model_family``, and the one place a
family is known.

A row says how to build the family's model from its config, what its
train step feeds it and how the step turns its outputs into a loss, and
which of the entry points' selections its model honours. ``train.py``,
``evaluate.py``, ``demo.py``, the step (``parallel/train_step.py``) and
the mesh's validation ask the row (:func:`family_of`); none of them
compares a family's name. Model modules are imported when a row
builds, not when this module is.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.config import AfmoeConfig, GraniteHybridConfig, LMConfig
from raft_tpu.losses import sequence_loss


class Family(NamedTuple):
    #: ``mcfg -> flax module``; ``mcfg`` is a ``RAFTConfig`` for a row of
    #: image pairs, the row's ``config_cls`` for a row of tokens
    build: Callable
    #: ``(tcfg, image_shape) -> (args, kwargs)`` of ``model.init``
    init_inputs: Callable
    #: ``(tcfg, freeze_bn) -> loss_fn(apply_fn, variables, batch, rngs,
    #: step) -> (loss, metrics, mutated)``; ``rngs`` holds ``noise`` and
    #: ``dropout`` keys already folded with the step
    make_loss: Callable
    #: batches are packed token sequences, not image pairs: no panels,
    #: validation sets, BatchNorm to freeze or ``image_size``; the
    #: loader yields ``tokens`` / ``segment_ids`` / ``positions``, and
    #: ``evaluate.py`` and ``demo.py`` do not offer the row
    tokens: bool = False
    #: the model returns ``(flow_preds, sparse_preds)``, the second the
    #: keypoint predictions ``--sparse_lambda`` weighs and the panels
    #: draw; otherwise its output is the flow predictions alone
    sparse_preds: bool = False
    #: ``small``, ``alternate_corr``, ``corr_dtype``, ``iters`` and
    #: ``corr_impl`` auto select something in the model; a row without
    #: this builds from a config of its own and the entry points refuse
    #: those selections instead of dropping them
    raft_options: bool = False
    #: published torch weights exist and ``utils/torch_convert.py``
    #: converts them (``.pth`` / ``.pt`` / torch-keyed ``.npz``)
    torch_weights: bool = False
    #: the model takes ``flow_init`` (``--warm_start``)
    flow_init: bool = False
    #: image rows may be split over the mesh's ``spatial`` axis
    spatial_shards: bool = False
    #: a token row's config dataclass (``--lm_config`` fills it from a
    #: JSON file's ``model`` object)
    config_cls: Optional[type] = None
    #: the integer counters the row's step reports beside its loss;
    #: ``train()`` puts them on the step's span and into the scalar
    #: stream
    step_counters: Tuple[str, ...] = ()


def _build_raft(mcfg):
    from raft_tpu.models.raft import RAFT
    return RAFT(mcfg)


def _build_sparse(mcfg):
    from raft_tpu.config import OursConfig, sparse_corr_from_env
    from raft_tpu.models.ours import SparseRAFT
    return SparseRAFT(OursConfig(
        mixed_precision=mcfg.mixed_precision,
        alternate_corr=sparse_corr_from_env()))


def _build_lfm2(mcfg):
    from raft_tpu.models.lfm2 import LFM2
    return LFM2(mcfg)


def _build_granite(mcfg):
    from raft_tpu.models.granitemoehybrid import GraniteMoeHybrid
    return GraniteMoeHybrid(mcfg)


def _build_afmoe(mcfg):
    from raft_tpu.models.afmoe import Afmoe
    return Afmoe(mcfg)


def _maybe_add_noise(rng, image1, image2):
    """Per-batch gaussian noise aug (reference ``train.py:373-376``):
    stdv ~ U(0, 5), images perturbed then clamped to [0, 255]."""
    k0, k1, k2 = jax.random.split(rng, 3)
    stdv = jax.random.uniform(k0, (), minval=0.0, maxval=5.0)
    image1 = jnp.clip(
        image1 + stdv * jax.random.normal(k1, image1.shape), 0.0, 255.0)
    image2 = jnp.clip(
        image2 + stdv * jax.random.normal(k2, image2.shape), 0.0, 255.0)
    return image1, image2


def _flow_init_inputs(tcfg, image_shape):
    H, W = image_shape if image_shape is not None else tcfg.image_size
    dummy = jnp.zeros((1, H, W, 3), jnp.float32)
    return (dummy, dummy), {"iters": 1}


def _flow_apply(tcfg, freeze_bn, apply_fn, variables, batch, rngs):
    image1, image2 = batch["image1"], batch["image2"]
    if tcfg.add_noise:
        image1, image2 = _maybe_add_noise(rngs["noise"], image1, image2)
    return apply_fn(
        variables, image1, image2, iters=tcfg.iters,
        train=True, freeze_bn=freeze_bn,
        rngs={"dropout": rngs["dropout"]},
        mutable=["batch_stats"])


def _raft_loss(tcfg, freeze_bn):
    def loss_fn(apply_fn, variables, batch, rngs, step):
        out, mutated = _flow_apply(tcfg, freeze_bn, apply_fn, variables,
                                   batch, rngs)
        loss, metrics = sequence_loss(
            out, batch["flow"], batch["valid"], gamma=tcfg.gamma,
            normalization=tcfg.loss_normalization)
        return loss, metrics, mutated
    return loss_fn


def _sparse_loss(tcfg, freeze_bn):
    """The fork's active trainer (reference train.py:19 ->
    core/ours.py): list of per-outer-iteration dense flows plus sparse
    keypoint predictions ((ref, key_flow, ...) tuples), with the
    auxiliary sparse loss gated to the first sparse_lambda_steps
    (reference train.py:379-383)."""
    def loss_fn(apply_fn, variables, batch, rngs, step):
        (flow_preds, sparse_preds), mutated = _flow_apply(
            tcfg, freeze_bn, apply_fn, variables, batch, rngs)
        out = jnp.stack(list(flow_preds))
        loss, metrics = sequence_loss(
            out, batch["flow"], batch["valid"], gamma=tcfg.gamma,
            normalization=tcfg.loss_normalization)
        if tcfg.sparse_lambda > 0:
            from raft_tpu.losses import sparse_keypoint_loss
            # key flows are normalized src-dst offsets; the loss
            # compares in pixels, scaled by (W-1, H-1) like the
            # reference (train.py:73-82)
            _, H_, W_, _ = batch["flow"].shape
            scale = jnp.asarray([W_ - 1, H_ - 1], jnp.float32)
            sparse = sparse_keypoint_loss(
                [(p[0], p[1] * scale) for p in sparse_preds],
                batch["flow"], batch["valid"])
            lam = tcfg.sparse_lambda * (step < tcfg.sparse_lambda_steps)
            loss = loss + lam * sparse
            metrics["sparse_loss"] = sparse
            metrics["loss"] = loss
        return loss, metrics, mutated
    return loss_fn


def _token_init_inputs(tcfg, image_shape):
    # parameter shapes do not depend on the sequence's length: a short
    # one keeps the initialising forward off the kernels' tilings
    dummy = jnp.zeros((1, min(tcfg.seq_len, 8)), jnp.int32)
    return (dummy, dummy, dummy), {}


def _token_loss(tcfg, freeze_bn):
    """Next-token cross-entropy over the vocabulary held; the model's
    counters (the row's ``step_counters``) ride the metrics."""
    def loss_fn(apply_fn, variables, batch, rngs, step):
        from raft_tpu.losses import token_cross_entropy
        logits, counters = apply_fn(
            {"params": variables["params"]}, batch["tokens"],
            batch["segment_ids"], batch["positions"], train=True)
        loss, metrics = token_cross_entropy(logits, batch["tokens"],
                                            batch["segment_ids"])
        metrics.update(counters)
        return loss, metrics, {}
    return loss_fn


def _blocked_token_loss(tcfg, freeze_bn):
    """:func:`_token_loss` for a model that runs its own head and loss
    in blocks of positions (``blocked_loss=True``): the step never holds
    the whole step's logits."""
    def loss_fn(apply_fn, variables, batch, rngs, step):
        (loss, metrics), counters = apply_fn(
            {"params": variables["params"]}, batch["tokens"],
            batch["segment_ids"], batch["positions"], train=True,
            blocked_loss=True)
        metrics.update(counters)
        return loss, metrics, {}
    return loss_fn


FAMILIES: Dict[str, Family] = {
    "raft": Family(_build_raft, _flow_init_inputs, _raft_loss,
                   raft_options=True, torch_weights=True, flow_init=True,
                   spatial_shards=True),
    "sparse": Family(_build_sparse, _flow_init_inputs, _sparse_loss,
                     sparse_preds=True),
    "lfm2_moe": Family(_build_lfm2, _token_init_inputs, _token_loss,
                       tokens=True, config_cls=LMConfig,
                       step_counters=("tokens", "routed_here",
                                      "expert_load_max", "dropped")),
    "granitemoehybrid": Family(_build_granite, _token_init_inputs,
                               _token_loss, tokens=True,
                               config_cls=GraniteHybridConfig,
                               step_counters=("tokens", "ssm_resets",
                                              "ssd_chunks")),
    "afmoe": Family(_build_afmoe, _token_init_inputs, _blocked_token_loss,
                    tokens=True, config_cls=AfmoeConfig,
                    step_counters=("tokens", "routed_here",
                                   "expert_load_max", "dropped",
                                   "window_pairs", "causal_pairs")),
}

#: the rows ``evaluate.py`` and ``demo.py`` offer: image pairs in, flow out
FLOW_FAMILIES = tuple(name for name, row in FAMILIES.items()
                      if not row.tokens)


def family_of(model_family: str) -> Family:
    try:
        return FAMILIES[model_family]
    except KeyError:
        raise ValueError(f"unknown model_family {model_family!r}; "
                         f"choose from {sorted(FAMILIES)}") from None
