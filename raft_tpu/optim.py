"""Optimizer and LR schedules.

Reference ``train.py:107-124`` (``fetch_optimizer``): AdamW with weight decay
and epsilon flags, gradient clipping at ``args.clip`` (global-norm 1.0), and a
choice of schedules — the original RAFT OneCycle (``train_mixed.sh`` era), the
fork's StepLR (``train.py:110-112``: step at 0.8*num_steps, gamma 0.5), and
the vendored-but-unused ``CosineAnnealingWarmupRestarts``
(reference ``core/utils/scheduler.py:6-92``), reproduced here natively in
optax so the capability survives.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import optax

from raft_tpu.config import TrainConfig


def onecycle_schedule(lr: float, num_steps: int,
                      pct_start: float = 0.05) -> optax.Schedule:
    """PyTorch OneCycleLR(linear anneal) as used by original RAFT:
    ``pct_start=0.05, cycle_momentum=False, anneal_strategy='linear'``."""
    warm = max(int(num_steps * pct_start), 1)
    return optax.join_schedules(
        [optax.linear_schedule(lr / 25.0, lr, warm),
         optax.linear_schedule(lr, lr / 25.0 / 1e4, num_steps - warm)],
        [warm])


def step_schedule(lr: float, num_steps: int, decay_point: float = 0.8,
                  gamma: float = 0.5) -> optax.Schedule:
    """The fork's StepLR: multiply by ``gamma`` once at
    ``decay_point * num_steps`` (reference ``train.py:110-112``)."""
    boundary = int(num_steps * decay_point)

    def sched(count):
        return lr * gamma ** (count >= boundary)

    return sched


def cosine_warmup_restarts_schedule(
        max_lr: float, first_cycle_steps: int, cycle_mult: float = 1.0,
        min_lr: float = 1e-7, warmup_steps: int = 0,
        gamma: float = 1.0) -> optax.Schedule:
    """``CosineAnnealingWarmupRestarts`` (reference
    ``core/utils/scheduler.py:6-92``): linear warmup then cosine decay per
    cycle; cycle length multiplies by ``cycle_mult`` and peak LR by ``gamma``
    at each restart.

    Implemented as a host-side closure over integer step count — optax
    schedules are traced with a scalar count, so we mirror the reference's
    cycle arithmetic with jnp ops kept branch-free for the common
    ``cycle_mult == 1`` case, and fall back to a precomputed boundary scan
    otherwise.
    """
    import jax.numpy as jnp

    if cycle_mult == 1.0:
        def sched(count):
            cycle = count // first_cycle_steps
            in_cycle = count % first_cycle_steps
            peak = max_lr * gamma ** cycle
            warm_frac = jnp.minimum(in_cycle / max(warmup_steps, 1), 1.0)
            warm_lr = (peak - min_lr) * warm_frac + min_lr
            t = (in_cycle - warmup_steps) / max(
                first_cycle_steps - warmup_steps, 1)
            cos_lr = min_lr + (peak - min_lr) * (
                1 + jnp.cos(jnp.pi * jnp.clip(t, 0.0, 1.0))) / 2
            return jnp.where(in_cycle < warmup_steps, warm_lr, cos_lr)
        return sched

    # General cycle_mult: precompute enough cycle boundaries (host side).
    boundaries = [0]
    step, length = 0, first_cycle_steps
    while step < 10_000_000 and len(boundaries) < 64:
        step += int(length)
        boundaries.append(step)
        length *= cycle_mult

    def sched(count):
        bs = jnp.asarray(boundaries[:-1])
        lens = jnp.asarray([boundaries[i + 1] - boundaries[i]
                            for i in range(len(boundaries) - 1)])
        cycle = jnp.sum((count >= jnp.asarray(boundaries[1:])).astype(
            jnp.int32))
        start = bs[cycle]
        clen = lens[cycle]
        in_cycle = count - start
        peak = max_lr * gamma ** cycle
        warm_frac = jnp.minimum(in_cycle / max(warmup_steps, 1), 1.0)
        warm_lr = (peak - min_lr) * warm_frac + min_lr
        t = (in_cycle - warmup_steps) / jnp.maximum(clen - warmup_steps, 1)
        cos_lr = min_lr + (peak - min_lr) * (
            1 + jnp.cos(jnp.pi * jnp.clip(t, 0.0, 1.0))) / 2
        return jnp.where(in_cycle < warmup_steps, warm_lr, cos_lr)
    return sched


def make_schedule(cfg: TrainConfig) -> optax.Schedule:
    if cfg.scheduler == "onecycle":
        # Reference fetch_optimizer pads num_steps by 100 to keep the final
        # steps on-schedule (train.py OneCycle total_steps=num_steps+100).
        return onecycle_schedule(cfg.lr, cfg.num_steps + 100)
    if cfg.scheduler == "step":
        return step_schedule(cfg.lr, cfg.num_steps)
    if cfg.scheduler == "cosine_warmup":
        return cosine_warmup_restarts_schedule(
            cfg.lr, first_cycle_steps=cfg.num_steps,
            warmup_steps=max(cfg.num_steps // 20, 1))
    raise ValueError(f"unknown scheduler {cfg.scheduler!r}")


#: leaves AdamW's decay leaves alone, by name
_NO_DECAY = frozenset({
    "expert_bias",                                  # models/lfm2.py
    "A_log", "D", "dt_bias", "norm",                # models/granitemoehybrid.py
    "input_layernorm", "post_attention_layernorm",
    "pre_mlp_layernorm", "post_mlp_layernorm",      # models/afmoe.py
    "q_norm", "k_norm"})


def _decay_mask(params):
    """True where AdamW weight decay applies.

    ``FrozenBatchNorm`` keeps its fixed statistics/affine as params (so
    torch weights convert 1:1) with gradients cut; decay must be masked
    off them too or they would shrink by ``(1 - lr*wd)`` every step. In
    torch they are buffers, which AdamW never touches — this mask restores
    that semantics. A frozen-BN subtree is recognized by its
    ``running_mean``/``running_var`` keys. The expert router's selection
    bias (``expert_bias``, ``models/lfm2.py``) is a buffer in the same
    sense: no gradient reaches it, and decay must not shrink it. The
    state-space mixer's ``A_log``, ``D`` and ``dt_bias`` and the norm
    weights of ``models/granitemoehybrid.py`` take none either, as in
    the published recipes of that family; nor do the norm weights of
    ``models/afmoe.py`` (four a layer, two a head, the last one).
    """
    def mask_tree(tree, name=None):
        if isinstance(tree, dict):
            if "running_mean" in tree and "running_var" in tree:
                return {k: False for k in tree}
            return {k: mask_tree(v, k) for k, v in tree.items()}
        return name not in _NO_DECAY

    # unwrap FrozenDict-likes into plain dicts for optax
    plain = jax.tree_util.tree_map(lambda x: x, params)
    if hasattr(plain, "unfreeze"):
        plain = plain.unfreeze()
    return mask_tree(plain)


def fetch_optimizer(cfg: TrainConfig,
                    schedule: Optional[optax.Schedule] = None
                    ) -> optax.GradientTransformation:
    """AdamW + global-norm clipping (reference ``train.py:107-124``).

    Clipping precedes the optimizer update, matching
    ``torch.nn.utils.clip_grad_norm_(model.parameters(), args.clip)``
    before ``optimizer.step()`` (reference ``train.py:386-389``).
    """
    sched = schedule if schedule is not None else make_schedule(cfg)
    return optax.chain(
        _named("grad_clip", optax.clip_by_global_norm(cfg.clip)),
        _named("optimizer_update",
               optax.adamw(sched, b1=0.9, b2=0.999, eps=cfg.epsilon,
                           weight_decay=cfg.wdecay, mask=_decay_mask)),
    )


def _named(scope: str, tx: optax.GradientTransformation
           ) -> optax.GradientTransformation:
    """``tx`` with its update traced under ``jax.named_scope(scope)``:
    the compiled step's ``op_name`` then tells clipping from the
    optimizer's arithmetic. Same state, same instructions."""
    def update(updates, state, params=None):
        with jax.named_scope(scope):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)
