"""The jitted, mesh-sharded train/eval steps.

The whole reference hot loop body (``train.py:368-421``: forward, sequence
loss, backward, clip, optimizer step, scheduler step, metric computation)
compiles into ONE XLA program per device. Batch inputs arrive sharded over
the ``data`` mesh axis, parameters are replicated; XLA inserts the gradient
all-reduce (the TPU equivalent of ``nn.DataParallel``'s gather +
``loss.backward()`` sync).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import core, struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.losses import sequence_loss
from raft_tpu.resilience import active_injector


class RAFTTrainState(struct.PyTreeNode):
    """Carried training state: step, params, BN running stats, opt state.

    Unlike the reference (which checkpoints only ``model.state_dict()``,
    ``train.py:398-400``), the full state is checkpointable so training
    truly resumes (SURVEY.md §5 checkpoint/resume gap).
    """

    step: jnp.ndarray
    params: core.FrozenDict
    batch_stats: core.FrozenDict
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        # the default optimizer names its own stages (`grad_clip`,
        # `optimizer_update`: optim.fetch_optimizer)
        updates, new_opt_state = self.tx.update(
            grads, self.opt_state, self.params)
        with jax.named_scope("optimizer_update"):
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params,
                            opt_state=new_opt_state)


def create_train_state(rng, model, tcfg: TrainConfig,
                       image_shape: Optional[Tuple[int, int]] = None,
                       tx: Optional[optax.GradientTransformation] = None,
                       mesh: Optional[Mesh] = None) -> RAFTTrainState:
    """Initialize params + opt state (replicated over ``mesh`` if given).
    The family's row of ``FAMILIES`` makes the inputs the model is
    initialised on (``image_shape`` is read by the flow families only)."""
    from raft_tpu.optim import fetch_optimizer

    args, kwargs = family_of(tcfg).init_inputs(tcfg, image_shape)
    variables = model.init({"params": rng, "dropout": rng}, *args, **kwargs)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", core.FrozenDict({}))
    tx = tx if tx is not None else fetch_optimizer(tcfg)
    state = RAFTTrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        batch_stats=batch_stats, opt_state=tx.init(params),
        apply_fn=model.apply, tx=tx)
    if mesh is not None:
        from raft_tpu.parallel.mesh import replicate
        state = replicate(state, mesh)
    return state


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


def _all_finite(tree) -> jnp.ndarray:
    """Scalar bool: every leaf of ``tree`` is entirely finite."""
    leaves = [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(tree)]
    return functools.reduce(jnp.logical_and, leaves, jnp.bool_(True))


# ------------------------------------------------------------ the families
#
# One row a ``model_family``: how to make the inputs its model is
# initialised on, and how to turn ``(apply_fn, variables, batch, rngs,
# step)`` into ``(loss, metrics, mutated)``. Everything else in the step
# (the guard, ``apply_gradients``, donation, jit) is one code path.

class Family(NamedTuple):
    #: ``(tcfg, image_shape) -> (args, kwargs)`` of ``model.init``
    init_inputs: Callable
    #: ``(tcfg, freeze_bn) -> loss_fn(apply_fn, variables, batch, rngs,
    #: step) -> (loss, metrics, mutated)``; ``rngs`` holds ``noise`` and
    #: ``dropout`` keys already folded with the step
    make_loss: Callable


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


def _flow_list_loss(tcfg, freeze_bn):
    """ours_02 snapshot: a plain list of dense flows."""
    def loss_fn(apply_fn, variables, batch, rngs, step):
        flow_preds, mutated = _flow_apply(tcfg, freeze_bn, apply_fn,
                                          variables, batch, rngs)
        loss, metrics = sequence_loss(
            jnp.stack(list(flow_preds)), batch["flow"],
            batch["valid"], gamma=tcfg.gamma,
            normalization=tcfg.loss_normalization)
        return loss, metrics, mutated
    return loss_fn


def _flow_corr_loss(tcfg, freeze_bn):
    """The two-list snapshot trainer (reference train_02.py:54-81): flow
    + corr predictions, each under a uniformly-weighted masked L1."""
    def loss_fn(apply_fn, variables, batch, rngs, step):
        from raft_tpu.losses import sequence_corr_loss
        (flow_preds, corr_preds), mutated = _flow_apply(
            tcfg, freeze_bn, apply_fn, variables, batch, rngs)
        loss, metrics = sequence_corr_loss(
            jnp.stack(list(flow_preds)),
            jnp.stack(list(corr_preds)),
            batch["flow"], batch["valid"])
        return loss, metrics, mutated
    return loss_fn


def _sparse_loss(tcfg, freeze_bn):
    """The fork's active trainer (reference train.py:19 ->
    core/ours.py): list of per-outer-iteration dense flows plus sparse
    keypoint predictions ((ref, key_flow, ...) tuples —
    TwoStageKeypointRAFT emits the same contract), with the auxiliary
    sparse loss gated to the first sparse_lambda_steps (reference
    train.py:379-383)."""
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
    """Next-token cross-entropy over the vocabulary held; the routing
    counters of the expert layers ride the metrics."""
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


FAMILIES: Dict[str, Family] = {
    "raft": Family(_flow_init_inputs, _raft_loss),
    "keypoint_transformer": Family(_flow_init_inputs, _flow_list_loss),
    "dual_query": Family(_flow_init_inputs, _flow_corr_loss),
    "full_transformer": Family(_flow_init_inputs, _flow_corr_loss),
    "sparse": Family(_flow_init_inputs, _sparse_loss),
    "two_stage": Family(_flow_init_inputs, _sparse_loss),
    "lfm2_moe": Family(_token_init_inputs, _token_loss),
}

def family_of(tcfg: TrainConfig) -> Family:
    try:
        return FAMILIES[tcfg.model_family]
    except KeyError:
        raise ValueError(f"unknown model_family {tcfg.model_family!r}; "
                         f"choose from {sorted(FAMILIES)}") from None


def make_train_step(tcfg: TrainConfig, freeze_bn: bool = False,
                    mesh: Optional[Mesh] = None,
                    donate: bool = True,
                    guard_nonfinite: bool = True) -> Callable:
    """Build the jitted train step.

    ``freeze_bn`` mirrors the reference's post-chairs BN freeze
    (``train.py:414-415`` / ``core/raft.py:60-63``).

    ``guard_nonfinite`` (default on) arms the non-finite step guard: a
    batch producing NaN/Inf loss or grads has its parameter/optimizer/BN
    update suppressed inside the jitted program (``jnp.where`` select,
    no host round-trip) and reports ``metrics["skipped_steps"] = 1``;
    one poison batch then costs one step instead of the whole run. On a
    finite step the select picks the freshly-computed arrays, so
    per-step numerics are bit-identical to the unguarded step. The step
    counter always advances (it counts batches seen, keeping the host
    loop and LR schedule aligned).

    Fault injection: when the active
    :class:`raft_tpu.resilience.FaultInjector` carries ``nan_loss_steps``
    (trace-time constant), the loss is forced non-finite at those step
    numbers — CPU-testable coverage of the guard. With an inert injector
    no injection nodes are traced.

    Returns ``step_fn(state, batch, rng) -> (state, metrics)`` where
    ``batch`` is what the family's loss reads: for the flow families a
    dict with ``image1/image2`` (B,H,W,3) float [0,255], ``flow``
    (B,H,W,2), ``valid`` (B,H,W); for the token family ``tokens``,
    ``segment_ids``, ``positions`` (B,S) int32.
    """
    nan_steps = tuple(active_injector().nan_loss_steps)

    family_loss = family_of(tcfg).make_loss(tcfg, freeze_bn)

    def step_fn(state: RAFTTrainState, batch: Dict[str, jnp.ndarray], rng):
        noise_rng, dropout_rng = jax.random.split(
            jax.random.fold_in(rng, state.step))
        rngs = {"noise": noise_rng, "dropout": dropout_rng}

        def loss_fn(params):
            variables = {"params": params,
                         "batch_stats": state.batch_stats}
            loss, metrics, mutated = family_loss(
                state.apply_fn, variables, batch, rngs, state.step)
            # Under freeze_bn (or a BN-free model) nothing is written to
            # the batch_stats collection; keep the existing stats then.
            new_bs = mutated.get("batch_stats")
            if not new_bs:
                new_bs = state.batch_stats
            if nan_steps:
                # Multiplicative poison so the backward pass goes
                # non-finite too (NaN * grad = NaN), like a real blowup.
                inject = functools.reduce(
                    jnp.logical_or,
                    [state.step == s for s in nan_steps],
                    jnp.bool_(False))
                loss = loss * jnp.where(inject, jnp.float32(jnp.nan), 1.0)
                metrics["loss"] = loss
            return loss, (metrics, new_bs)

        (loss, (metrics, new_bs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        metrics["grad_norm"] = optax.global_norm(grads)
        new_state = state.apply_gradients(grads).replace(batch_stats=new_bs)
        if guard_nonfinite:
            ok = jnp.logical_and(jnp.all(jnp.isfinite(loss)),
                                 _all_finite(grads))

            def keep(new, old):
                return jnp.where(ok, new, old)

            new_state = new_state.replace(
                params=jax.tree.map(keep, new_state.params, state.params),
                opt_state=jax.tree.map(keep, new_state.opt_state,
                                       state.opt_state),
                batch_stats=jax.tree.map(keep, new_state.batch_stats,
                                         state.batch_stats))
            metrics["skipped_steps"] = \
                jnp.logical_not(ok).astype(jnp.float32)
        return new_state, metrics

    if mesh is not None:
        # Batch arrays arrive committed by ``shard_batch`` — batch dim on
        # ``data`` and, for spatial arrays, rows on ``spatial`` (2-D
        # data x sequence-parallel mesh). Let jit adopt those input
        # shardings rather than pinning (which would reject the
        # sequence-parallel layout); params/rng are replicated.
        from raft_tpu.parallel.spatial import spatial_kernel_mesh

        def traced_step(state, batch, rng):
            # trace-time mesh context: lets the correlation engine wrap
            # its Pallas kernel in shard_map when the spatial axis is
            # active (see parallel.spatial.spatial_kernel_mesh)
            with spatial_kernel_mesh(mesh):
                return step_fn(state, batch, rng)

        repl = NamedSharding(mesh, P())
        return jax.jit(
            traced_step,
            in_shardings=(None, None, repl),
            donate_argnums=(0,) if donate else ())
    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def make_eval_step(iters: int = 32) -> Callable:
    """Jitted inference step: ``(state, image1, image2) -> (flow_low,
    flow_up)`` (the reference ``test_mode`` interface,
    ``core/raft.py:142-143``)."""

    @functools.partial(jax.jit, static_argnums=())
    def eval_fn(state: RAFTTrainState, image1, image2, flow_init=None):
        return state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            image1, image2, iters=iters, flow_init=flow_init,
            test_mode=True)

    return eval_fn
