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
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import core, struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.config import TrainConfig
from raft_tpu.families import family_of
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
    The family's row (``raft_tpu/families.py``) makes the inputs the
    model is initialised on (``image_shape`` is read by the rows of
    image pairs only)."""
    from raft_tpu.optim import fetch_optimizer

    args, kwargs = family_of(tcfg.model_family).init_inputs(tcfg, image_shape)
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


def _all_finite(tree) -> jnp.ndarray:
    """Scalar bool: every leaf of ``tree`` is entirely finite."""
    leaves = [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(tree)]
    return functools.reduce(jnp.logical_and, leaves, jnp.bool_(True))


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

    family_loss = family_of(tcfg.model_family).make_loss(tcfg, freeze_bn)

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
