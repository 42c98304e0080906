"""Training entry point: stage curriculum, periodic val + checkpoints.

The reference trainer (``train.py:340-427``) is a Python hot loop around a
DataParallel model; here the whole step (forward, sequence loss, backward,
clip, AdamW, schedule) is one jitted, mesh-sharded XLA program
(:func:`raft_tpu.parallel.make_train_step`) fed by a prefetching host
loader. Flags mirror reference ``train.py:431-452``; stage schedules mirror
``train_standard.sh`` / ``train_mixed.sh``.

Improvements over the reference, kept explicit:
  * true resume (``--resume``): step/optimizer/BN state round-trip through
    orbax (the reference restarts the schedule every stage), and the
    input-pipeline cursor rides every checkpoint — resume continues the
    epoch at the exact sample, bit-identically to an uninterrupted run
    (``scripts/fault_drill.py --drill resume-exact`` proves it);
  * graceful preemption: SIGTERM/SIGINT checkpoint the exact step and
    exit cleanly, multi-host-safe (:class:`_PreemptionGuard`);
  * validation runs through the shape-bucketed jitted
    :class:`raft_tpu.evaluate.FlowPredictor`;
  * scalars stream to JSONL (+ TensorBoard when available).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import os
import time
from typing import Optional, Sequence

import jax
import numpy as np

import signal
import threading

from raft_tpu import checkpoint as ckpt_lib
from raft_tpu import evaluate
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.families import FAMILIES, family_of
from raft_tpu.resilience import TrainingDiverged, all_hosts_agree
from raft_tpu.optim import make_schedule
from raft_tpu.parallel import (create_train_state, make_mesh,
                               make_train_step, shard_batch)
from raft_tpu.utils.compile_count import xla_compile_count
from raft_tpu.utils.logger import TrainLogger
from raft_tpu.utils.profiling import host_timer


class _PreemptionGuard:
    """Graceful-preemption handling (TPU pods get SIGTERM'd; the
    reference's loop has no failure handling at all, SURVEY.md §5).

    While installed, SIGTERM/SIGINT set a flag instead of killing the
    process; the train loop checks it each step, checkpoints the full
    state, and returns cleanly — ``--resume`` then continues from the
    exact step.  A second signal restores default handling (force quit).
    Only installs from the main thread (signal API requirement); no-ops
    elsewhere (e.g. pytest workers running train() off-main)."""

    def __init__(self):
        self.requested = False
        self._installed = False
        self._previous = {}

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for sig in (signal.SIGTERM, signal.SIGINT):
                self._previous[sig] = signal.signal(sig, self._handle)
            self._installed = True
        return self

    def _handle(self, signum, frame):
        if self.requested:         # second signal: give up gracefully
            signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
        print(f"received signal {signum}: finishing step, "
              "checkpointing, exiting (send again to force quit)",
              flush=True)
        self.requested = True

    def __exit__(self, *exc):
        if self._installed:
            for sig, prev in self._previous.items():
                signal.signal(sig, prev)
        return False


def _preemption_agreed(requested: bool) -> bool:
    """Cross-host agreement on the preemption flag.

    On a multi-host pod SIGTERM delivery is per-host and racy: one host
    diverging into the (collective) checkpoint save while another enters
    the step's collectives would deadlock the pod.  All hosts therefore
    vote at the SAME deterministic points (the caller schedules this by
    step count) and stop iff ANY host saw the signal. The vote itself is
    :func:`raft_tpu.resilience.all_hosts_agree` — the same primitive
    that drives checkpoint commit agreement (there with ``"all"``
    semantics)."""
    return all_hosts_agree(bool(requested), require="any")


def _eval_variables(state):
    return {"params": state.params, "batch_stats": state.batch_stats}


def build_model(model_family: str, mcfg):
    """``mcfg``: a ``RAFTConfig`` for a family of image pairs, the
    row's ``config_cls`` for a token family."""
    return family_of(model_family).build(mcfg)


def _panels_and_validation(tcfg, model, state, batch, panel_fn,
                           validation, eval_iters, logger, step):
    """The ``val_freq`` block after its checkpoint: the training image
    panels of the current batch, then the validation sets."""
    # Single-process only: sharded batch/pred arrays span
    # non-addressable devices on multi-host meshes and device_get would
    # raise there (panels are a debug aid, not worth an allgather of
    # full images).
    if jax.process_count() == 1:
        preds = jax.device_get(panel_fn(
            _eval_variables(state), batch["image1"], batch["image2"]))
        i1, i2, fl = jax.device_get(
            (batch["image1"], batch["image2"], batch["flow"]))
        flow_preds, sparse_preds = (
            preds if family_of(tcfg.model_family).sparse_preds
            else (preds, None))
        logger.write_images(i1, i2, fl, flow_preds, sparse_preds,
                            step=step)
    if validation:
        predictor = evaluate.FlowPredictor(
            model, _eval_variables(state), iters=eval_iters)
        results = evaluate.run_validation(predictor, validation)
        logger.write_dict(results, step=step)


def train(tcfg: TrainConfig, mcfg, *,
          data_root: Optional[str] = None,
          ckpt_dir: str = "checkpoints",
          log_dir: str = "runs",
          restore_ckpt: Optional[str] = None,
          resume: bool = False,
          validation: Sequence[str] = (),
          dataloader=None,
          logger: Optional[TrainLogger] = None,
          eval_iters: int = 32,
          spatial_shards: int = 1,
          loader: str = "auto",
          num_workers: Optional[int] = None):
    """Run one training stage; returns the final train state.

    ``dataloader`` may be injected (tests); by default it is built from
    ``tcfg.stage`` (reference ``datasets.fetch_dataloader``).
    ``spatial_shards`` > 1 splits image rows over that many mesh columns
    (sequence parallelism, where the family's row allows it — the 2-D
    data x spatial step is what ``dryrun_multichip`` validates).

    A token family (``Family.tokens``; ``mcfg`` the row's
    ``config_cls``) goes through the same loop, state, optimizer, guard,
    checkpointer and spans; it has no image panels, validation sets,
    BatchNorm to freeze or ``image_size``, its loader yields packed
    sequences (``data_root`` names an optional token file), and the
    counters its step reports (the row's ``step_counters``) ride each
    ``train.step`` span.

    **Order.** The loop is two steps deep: step n+1 is fetched, sharded
    and dispatched (queued behind step n on the device, the state
    donated) before step n's metrics are waited for, read and logged,
    so the host's work runs while the device does. The logged rate is
    the schedule evaluated eagerly on the host's CPU backend: on the
    accelerator it would queue behind the step in flight. Nothing is
    dispatched past a point that needs the state after a step: a
    ``val_freq`` boundary, ``num_steps``, the preemption vote, an abort
    after ``max_consecutive_skips`` (one step later than the step that
    reached it), the loader running out or raising; there every
    dispatched step is finished and logged first, and only then is the
    state saved, validated or returned, or the loader's exception let
    through.
    """
    family = family_of(tcfg.model_family)
    tokens, step_counters = family.tokens, family.step_counters
    image_size = None if tokens else tcfg.image_size
    rng = jax.random.PRNGKey(tcfg.seed)
    np.random.seed(tcfg.seed)                 # host-side aug reproducibility

    from raft_tpu.parallel.mesh import validate_spatial_shards
    validate_spatial_shards(spatial_shards, tcfg.model_family,
                            image_height=image_size and image_size[0])
    mesh = make_mesh(n_spatial=spatial_shards)
    model = build_model(tcfg.model_family, mcfg)
    run_ckpt_dir = os.path.join(ckpt_dir, tcfg.name)
    # ONE manager per run: saves stop re-scanning the directory and the
    # keep policy sees every save; saves retry transient I/O, restores
    # fall back past truncated/uncommitted steps (raft_tpu/checkpoint.py).
    # With async_checkpointing, save() only dispatches the write; the
    # explicit wait_for_pending() barriers below (preemption, abort,
    # exit — the next save point is covered by save() itself) are where
    # the write is finalized and cross-host commit-voted.
    # gc_orphans: this is the run-OWNING checkpointer — it may sweep
    # step dirs that never made commit.json (crash-interrupted saves).
    ckptr = ckpt_lib.RunCheckpointer(run_ckpt_dir,
                                     async_save=tcfg.async_checkpointing,
                                     gc_orphans=True)

    restored_loader_state = None
    resumed = False
    with ckptr, mesh:
        state = create_train_state(rng, model, tcfg, image_size,
                                   mesh=mesh)
        if resume and ckptr.latest_step() is not None:
            state = ckptr.restore(state)
            resumed = True
            restored_loader_state = ckptr.loader_state(
                int(jax.device_get(state.step)))
            print(f"resumed from step {int(state.step)}")
        elif restore_ckpt:
            params, batch_stats = ckpt_lib.load_params(restore_ckpt)
            state = state.replace(params=params)
            if batch_stats:
                state = state.replace(batch_stats=batch_stats)
            print(f"restored weights from {restore_ckpt}")

        # Post-chairs BN freeze (reference train.py:414-415,
        # core/raft.py:60-63).
        freeze_bn = tcfg.stage != "chairs" and not tokens
        step_fn = make_train_step(tcfg, freeze_bn=freeze_bn, mesh=mesh)
        schedule = make_schedule(tcfg)
        host_cpu = jax.local_devices(backend="cpu")[0]

        if dataloader is None:
            from raft_tpu.data.datasets import fetch_dataloader
            dataloader = fetch_dataloader(
                tcfg.stage, tcfg.batch_size, image_size, seed=tcfg.seed,
                root=data_root, loader=loader, num_workers=num_workers,
                tokens={"seq_len": tcfg.seq_len, "vocab": mcfg.vocab,
                        "token_file": data_root} if tokens else None)
        # Exact-cursor resume: restore this process's input-pipeline
        # state BEFORE the first post-resume batch, so the stream
        # continues at the precise sample the checkpointed step had
        # consumed up to (not an epoch-start replay).
        can_cursor = hasattr(dataloader, "load_state")
        if restored_loader_state is not None and can_cursor:
            dataloader.load_state(restored_loader_state)
            print(f"restored input-pipeline cursor: epoch "
                  f"{dataloader.epoch}, sample {dataloader._pos}")
        elif resumed and int(jax.device_get(state.step)) > 0:
            print("WARNING: checkpoint has no input-pipeline state "
                  "(old format, or a loader without cursor support); "
                  "resuming replays the epoch from its start",
                  flush=True)
        if logger is None:
            logger = TrainLogger(os.path.join(log_dir, tcfg.name),
                                 sum_freq=tcfg.sum_freq)

        # One extra jitted forward per val_freq to render the reference's
        # training image panels (train.py:395-396 → :170-334) from the
        # current batch with current params.
        panel_fn = None if tokens else jax.jit(
            lambda variables, i1, i2: model.apply(variables, i1, i2,
                                                  iters=tcfg.iters))

        step_rng = jax.random.fold_in(rng, 1)
        total_steps = int(state.step)     # steps whose metrics were read
        dispatched = total_steps          # and the step in flight, if any
        keep_training = total_steps < tcfg.num_steps
        guard = _PreemptionGuard()
        # Multi-host runs vote on the flag only at deterministic step
        # counts (a conditional collective would deadlock); single
        # process checks every step with no collective.
        check_every = 1 if jax.process_count() == 1 else 10
        consecutive_skips = 0
        diverged = None         # why the run stops once nothing is in flight
        loader_stats = getattr(dataloader, "stats", None)
        if loader_stats is not None and \
                hasattr(loader_stats, "attach_registry"):
            # Degradation counters onto the same process registry the
            # checkpointer's save/restore timings land on — one
            # telemetry surface for the whole run.
            from raft_tpu.observability import get_registry
            loader_stats.attach_registry(get_registry())
        # Counter deltas must start from the RESTORED totals, not zero —
        # otherwise the first post-resume step logs the whole history as
        # one spurious spike.
        last_substituted = (loader_stats.substituted_samples
                            if loader_stats is not None else 0)
        # Loader snapshot taken as each batch is dispatched. A save
        # comes only once every dispatched step is done, and a batch
        # pulled but not dispatched (the preemption vote's) is not in
        # it: resume re-produces that batch.
        loader_snap = (dataloader.state().to_dict()
                       if hasattr(dataloader, "state") else None)
        timer = host_timer()

        def launch(batch):
            """Shard ``batch`` and queue its step behind the one in
            flight (the state donated), its metrics' copies to the host
            behind it; returns the step's metrics and sharded batch."""
            nonlocal state, dispatched, loader_snap
            with timer.span("train.shard_batch") as span:
                batch = shard_batch(batch, mesh)
                span.nbytes = sum(a.nbytes for a in jax.tree.leaves(batch))
            with timer.span("train.dispatch"):
                state, metrics = step_fn(state, batch, step_rng)
            dispatched += 1
            for leaf in jax.tree.leaves(metrics):
                leaf.copy_to_host_async()
            if loader_snap is not None:
                with timer.span("train.loader_snapshot"):
                    loader_snap = dataloader.state().to_dict()
            return metrics, batch

        def finish(root, metrics):
            """Wait for the oldest step in flight, read its metrics and
            log them under ``root``, which then reads ``complete`` 1."""
            nonlocal total_steps, consecutive_skips, last_substituted
            # device_get would block as long; waiting first tells the
            # device's time from the fetch's
            with timer.span("train.device_wait"):
                jax.block_until_ready(metrics)
            with timer.span("train.metrics_fetch", leaves=len(metrics)):
                host_metrics = jax.device_get(metrics)
            total_steps += 1
            for key in step_counters:
                if key in host_metrics:
                    root.args[key] = int(host_metrics[key])
            with timer.span("train.log"):
                # Degradation counters into the scalar stream (logger
                # accumulates them as run totals): per-step skip flag
                # from the jitted guard, substitution delta from the
                # loader.
                if loader_stats is not None:
                    subs = loader_stats.substituted_samples
                    host_metrics["substituted_samples"] = \
                        float(subs - last_substituted)
                    last_substituted = subs
                if host_metrics.get("skipped_steps", 0.0) > 0:
                    consecutive_skips += 1
                else:
                    consecutive_skips = 0
                with jax.default_device(host_cpu):
                    lr = float(schedule(total_steps - 1))
                logger.push(host_metrics, lr=lr)
            root.args["complete"] = 1

        def stop_voted():
            # before a batch is dispatched, at the same step counts on
            # every host
            return (dispatched % check_every == 0
                    and _preemption_agreed(guard.requested))

        pending = None          # the step in flight: (metrics, batch)
        with guard, contextlib.ExitStack() as leaving:
            # what a compiling step freezes (below) is the cyclic
            # collector's again once the loop is left, however it is
            leaving.callback(gc.unfreeze)
            # the collector's passes are spans for as long as steps run
            leaving.enter_context(timer.collector_spans())
            # the while-condition check also escapes a pathological spin
            # over an exhausted one-shot dataloader (local flag only; no
            # collectives run in an empty pass)
            while keep_training and not guard.requested:
                batches = iter(dataloader)
                exhausted = preempted = False
                while not exhausted:
                    # Two deep: step n+1 is dispatched before step n's
                    # metrics are read, so the host's work lies behind
                    # the device's. One root span a step in the process
                    # host timer, from asking the loader for the next
                    # batch to the end of this step's logger.push
                    # (`complete` 1 there); `unit` is the step's number.
                    # A root with nothing in flight before it also
                    # fetches and dispatches its own step; `ahead` 1
                    # where its step was already in flight when it began
                    # and it dispatched the next one: its host work lay
                    # wholly behind the device's.
                    compiles0 = xla_compile_count()
                    root = timer.span("train.step", unit=total_steps + 1,
                                      complete=0, ahead=0)
                    following, own = None, False
                    try:
                        if pending is None:
                            with timer.span("train.loader_wait"):
                                batch = next(batches, None)
                            if batch is None:
                                break
                            preempted = stop_voted()
                            if not preempted:
                                pending, own = launch(batch), True
                        # No step is dispatched past a point that needs
                        # a finished state: a val_freq boundary, the
                        # last step, an abort.
                        if pending is not None and not preempted \
                                and diverged is None \
                                and dispatched % tcfg.val_freq \
                                and dispatched < tcfg.num_steps:
                            try:
                                with timer.span("train.loader_wait"):
                                    batch = next(batches, None)
                                exhausted = batch is None
                                preempted = not exhausted and stop_voted()
                                if not (exhausted or preempted):
                                    following = launch(batch)
                                    root.args["ahead"] = int(not own)
                            except BaseException:
                                # What raised began no step: its root
                                # closes incomplete, and the step in
                                # flight ends complete and logged under
                                # a root of its own before the exception
                                # goes on.
                                timer.close_root(root)
                                root = timer.span(
                                    "train.step", unit=total_steps + 1,
                                    complete=0, ahead=0)
                                finish(root, pending[0])
                                raise
                        if pending is not None:
                            finish(root, pending[0])
                            batch, pending = pending[1], following
                    finally:
                        compiles = xla_compile_count() - compiles0
                        if compiles:
                            root.args["compiles"] = compiles
                            # What tracing and compiling left (jaxprs,
                            # the executables' Python side: ~1 M
                            # container objects for a token model's
                            # step) lives as long as the run. Out of
                            # the cyclic collector's reach, a full pass
                            # no longer walks it: on the chip such a
                            # pass held one 0.8 s step in ~25 for
                            # 1.2-1.8 s (PERF.md, Findings of PR 35).
                            gc.collect()
                            gc.freeze()
                        timer.close_root(root)
                    if preempted:
                        ckptr.save(state, loader_state=loader_snap)
                        ckptr.wait_for_pending()  # commit before exit
                        print(f"preemption checkpoint at step "
                              f"{total_steps}; resume with --resume")
                        return state
                    if tcfg.max_consecutive_skips and diverged is None \
                            and consecutive_skips \
                            >= tcfg.max_consecutive_skips:
                        diverged = (f"{consecutive_skips} consecutive "
                                    f"non-finite steps at step {total_steps}")
                    if diverged is not None and pending is None:
                        # The guard never applied a non-finite update,
                        # so the state being saved is the last finite
                        # one; persistent divergence needs an operator,
                        # not more poisoned batches.
                        ckptr.save(state, loader_state=loader_snap)
                        ckptr.wait_for_pending()   # commit before abort
                        raise TrainingDiverged(
                            f"{diverged}; checkpointed the last finite "
                            f"state (step {total_steps}) to {run_ckpt_dir}")

                    if total_steps % tcfg.val_freq == 0:
                        with timer.span("train.checkpoint",
                                        unit=total_steps):
                            ckptr.save(state, loader_state=loader_snap)
                        if not tokens:
                            with timer.span("train.validation",
                                            unit=total_steps):
                                _panels_and_validation(
                                    tcfg, model, state, batch, panel_fn,
                                    validation, eval_iters, logger,
                                    total_steps)
                        # A SIGTERM landing during the validation/panel
                        # block above must not wait for the next batch
                        # to complete: re-vote here (deterministic
                        # point — every host reaches this val_freq
                        # boundary). The val checkpoint above already
                        # holds this exact state.
                        if _preemption_agreed(guard.requested):
                            # The val checkpoint above may still be in
                            # flight (async mode): commit it so resume
                            # sees this exact step.
                            ckptr.wait_for_pending()
                            print(f"preemption after validation at step "
                                  f"{total_steps}; resume with --resume")
                            return state

                    if total_steps >= tcfg.num_steps:
                        keep_training = False
                        break

        ckptr.save(state, loader_state=loader_snap)
        ckptr.wait_for_pending()       # exit barrier: final save commits
    return state


def resolve_train_corr_engine(model_family, corr_impl, alternate_corr,
                              corr_dtype, small, mixed_precision,
                              image_size, spatial_shards: int = 1) -> bool:
    """Resolve whether canonical-RAFT training runs through the
    on-demand banded kernel.

    ``corr_impl=None`` defaults to "auto" for the raft family: train
    through the kernel on TPU wherever the crop fits its *backward*
    VMEM budget — measured +34%/+49% samples/s at chairs b4/b8 with
    ~1.4 GB less HBM (TPU_EXTRAS raft_train alt arms), identical
    numerics (f32 accumulation, same zero-coords-grad contract). An
    explicit ``--alternate_corr`` always wins; an explicit
    ``--corr_dtype bfloat16`` (a materialized-storage lever) pins the
    materialized engine rather than silently losing its meaning; off
    TPU the jnp on-demand path is slower than the materialized matmul
    form, so auto keeps the volume there."""
    if alternate_corr:
        return True
    corr_impl = corr_impl or (
        "auto" if family_of(model_family).raft_options else "fixed")
    if corr_impl != "auto" or corr_dtype == "bfloat16":
        return False
    import jax as _jax

    from raft_tpu.models.corr import alternate_eval_eligible
    probe_cfg = RAFTConfig(small=small, mixed_precision=mixed_precision)
    # spatial_shards > 1 composes since round 5 (VERDICT r4 #2): the
    # kernel runs per-shard under shard_map with the pooled target
    # pyramid replicated; eligibility additionally requires the feature
    # rows to divide across the spatial axis.
    return (_jax.default_backend() == "tpu"
            and alternate_eval_eligible(probe_cfg, image_size,
                                        differentiable=True,
                                        spatial_shards=spatial_shards))


def lm_config_from_json(path: Optional[str],
                        model_family: str = "lfm2_moe"):
    """A token family's config (the row's ``config_cls``) from a JSON
    file's ``model`` object (or its top level); ``None`` gives the
    published model whole."""
    config_cls = family_of(model_family).config_cls
    if path is None:
        return config_cls()
    import json
    with open(path) as f:
        keys = json.load(f)
    keys = dict(keys.get("model", keys))
    if "layer_types" in keys:
        keys["layer_types"] = tuple(keys["layer_types"])
    return config_cls(**keys)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Train RAFT (TPU-native). Flags mirror the reference "
                    "train.py:431-452.")
    parser.add_argument("--name", default="raft", help="experiment name")
    parser.add_argument("--stage", default="chairs",
                        choices=["chairs", "things", "sintel", "kitti"])
    parser.add_argument("--model_family", default="raft",
                        choices=list(FAMILIES),
                        help="canonical RAFT, the fork's sparse-keypoint "
                             "(ours) family, or a language model on packed "
                             "token sequences: lfm2_moe (LFM2-MoE: gated "
                             "short convolutions, grouped-query "
                             "attention, sigmoid-routed experts), "
                             "granitemoehybrid (Granite-4.0-H: Mamba-2 "
                             "state-space layers 9:1 with attention "
                             "without positions, scaled residual path) or "
                             "afmoe (Trinity: sliding-window and full "
                             "attention 3:1 with gated heads, routed "
                             "experts beside a shared one, head and loss "
                             "in blocks); see --lm_config, --seq_len")
    parser.add_argument("--lm_config", default=None,
                        help="token families only: a JSON file whose "
                             "`model` object (or top level) holds the "
                             "family's config keys: the published sizes "
                             "and this chip's share (vocab_held; lfm2_moe "
                             "and afmoe: experts_held, expert_offset), "
                             "e.g. benchmark/configs/lfm2_24b_a2b.json, "
                             "benchmark/configs/granite_4_0_h_micro.json, "
                             "benchmark/configs/trinity_mini.json; "
                             "default: the published model whole")
    parser.add_argument("--seq_len", type=int, default=8192,
                        help="token families only: tokens a packed sequence "
                             "(--batch_size counts sequences; --data_root "
                             "names an optional .npz token file with "
                             "`tokens` and document `offsets`, else the "
                             "stream is seeded)")
    parser.add_argument("--sparse_lambda", type=float, default=0.0,
                        help="auxiliary sparse loss weight (first 20k "
                             "steps; reference train.py:379-383)")
    parser.add_argument("--restore_ckpt", default=None,
                        help="orbax dir or torch .pth (params only)")
    parser.add_argument("--resume", action="store_true",
                        help="resume full state from this run's checkpoints")
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--validation", nargs="*", default=[],
                        choices=list(evaluate._VALIDATORS))
    parser.add_argument("--lr", type=float, default=4e-4)
    parser.add_argument("--num_steps", type=int, default=100000)
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--image_size", type=int, nargs=2,
                        default=[368, 496])
    parser.add_argument("--wdecay", type=float, default=1e-4)
    parser.add_argument("--epsilon", type=float, default=1e-8)
    parser.add_argument("--clip", type=float, default=1.0)
    parser.add_argument("--dropout", type=float, default=0.0)
    parser.add_argument("--gamma", type=float, default=0.8,
                        help="exponential loss weighting")
    parser.add_argument("--iters", type=int, default=None,
                        help="refinement iterations (canonical RAFT "
                             "only; default 12 — the other families' "
                             "iteration counts are architectural)")
    parser.add_argument("--add_noise", action="store_true")
    parser.add_argument("--mixed_precision", action="store_true")
    parser.add_argument("--alternate_corr", action="store_true")
    parser.add_argument("--corr_dtype", default=None,
                        choices=["float32", "bfloat16", "auto"],
                        help="storage dtype of the correlation pyramid "
                             "(float32 = reference autocast semantics; "
                             "bfloat16 halves its HBM footprint)")
    parser.add_argument("--scheduler", default="onecycle",
                        choices=["onecycle", "step", "cosine_warmup"])
    parser.add_argument("--spatial_shards", type=int, default=1,
                        help="split image rows over this many mesh "
                             "columns (sequence-parallel training; "
                             "canonical family only, must divide the "
                             "device count and the image height)")
    parser.add_argument("--val_freq", type=int, default=5000)
    parser.add_argument("--async_ckpt", action="store_true",
                        help="non-blocking checkpointing: saves "
                             "dispatch the orbax write and training "
                             "keeps stepping; the write is finalized + "
                             "cross-host commit-voted at the next save "
                             "point / preemption / abort / exit "
                             "barrier (hides multi-second save latency "
                             "on big models)")
    parser.add_argument("--corr_impl", default=None,
                        choices=["fixed", "auto"],
                        help="correlation engine for canonical-RAFT "
                             "training: 'auto' (default for the raft "
                             "family) trains through the on-demand "
                             "banded kernel on TPU when the crop fits "
                             "its backward VMEM budget — measured +34%% "
                             "samples/s at chairs b4 and +49%% at b8 "
                             "with ~1.4 GB less HBM, numerics "
                             "identical; 'fixed' honors "
                             "--alternate_corr as given")
    parser.add_argument("--data_root", default=None)
    parser.add_argument("--loader", default="auto",
                        choices=("auto", "thread", "process"),
                        help="input pipeline kind: forked worker "
                             "processes (the torch num_workers=24 "
                             "analogue) vs a thread prefetcher; auto "
                             "picks process on >=4-core hosts")
    parser.add_argument("--num_workers", type=int, default=None,
                        help="loader workers; default sizes to the host "
                             "core count (cap 24, reference "
                             "core/datasets.py:237)")
    parser.add_argument("--ckpt_dir", default="checkpoints")
    parser.add_argument("--log_dir", default="runs")
    parser.add_argument("--seed", type=int, default=2022)
    args = parser.parse_args(argv)
    from raft_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    evaluate.reject_raft_only_flags(parser, args)   # incl. --iters
    family = family_of(args.model_family)
    tokens = family.tokens
    if not tokens and args.lm_config:
        parser.error("--lm_config applies to the token families only "
                     "(lfm2_moe, granitemoehybrid, afmoe)")
    if tokens and (args.validation or args.spatial_shards != 1
                   or args.restore_ckpt):
        parser.error("--validation, --spatial_shards and --restore_ckpt "
                     "apply to the flow families only")
    # only a family with keypoint predictions has something to weigh
    if args.sparse_lambda > 0 and not family.sparse_preds:
        parser.error("--sparse_lambda requires a keypoint family "
                     "(sparse)")
    iters = args.iters if args.iters is not None else 12

    if args.corr_impl == "auto" and not family.raft_options:
        parser.error("--corr_impl auto applies to the canonical RAFT "
                     f"family only (the {args.model_family} family's "
                     "correlation engine has its own config default)")
    alternate = resolve_train_corr_engine(
        args.model_family, args.corr_impl, args.alternate_corr,
        args.corr_dtype, args.small, args.mixed_precision,
        tuple(args.image_size), args.spatial_shards)

    tcfg = TrainConfig(
        name=args.name, stage=args.stage,
        model_family=args.model_family, sparse_lambda=args.sparse_lambda,
        lr=args.lr,
        num_steps=args.num_steps, batch_size=args.batch_size,
        image_size=tuple(args.image_size), seq_len=args.seq_len,
        wdecay=args.wdecay,
        epsilon=args.epsilon, clip=args.clip, gamma=args.gamma,
        add_noise=args.add_noise, iters=iters,
        val_freq=args.val_freq, scheduler=args.scheduler, seed=args.seed,
        async_checkpointing=args.async_ckpt)
    if tokens:
        mcfg = lm_config_from_json(args.lm_config, args.model_family)
    else:
        mcfg = RAFTConfig(
            small=args.small, dropout=args.dropout, iters=iters,
            alternate_corr=alternate,
            mixed_precision=args.mixed_precision,
            corr_dtype=args.corr_dtype or "auto")

    t0 = time.time()
    train(tcfg, mcfg, data_root=args.data_root, ckpt_dir=args.ckpt_dir,
          log_dir=args.log_dir, restore_ckpt=args.restore_ckpt,
          resume=args.resume, validation=args.validation,
          spatial_shards=args.spatial_shards, loader=args.loader,
          num_workers=args.num_workers)
    print(f"done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
