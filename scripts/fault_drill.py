#!/usr/bin/env python
"""Fault drill: inject each failure class into a tiny training run.

CPU-runnable CI gate for the resilience subsystem
(``raft_tpu/resilience.py``): runs a miniature synthetic-data training
loop with each fault class injected in sequence —

1. transient checkpoint-save I/O errors  -> save retries succeed;
2. corrupt latest checkpoint             -> resume falls back to the
   newest intact step;
3. an unreadable sample                  -> the epoch completes with a
   logged, counted substitution;
4. a NaN batch                           -> the step is skipped, params
   stay finite, the skip is counted;
5. preemption (guard flag)               -> clean checkpoint, resume
   continues from the exact step;
6. async save (``--drill async-save``)   -> dispatch is non-blocking,
   the in-flight step is invisible to restore until the barrier
   commits it, an injected commit failure rolls the step back;
7. multi-host save (``--drill multihost-save``) -> two coordinated
   processes share a checkpoint dir; a targeted injection kills ONE
   host's save commit check and BOTH hosts must roll the step back,
   agree on the older committed step, and restore bit-identical state
   (the torn-step invariant) — per-process loader-state sidecars roll
   back with the step;
8. exact resume (``--drill resume-exact``)  -> training killed
   mid-epoch with one batch pulled but unstepped; resume re-produces
   that batch and the interrupted+resumed run matches an uninterrupted
   control bit-for-bit (batch-index stream, loss trajectory, final
   params), in sync and async checkpoint modes.

Exits nonzero if any recovery path fails (a torn step detected by the
multi-host drill is a failure; any resume divergence likewise). Usage::

    JAX_PLATFORMS=cpu python scripts/fault_drill.py [--drill NAME|--list]
"""

import argparse
import os
import sys
import tempfile
import textwrap
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from raft_tpu import checkpoint as ckpt_lib                 # noqa: E402
from raft_tpu.config import RAFTConfig, TrainConfig         # noqa: E402
from raft_tpu.resilience import (FaultInjector,             # noqa: E402
                                 TrainingDiverged, set_injector)
from raft_tpu.utils.logger import TrainLogger               # noqa: E402

H, W = 64, 96


class SyntheticLoader:
    """Batches with a constant 2px rightward flow."""

    def __init__(self, batch_size=8, n=4, seed=0):
        self.rng = np.random.default_rng(seed)
        self.batch_size = batch_size
        self.n = n

    def __iter__(self):
        for _ in range(self.n):
            img1 = self.rng.uniform(
                0, 255, (self.batch_size, H, W, 3)).astype(np.float32)
            img2 = np.roll(img1, 2, axis=2)
            flow = np.zeros((self.batch_size, H, W, 2), np.float32)
            flow[..., 0] = 2.0
            valid = np.ones((self.batch_size, H, W), np.float32)
            yield {"image1": img1, "image2": img2, "flow": flow,
                   "valid": valid}


def _cfg(num_steps, **kw):
    base = dict(name="drill", num_steps=num_steps, batch_size=8,
                image_size=(H, W), iters=2, val_freq=1000, sum_freq=2)
    base.update(kw)
    return (TrainConfig(**base), RAFTConfig(small=True, iters=2))


def _run(tcfg, mcfg, root, n_batches=8, resume=False):
    from raft_tpu.train import train

    return train(tcfg, mcfg, ckpt_dir=os.path.join(root, "ckpts"),
                 log_dir=os.path.join(root, "logs"),
                 dataloader=SyntheticLoader(n=n_batches), resume=resume,
                 logger=TrainLogger(os.path.join(root, "logs", "drill"),
                                    sum_freq=2, tensorboard=False))


def _finite(state):
    return all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(state.params))


# -- drills --------------------------------------------------------------


def drill_ckpt_io_errors(root):
    """Transient save failures are retried; the run still completes."""
    set_injector(FaultInjector(ckpt_save_errors=2))
    tcfg, mcfg = _cfg(num_steps=2)
    state = _run(tcfg, mcfg, root)
    d = os.path.join(root, "ckpts", "drill")
    assert int(state.step) == 2, f"run did not complete: {int(state.step)}"
    assert ckpt_lib.latest_step(d) == 2, "final save missing"
    assert _finite(state), "non-finite params"


def drill_corrupt_latest_checkpoint(root):
    """Truncate the newest checkpoint; resume falls back and retrains."""
    tcfg, mcfg = _cfg(num_steps=2)
    _run(tcfg, mcfg, root)                       # saves step 2
    tcfg3, _ = _cfg(num_steps=3)
    _run(tcfg3, mcfg, root, resume=True)         # saves step 3
    d = os.path.join(root, "ckpts", "drill")
    with ckpt_lib.RunCheckpointer(d) as ckptr:
        assert sorted(ckptr.all_steps())[-1] == 3
    step_dir = os.path.join(d, "3")
    for r, _, files in os.walk(step_dir):
        for f in files:                          # preemption mid-save
            open(os.path.join(r, f), "w").close()
    assert ckpt_lib.latest_step(d) == 2, "intact fallback failed"
    tcfg4, _ = _cfg(num_steps=4)
    state = _run(tcfg4, mcfg, root, resume=True)  # resumes from 2
    assert int(state.step) == 4, f"resume-after-corruption: {int(state.step)}"
    assert _finite(state)


def drill_corrupt_sample(root):
    """One unreadable sample: the epoch completes with a substitution."""
    from raft_tpu.data.datasets import DataLoader

    class ArrayDataset:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            img = np.full((H, W, 3), float(i), np.float32)
            return (img, img.copy(),
                    np.zeros((H, W, 2), np.float32),
                    np.ones((H, W), np.float32))

    set_injector(FaultInjector(corrupt_sample_indices=frozenset({5})))
    loader = DataLoader(ArrayDataset(), batch_size=8, shuffle=False,
                        num_workers=2, stall_timeout=0)
    batches = list(loader)
    assert len(batches) == 2, f"epoch truncated: {len(batches)} batches"
    assert loader.stats.substituted_samples == 1, \
        f"substitutions: {loader.stats.substituted_samples}"


def drill_nan_batch(root):
    """One poisoned batch: step skipped, params stay finite, counted."""
    set_injector(FaultInjector(nan_loss_steps=(1,)))
    tcfg, mcfg = _cfg(num_steps=3)
    state = _run(tcfg, mcfg, root)
    assert int(state.step) == 3, f"run did not complete: {int(state.step)}"
    assert _finite(state), "NaN leaked into params"
    import json
    scalars = [json.loads(l) for l in open(os.path.join(
        root, "logs", "drill", "scalars.jsonl"))]
    skipped = max(s.get("skipped_steps", 0) for s in scalars)
    assert skipped == 1, f"skip not counted: {skipped}"


def drill_nan_divergence_abort(root):
    """Every batch poisoned: the loop aborts with a finite checkpoint
    instead of grinding on."""
    set_injector(FaultInjector(nan_loss_steps=tuple(range(64))))
    tcfg, mcfg = _cfg(num_steps=50, max_consecutive_skips=3)
    try:
        _run(tcfg, mcfg, root, n_batches=50)
    except TrainingDiverged:
        pass
    else:
        raise AssertionError("divergence did not abort")
    # the third skip is read with step 4 in flight: it is finished
    # (skipped too) before the save
    d = os.path.join(root, "ckpts", "drill")
    assert ckpt_lib.latest_step(d) == 4, "abort checkpoint missing"


def drill_preemption_resume(root):
    """Guard flag mid-run -> exact-step checkpoint -> resume finishes."""
    import raft_tpu.train as train_mod
    from raft_tpu.train import train

    tcfg, mcfg = _cfg(num_steps=50)
    box = [None]

    class SpyGuard(train_mod._PreemptionGuard):
        def __init__(self):
            super().__init__()
            box[0] = self

    class PreemptingLoader(SyntheticLoader):
        def __iter__(self):
            for i, batch in enumerate(super().__iter__()):
                if i == 2:            # SIGTERM lands before batch 3
                    box[0].requested = True
                yield batch

    orig = train_mod._PreemptionGuard
    train_mod._PreemptionGuard = SpyGuard
    try:
        state = train(tcfg, mcfg, ckpt_dir=os.path.join(root, "ckpts"),
                      log_dir=os.path.join(root, "logs"),
                      dataloader=PreemptingLoader(n=50),
                      logger=TrainLogger(os.path.join(root, "logs", "d"),
                                         sum_freq=2, tensorboard=False))
    finally:
        train_mod._PreemptionGuard = orig
    assert int(state.step) == 2, f"preempted at {int(state.step)}, not 2"
    d = os.path.join(root, "ckpts", "drill")
    assert ckpt_lib.latest_step(d) == 2, "preemption checkpoint missing"

    tcfg2, _ = _cfg(num_steps=4)
    state2 = _run(tcfg2, mcfg, root, resume=True)
    assert int(state2.step) == 4, f"resume reached {int(state2.step)}, not 4"
    assert _finite(state2)


class _IndexDataset:
    """Samples carry their own index at ``image1[0, 0, 0]`` — a batch's
    identity is readable from the stacked array, so a drill can compare
    the exact sample stream two runs consumed."""

    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng(1000 + i)
        img1 = rng.uniform(0, 255, (H, W, 3)).astype(np.float32)
        img1[0, 0, 0] = float(i)                 # identity marker
        img2 = np.roll(img1, 2, axis=1)
        flow = np.zeros((H, W, 2), np.float32)
        flow[..., 0] = 2.0
        valid = np.ones((H, W), np.float32)
        return img1, img2, flow, valid


def _losses(log_dir):
    import json
    path = os.path.join(log_dir, "scalars.jsonl")
    return [rec["loss"] for rec in map(json.loads, open(path))
            if "loss" in rec]


def _params_digest(state):
    import hashlib
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(state.params):
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def drill_resume_exact(root):
    """Kill training mid-epoch, resume, and require a bit-identical
    batch-index stream + loss trajectory + final params versus an
    uninterrupted control run — in sync AND async checkpoint modes.
    The interruption lands with one batch pulled but not yet stepped;
    exact resume must re-produce that batch (not skip it, not replay
    an already-trained one)."""
    import raft_tpu.train as train_mod
    from raft_tpu.data.datasets import DataLoader
    from raft_tpu.train import train

    box = [None]

    class SpyGuard(train_mod._PreemptionGuard):
        def __init__(self):
            super().__init__()
            box[0] = self

    class RecordingLoader(DataLoader):
        """Records the index stream it hands the consumer; optionally
        raises the (spied) preemption flag as the ``preempt_at``-th
        batch is being handed over — the train loop then checkpoints
        WITHOUT stepping it, the pulled-but-unstepped case."""

        def __init__(self, *a, preempt_at=None, **kw):
            super().__init__(*a, **kw)
            self.record = []
            self.preempt_at = preempt_at

        def __iter__(self):
            for b in super().__iter__():
                self.record.append(
                    [int(x) for x in b["image1"][:, 0, 0, 0]])
                if len(self.record) - 1 == self.preempt_at:
                    box[0].requested = True
                yield b

    def make_loader(**kw):
        return RecordingLoader(_IndexDataset(), batch_size=8,
                               shuffle=True, num_workers=2, seed=7,
                               stall_timeout=0, **kw)

    def run(sub, tcfg, mcfg, loader, resume=False):
        return train(tcfg, mcfg,
                     ckpt_dir=os.path.join(sub, "ckpts"),
                     log_dir=os.path.join(sub, "logs"),
                     dataloader=loader, resume=resume,
                     logger=TrainLogger(
                         os.path.join(sub, "logs",
                                      "r" if resume else "f"),
                         sum_freq=1, tensorboard=False))

    for mode in ("sync", "async"):
        tcfg, mcfg = _cfg(num_steps=10, sum_freq=1,
                          async_checkpointing=(mode == "async"))
        ctrl = os.path.join(root, mode, "control")
        kill = os.path.join(root, mode, "kill")

        # Control: 10 uninterrupted steps (2.5 epochs of 4 batches).
        ctrl_loader = make_loader()
        ctrl_state = run(ctrl, tcfg, mcfg, ctrl_loader)
        control = ctrl_loader.record
        assert len(control) == 10, f"[{mode}] control pulled " \
            f"{len(control)} batches, expected 10"

        # Interrupted: preemption flag raised as batch 6 is handed
        # over — 6 steps trained, the 7th batch pulled but unstepped.
        int_loader = make_loader(preempt_at=6)
        orig = train_mod._PreemptionGuard
        train_mod._PreemptionGuard = SpyGuard
        try:
            int_state = run(kill, tcfg, mcfg, int_loader)
        finally:
            train_mod._PreemptionGuard = orig
        assert int(int_state.step) == 6, \
            f"[{mode}] preempted at step {int(int_state.step)}, not 6"
        assert len(int_loader.record) == 7
        assert int_loader.record[:6] == control[:6], \
            f"[{mode}] pre-kill stream diverged from control"

        # The checkpoint carries the exact cursor: epoch 1, 2 batches
        # (16 samples) in — the snapshot at step 6, NOT the pump-ahead
        # position (which already pulled batch 7).
        d = os.path.join(kill, "ckpts", "drill")
        with ckpt_lib.RunCheckpointer(d) as ckptr:
            ls = ckptr.loader_state(6)
        assert ls is not None, f"[{mode}] no loader state in checkpoint"
        assert (ls["epoch"], ls["pos"]) == (1, 16), \
            f"[{mode}] wrong cursor: {ls}"

        # Resume: must re-produce the unstepped batch first, then match
        # the control stream, losses and final params bit-for-bit.
        res_loader = make_loader()
        res_state = run(kill, tcfg, mcfg, res_loader, resume=True)
        assert int(res_state.step) == 10
        assert res_loader.record[0] == int_loader.record[6], \
            f"[{mode}] pulled-but-unstepped batch not replayed"
        assert res_loader.record == control[6:10], \
            (f"[{mode}] DIVERGED: resumed stream "
             f"{res_loader.record} != control {control[6:10]}")

        ctrl_losses = _losses(os.path.join(ctrl, "logs", "f"))
        int_losses = _losses(os.path.join(kill, "logs", "f"))
        res_losses = _losses(os.path.join(kill, "logs", "r"))
        assert len(ctrl_losses) == 10 and len(int_losses) == 6 \
            and len(res_losses) == 4
        assert int_losses + res_losses == ctrl_losses, \
            (f"[{mode}] loss trajectory diverged:\n"
             f"  control  {ctrl_losses}\n"
             f"  stitched {int_losses + res_losses}")
        assert _params_digest(res_state) == _params_digest(ctrl_state), \
            f"[{mode}] final params differ from control"
        print(f"  [{mode}] stream+losses+params bit-identical",
              flush=True)


class _TinyState:
    """Minimal checkpointable state for direct RunCheckpointer drills
    (no training loop needed — save/restore only touch the four array
    fields)."""

    def __init__(self, step):
        self.step = jnp.asarray(step, jnp.int32)
        self.params = {"w": jnp.arange(8, dtype=jnp.float32) * step}
        self.batch_stats = {}
        self.opt_state = {"m": jnp.zeros(8, jnp.float32)}

    def replace(self, **kw):
        import copy
        s = copy.copy(self)
        for k, v in kw.items():
            setattr(s, k, v)
        return s


def drill_async_save(root):
    """Async saves: non-blocking dispatch, commit gating of the
    in-flight step, rollback + resume on an injected commit failure,
    and the train loop's exit barrier."""
    # Integration: the train loop with async checkpointing on completes
    # and its exit barrier commits the final save.
    tcfg, mcfg = _cfg(num_steps=2, async_checkpointing=True)
    state = _run(tcfg, mcfg, root)
    d = os.path.join(root, "ckpts", "drill")
    assert int(state.step) == 2, f"run did not complete: {int(state.step)}"
    assert ckpt_lib.latest_step(d) == 2, "exit barrier did not commit"
    assert _finite(state)

    # Direct: dispatch returns immediately and the in-flight step is
    # invisible until the barrier commits it.
    d2 = os.path.join(root, "direct")
    c = ckpt_lib.RunCheckpointer(d2, async_save=True, save_retries=1,
                                 retry_delay=0.05)
    c.save(_TinyState(1))
    assert c.pending_step == 1, "async save did not stay pending"
    assert c.latest_step() is None, "uncommitted step visible"
    st = c.restore(_TinyState(0))
    assert int(st.step) == 0, "restore observed the in-flight step"
    c.wait_for_pending()
    assert c.latest_step() == 1, "barrier did not commit"

    # Non-blocking proof: dispatch must defer the whole finalize +
    # vote + commit routine to the barrier — the loop keeps stepping
    # (simulated below) while the write runs in background threads.
    finalizes = []
    orig_fin = c._save_with_agreement
    c._save_with_agreement = lambda *a, **kw: (finalizes.append(1),
                                               orig_fin(*a, **kw))[1]
    c.save(_TinyState(2))
    assert not finalizes, "async dispatch ran the finalize inline"
    assert c.pending_step == 2
    work = sum(float(jnp.sum(jnp.ones(64) * i)) for i in range(16))
    assert work > 0                     # steps ran while save in flight
    c.wait_for_pending()
    assert finalizes, "barrier did not finalize"
    c._save_with_agreement = orig_fin
    assert c.latest_step() == 2

    # Injected commit failure past the retry budget: the barrier
    # raises, the torn step is rolled back, resume sees the older one.
    set_injector(FaultInjector(ckpt_commit_errors=8))
    c.save(_TinyState(3))
    try:
        c.wait_for_pending()
    except OSError:
        pass
    else:
        raise AssertionError("commit failure did not surface")
    set_injector(None)
    assert c.latest_step() == 2, \
        f"torn step visible: latest={c.latest_step()}"
    assert not os.path.isdir(os.path.join(d2, "3")), \
        "failed step dir not rolled back"
    st = c.restore(_TinyState(0))
    assert int(st.step) == 2, "resume did not use the committed step"
    c.close()


_MULTIHOST_CHILD = textwrap.dedent("""
    import hashlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ""          # drop inherited topology flags
    os.environ["COORDINATOR_ADDRESS"] = "localhost:%(port)d"
    # Targeted injection, described the way CI would: host 1's commit
    # health check fails past the retry budget (the mid-save host-death
    # simulation); host 0 stays healthy.
    os.environ["RAFT_FAULT_CKPT_COMMIT_ERRORS"] = "8"
    os.environ["RAFT_FAULT_TARGET_PROCESS"] = "1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid = int(sys.argv[1])
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from raft_tpu.parallel.distributed import init_distributed
    init_distributed(num_processes=2, process_id=pid)

    from raft_tpu import checkpoint as ckpt_lib
    from raft_tpu.resilience import (CheckpointCommitError, FaultInjector,
                                     set_injector)

    root = %(root)r
    mesh = Mesh(np.array(jax.devices()), ("d",))
    rep = NamedSharding(mesh, PartitionSpec())

    def garr(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, rep,
                                            lambda idx: x[idx])

    class TinyState:
        def __init__(self, step):
            self.step = garr(np.int32(step))
            self.params = {"w": garr(
                np.arange(8, dtype=np.float32) * step)}
            self.batch_stats = {}
            self.opt_state = {"m": garr(np.zeros(8, np.float32))}
        def replace(self, **kw):
            import copy
            s = copy.copy(self)
            for k, v in kw.items():
                setattr(s, k, v)
            return s

    out = {"pid": pid}
    c = ckpt_lib.RunCheckpointer(root, save_retries=1, retry_delay=0.05)
    set_injector(FaultInjector())         # baseline save is clean
    # Each host checkpoints its own shard cursor alongside the arrays.
    c.save(TinyState(1),
           loader_state={"seed": 0, "epoch": 0, "pos": 8 * (pid + 1)})
    out["baseline_latest"] = c.latest_step()

    # Arm the env-described injection (exercises from_env + targeting).
    set_injector(FaultInjector.from_env())
    torn = False
    try:
        c.save(TinyState(2),
               loader_state={"seed": 0, "epoch": 0, "pos": 999})
    except CheckpointCommitError:
        torn = True
    out["commit_error_raised"] = torn
    set_injector(FaultInjector())

    out["latest_after_tear"] = c.latest_step()
    out["step2_dir_absent"] = not os.path.isdir(
        os.path.join(root, "2"))
    # Torn loader state must roll back WITH the step...
    out["torn_loader_state_absent"] = c.loader_state(2) is None
    st = c.restore(TinyState(0))
    out["restored_step"] = int(jax.device_get(st.step))
    # ...and the committed step must still hold THIS host's cursor.
    ls = c.loader_state(out["restored_step"]) or {}
    out["restored_loader_pos"] = ls.get("pos")
    w = np.asarray(jax.device_get(st.params["w"]))
    out["restored_hash"] = hashlib.sha256(w.tobytes()).hexdigest()

    # Transient one-host blip: one injected failure inside the retry
    # budget — every host retries in lockstep and the step commits.
    set_injector(FaultInjector(ckpt_commit_errors=1, target_process=1))
    c.save(TinyState(3))
    set_injector(FaultInjector())
    out["latest_after_blip"] = c.latest_step()
    c.close()
    print("RESULT " + json.dumps(out), flush=True)
""")


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _scaled_timeout(timeout: int) -> int:
    try:
        load = os.getloadavg()[0] / max(os.cpu_count() or 1, 1)
    except OSError:
        load = 0.0
    return int(timeout * (1.0 + min(3.0, max(0.0, load))))


def drill_multihost_save(root):
    """Two coordinated processes, shared checkpoint dir, one host's
    save killed by targeted injection: both hosts must roll the step
    back, agree on the older committed step and restore bit-identical
    state. A torn step (any host still seeing step 2) fails the drill."""
    import json
    import subprocess

    repo_root = os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..")
    ckpt_root = os.path.join(root, "shared_ckpts")
    os.makedirs(ckpt_root, exist_ok=True)
    code = _MULTIHOST_CHILD % {"port": _free_port(), "root": ckpt_root}
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               [os.path.abspath(repo_root),
                os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep)}
    env.pop("RAFT_FAULT_CKPT_COMMIT_ERRORS", None)
    env.pop("RAFT_FAULT_TARGET_PROCESS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(2)]
    results = {}
    timeout = _scaled_timeout(300)
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(
                "multihost drill child timed out (coordinator hang?)")
        assert p.returncode == 0, f"child failed:\n{out[-3000:]}"
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line:\n{out[-3000:]}"
        r = json.loads(lines[-1][len("RESULT "):])
        results[r["pid"]] = r
    assert set(results) == {0, 1}, f"missing host: {set(results)}"
    for pid, r in results.items():
        assert r["baseline_latest"] == 1, (pid, r)
        assert r["commit_error_raised"], \
            f"host {pid} did not observe the commit failure"
        assert r["latest_after_tear"] == 1, \
            f"TORN STEP: host {pid} sees latest={r['latest_after_tear']}"
        assert r["step2_dir_absent"], \
            f"TORN STEP: failed step dir survived on host {pid}"
        assert r["torn_loader_state_absent"], \
            f"TORN STEP: loader state outlived its step on host {pid}"
        assert r["restored_step"] == 1, (pid, r)
        assert r["restored_loader_pos"] == 8 * (pid + 1), \
            (f"host {pid} restored the wrong shard cursor: "
             f"{r['restored_loader_pos']}")
        assert r["latest_after_blip"] == 3, \
            f"lockstep retry failed on host {pid}: {r}"
    assert results[0]["restored_hash"] == results[1]["restored_hash"], \
        "hosts restored DIFFERENT states from the same committed step"


DRILLS = [
    drill_ckpt_io_errors,
    drill_corrupt_latest_checkpoint,
    drill_corrupt_sample,
    drill_nan_batch,
    drill_nan_divergence_abort,
    drill_preemption_resume,
    drill_resume_exact,
    drill_async_save,
    drill_multihost_save,
]


def _drill_name(fn) -> str:
    return fn.__name__[len("drill_"):].replace("_", "-")


def main(argv=None) -> int:
    by_name = {_drill_name(fn): fn for fn in DRILLS}
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--drill", default="all",
                    choices=["all", *by_name],
                    help="run one drill (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print available drills and exit")
    args = ap.parse_args(argv)
    if args.list:
        for fn in DRILLS:
            doc = (fn.__doc__ or "").strip().split("\n")[0]
            print(f"{_drill_name(fn):28s} {doc}")
        return 0
    selected = DRILLS if args.drill == "all" else [by_name[args.drill]]

    failures = 0
    for drill in selected:
        name = drill.__name__
        set_injector(None)
        with tempfile.TemporaryDirectory(prefix=f"{name}_") as root:
            print(f"=== {name} ===", flush=True)
            try:
                drill(root)
            except Exception:
                failures += 1
                print(f"FAIL {name}", flush=True)
                traceback.print_exc()
            else:
                print(f"PASS {name}", flush=True)
            finally:
                set_injector(None)
    print(f"\n{len(selected) - failures}/{len(selected)} drills passed",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
