"""The train loop two steps deep: step n+1 is dispatched before step n's
metrics are read. What it logs, checkpoints and records is what the
same steps give run one at a time, however the loop ends."""

import jax
import numpy as np
import pytest

from raft_tpu import checkpoint as ckpt_lib
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.optim import make_schedule
from raft_tpu.parallel import (create_train_state, make_mesh,
                               make_train_step, shard_batch)
from raft_tpu.utils import profiling
from raft_tpu.utils.logger import TrainLogger

TH, TW = 32, 48
MCFG = RAFTConfig(small=True, iters=2)


def tcfg_of(**kw):
    return TrainConfig(**dict(dict(name="t", num_steps=6, batch_size=8,
                                   image_size=(TH, TW), iters=2,
                                   val_freq=1000, sum_freq=100), **kw))


def make_batch(k):
    rng = np.random.default_rng(k)
    image = rng.uniform(0, 255, (8, TH, TW, 3)).astype(np.float32)
    return {"image1": image, "image2": np.roll(image, 2, axis=2),
            "flow": np.full((8, TH, TW, 2), 2.0, np.float32),
            "valid": np.ones((8, TH, TW), np.float32)}


class Batches:
    """``n`` distinct batches an epoch, then the end or ``raises``;
    ``on_pull(count)`` runs as each is pulled. ``pulled``: the batch
    numbers handed out, in order."""

    def __init__(self, n, raises=None, on_pull=None):
        self.n, self.raises, self.on_pull = n, raises, on_pull
        self.pulled = []

    def __iter__(self):
        for k in range(self.n):
            self.pulled.append(k)
            if self.on_pull:
                self.on_pull(len(self.pulled))
            yield make_batch(k)
        if self.raises is not None:
            raise self.raises


class Closed(Exception):
    pass


class Recording(TrainLogger):
    """Keeps every step's loss and rate as pushed, in ``pushed``."""

    def __init__(self, log_dir, pushed):
        super().__init__(log_dir, sum_freq=100, tensorboard=False)
        self.pushed = pushed

    def push(self, metrics, lr=None):
        self.pushed.append((float(metrics["loss"]), lr))
        super().push(metrics, lr)


@pytest.fixture
def timer(monkeypatch):
    fresh = profiling.HostStageTimer(ring=4096)
    monkeypatch.setattr(profiling, "_HOST_TIMER", fresh)
    return fresh


@pytest.fixture(scope="module")
def one_at_a_time(compile_cache):
    """Steps 1-6 on batches 0-5, each waited for before the next is
    dispatched: the loss, and the state after it on the host. (With the
    module's compile cache on, the ``train()`` calls of six steps below
    read back the step compiled here.)"""
    tcfg = tcfg_of()
    mesh = make_mesh()
    rng = jax.random.PRNGKey(tcfg.seed)
    with mesh:
        state = create_train_state(rng, RAFT(MCFG), tcfg, (TH, TW),
                                   mesh=mesh)
        step = make_train_step(tcfg, mesh=mesh)
        losses, states = [], [jax.device_get(state)]
        for k in range(6):
            state, metrics = step(state, shard_batch(make_batch(k), mesh),
                                  jax.random.fold_in(rng, 1))
            losses.append(float(metrics["loss"]))
            states.append(jax.device_get(state))
    return losses, states


def run(tmp_path, loader, pushed, **kw):
    """``train()`` over ``loader``; each step's (loss, rate) as logged
    into ``pushed``."""
    from raft_tpu.train import train

    logger = Recording(str(tmp_path / "logs"), pushed)
    try:
        return train(tcfg_of(**kw), MCFG, ckpt_dir=str(tmp_path / "ckpts"),
                     log_dir=str(tmp_path / "logs"), dataloader=loader,
                     logger=logger)
    finally:
        logger.close()


def roots(timer):
    return [s for s in timer.spans() if s.name == "train.step"]


def assert_same_state(got, want):
    got, want = jax.device_get(got), jax.device_get(want)
    for part in ("step", "params", "batch_stats", "opt_state"):
        a, b = jax.tree.leaves(getattr(got, part)), jax.tree.leaves(
            getattr(want, part))
        assert len(a) == len(b), part
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def restored(tmp_path, step):
    fresh = create_train_state(jax.random.PRNGKey(1), RAFT(MCFG), tcfg_of(),
                               (TH, TW))
    return ckpt_lib.restore_checkpoint(str(tmp_path / "ckpts" / "t"), fresh,
                                       step=step)


def test_logged_loss_and_rate_are_those_of_one_step_at_a_time(
        tmp_path, timer, one_at_a_time):
    """Six steps to ``num_steps``: the last is finished, logged and in
    the exit checkpoint."""
    losses, states = one_at_a_time
    pushed = []
    state = run(tmp_path, Batches(6), pushed, num_steps=6)
    schedule = make_schedule(tcfg_of())
    assert [loss for loss, _ in pushed] == losses
    assert [lr for _, lr in pushed] == [float(schedule(n - 1))
                                        for n in range(1, 7)]
    assert_same_state(state, states[6])
    assert_same_state(restored(tmp_path, 6), states[6])
    # two deep: every root but the first (which dispatched its own step)
    # and the last (nothing past num_steps) had the next step queued
    assert [(r.unit, r.args["complete"], r.args["ahead"])
            for r in roots(timer)] == [(n, 1, int(1 < n < 6))
                                       for n in range(1, 7)]


def test_a_loader_that_raises_after_three_batches(tmp_path, timer,
                                                  one_at_a_time):
    """The window of a benchmark cell closes so: step 3 is in flight
    when the fourth fetch raises. The root that asked began no step and
    closes incomplete; step 3 ends complete and logged under a root of
    its own, then the exception goes on, with no exit checkpoint."""
    losses, _ = one_at_a_time
    pushed = []
    with pytest.raises(Closed):
        run(tmp_path, Batches(3, raises=Closed()), pushed)
    assert [loss for loss, _ in pushed] == losses[:3]
    found = roots(timer)
    assert [(r.unit, r.args["complete"], r.args["ahead"])
            for r in found] == [(1, 1, 0), (2, 1, 1), (3, 0, 0), (3, 1, 0)]
    assert found[2].start_ns + found[2].dur_ns <= found[3].start_ns
    kids = [[s.name for s in timer.spans()
             if s.parent == root.id and s.name != "gc.pass"]
            for root in found[2:]]
    assert kids == [["train.loader_wait"], ["train.device_wait",
                                            "train.metrics_fetch",
                                            "train.log"]]
    assert not (tmp_path / "ckpts" / "t").exists() or not any(
        (tmp_path / "ckpts" / "t").iterdir())


def test_an_exhausted_loader_drains_the_loop(tmp_path, timer,
                                            one_at_a_time):
    """An epoch of three, then the second epoch's first two: the end of
    the first finishes step 3 with nothing dispatched past it, and the
    second epoch starts two deep anew."""
    losses, _ = one_at_a_time
    loader, pushed = Batches(3), []
    run(tmp_path, loader, pushed, num_steps=5)
    assert loader.pulled == [0, 1, 2, 0, 1]
    assert [(r.unit, r.args["complete"], r.args["ahead"])
            for r in roots(timer)] == [(1, 1, 0), (2, 1, 1), (3, 1, 0),
                                       (4, 1, 0), (5, 1, 0)]
    assert len(pushed) == 5 and [loss for loss, _ in pushed][:3] == losses[:3]


def test_preemption_finishes_the_step_in_flight(tmp_path, timer,
                                                one_at_a_time, monkeypatch):
    """A SIGTERM while batch 3 is pulled: batch 3 is not dispatched,
    step 2 (in flight) is finished and logged, and the checkpoint holds
    the state after exactly step 2."""
    import raft_tpu.train as train_mod

    losses, states = one_at_a_time
    guards = []

    class SpyGuard(train_mod._PreemptionGuard):
        def __init__(self):
            super().__init__()
            guards.append(self)

    def signal(count):
        if count == 3:
            guards[0].requested = True

    monkeypatch.setattr(train_mod, "_PreemptionGuard", SpyGuard)
    loader, pushed = Batches(6, on_pull=signal), []
    state = run(tmp_path, loader, pushed, num_steps=6)
    assert loader.pulled == [0, 1, 2]
    assert [(r.unit, r.args["complete"], r.args["ahead"])
            for r in roots(timer)] == [(1, 1, 0), (2, 1, 0)]
    assert [loss for loss, _ in pushed] == losses[:2]
    assert_same_state(state, states[2])
    assert ckpt_lib.latest_step(str(tmp_path / "ckpts" / "t")) == 2
    assert_same_state(restored(tmp_path, 2), states[2])


def test_a_val_freq_boundary_sees_the_state_after_its_step(
        tmp_path, timer, one_at_a_time, monkeypatch):
    """Checkpoint and validation at step 2 get the state after exactly
    step 2: step 3 is not dispatched before they are done."""
    import raft_tpu.train as train_mod

    _, states = one_at_a_time
    seen = []

    def validation(tcfg, model, state, batch, *args):
        seen.append((jax.device_get(state), len(loader.pulled)))

    monkeypatch.setattr(train_mod, "_panels_and_validation", validation)
    loader = Batches(6)
    run(tmp_path, loader, [], num_steps=3, val_freq=2)
    ((at_boundary, pulled),) = seen
    assert pulled == 2
    assert_same_state(at_boundary, states[2])
    assert_same_state(restored(tmp_path, 2), states[2])
    assert_same_state(restored(tmp_path, 3), states[3])
    assert [r.args["ahead"] for r in roots(timer)] == [0, 0, 0]
